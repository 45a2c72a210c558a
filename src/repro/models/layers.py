"""Model-layer primitives shared by every architecture in the zoo.

All functions are pure (params-in, activations-out), bf16-activation /
fp32-accumulation, and written so XLA GSPMD can shard them along the
(data, model) mesh axes declared in ``repro.distributed.sharding``:

* weights are einsum'd on their natural contraction axes (no reshapes that
  would break sharding propagation through the model axis),
* attention keeps a ``(batch, heads, seq, head_dim)`` layout with heads as
  the model-sharded axis,
* normalizations and softmax accumulate in fp32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.config import ModelConfig, Yarn


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_find_correction_range(low_rot: float, high_rot: float, dim: int,
                               base: float, max_pos: int) -> tuple[int, int]:
    """The rope dims between which yarn ramps from interpolated to
    original frequencies: those that turn ``low_rot`` / ``high_rot`` times
    over ``max_pos`` positions."""
    def corr(rot):
        return dim * math.log(max_pos / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    return max(math.floor(corr(low_rot)), 0), min(math.ceil(corr(high_rot)),
                                                 dim - 1)


def yarn_linear_ramp_mask(lo: float, hi: float, n: int) -> np.ndarray:
    if lo == hi:
        hi += 0.001          # as the source: no division by zero
    return np.clip((np.arange(n, dtype=np.float32) - lo) / (hi - lo), 0, 1)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                yarn: Optional[Yarn] = None) -> tuple[jax.Array, jax.Array]:
    """(sin, cos) tables for integer ``positions`` (any leading shape).
    With ``yarn``, dims past the correction range take their frequency
    divided by the factor (at every position), and both tables are scaled
    by the ratio of the two mscales."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    scale = 1.0
    if yarn is not None:
        lo, hi = yarn_find_correction_range(yarn.beta_fast, yarn.beta_slow,
                                            head_dim, theta,
                                            yarn.original_max)
        keep = 1.0 - yarn_linear_ramp_mask(lo, hi, half)
        freqs = freqs / yarn.factor * (1.0 - keep) + freqs * keep
        scale = (yarn_get_mscale(yarn.factor, yarn.mscale)
                 / yarn_get_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., half)
    if scale == 1.0:
        return jnp.sin(ang), jnp.cos(ang)
    return jnp.sin(ang) * scale, jnp.cos(ang) * scale


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, H, S, D); sin/cos: (B, S, D/2) or (S, D/2)."""
    if sin.ndim == 2:
        sin = sin[None, None, :, :]
        cos = cos[None, None, :, :]
    else:
        sin = sin[:, None, :, :]
        cos = cos[:, None, :, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static attention behaviour derived from a ModelConfig."""
    num_heads: int
    num_kv_heads: int
    head_dim: int
    causal: bool = True
    sliding_window: int = 0      # 0 = full
    qk_norm: bool = False
    softmax_scale: Optional[float] = None

    @property
    def scale(self) -> float:
        return self.softmax_scale or self.head_dim ** -0.5


def attn_mask_bias(spec: AttnSpec, q_pos: jax.Array, k_pos: jax.Array,
                   ) -> jax.Array:
    """Additive fp32 bias (Q, K): 0 where attendable, -inf where masked.

    q_pos/k_pos are absolute token positions, so the same code serves
    prefill (q_pos == k_pos grid) and decode (single q position against a
    cache whose live region is position-tagged)."""
    dq, dk = q_pos[:, None], k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if spec.causal:
        ok &= dk <= dq
    if spec.sliding_window:
        ok &= dk > dq - spec.sliding_window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  bias: Optional[jax.Array], spec: AttnSpec) -> jax.Array:
    """Reference GQA attention.

    q: (B, Hq, Sq, D);  k/v: (B, Hkv, Sk, D);  bias: (Sq, Sk) or None.
    Grouped heads are folded by reshaping q to (B, Hkv, G, Sq, D) so the
    kv tensors are never materialized per-q-head (GQA's entire point)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    logits *= spec.scale
    if bias is not None:
        logits = logits + bias
    # rows that are fully masked (e.g. cache slots beyond the window) must
    # not produce NaNs: max-subtract with a -inf guard.
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs.astype(v.dtype), v)
    return out.reshape(b, hq, sq, d)


def chunked_gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          spec: AttnSpec, q_pos: jax.Array,
                          k_pos: jax.Array, *, chunk: int = 512,
                          unroll: bool = False, mesh=None) -> jax.Array:
    """Memory-bounded attention: q is processed in chunks so the live score
    tile is (..., chunk, Sk) instead of (..., Sq, Sk) — the XLA-lowering
    stand-in for the Pallas flash kernel (which replaces it on real TPU).

    Each chunk's q-seq dim is constrained onto the ``model`` mesh axis
    (sequence-parallel attention): head counts like 40 or kv=8 never
    divide a 16-way axis, a seq split always does.

    ``unroll=True`` uses a Python loop (dry-run accounting variants: XLA's
    cost model counts a scan body once; an unrolled loop is counted fully).
    """
    b, hq, sq, d = q.shape

    def constrain_seq(t):
        if mesh is None or "model" not in getattr(mesh, "shape", {}):
            return t
        m = mesh.shape["model"]
        if t.shape[2] % m:
            return t
        from jax.sharding import NamedSharding, PartitionSpec
        bp = tuple(ax for ax in ("pod", "data") if ax in mesh.shape)
        dp = 1
        for ax in bp:
            dp *= mesh.shape[ax]
        b_ax = bp if t.shape[0] % max(dp, 1) == 0 else None
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh, PartitionSpec(b_ax, None, "model", None)))

    if chunk <= 0 or sq <= chunk:
        bias = attn_mask_bias(spec, q_pos, k_pos)
        return gqa_attention(constrain_seq(q), k, v, bias, spec)
    n = -(-sq // chunk)
    pad = n * chunk - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad), constant_values=2 ** 30)
    qc = q.reshape(b, hq, n, chunk, d)
    pc = q_pos.reshape(n, chunk)

    def one(qi, pi):
        bias = attn_mask_bias(spec, pi, k_pos)
        return gqa_attention(constrain_seq(qi), k, v, bias, spec)

    if unroll:
        outs = [one(qc[:, :, i], pc[i]) for i in range(n)]
        out = jnp.stack(outs, axis=2)
    else:
        def body(_, xs):
            qi, pi = xs
            return None, one(qi, pi)
        _, out = jax.lax.scan(
            body, None, (jnp.moveaxis(qc, 2, 0), pc))
        out = jnp.moveaxis(out, 0, 2)
    out = out.reshape(b, hq, n * chunk, d)
    return out[:, :, :sq, :]


def qk_head_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Per-head RMS norm on q/k (qwen3). x: (B, H, S, D), scale: (D,)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Projections & MLP
# ---------------------------------------------------------------------------

def linear(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None
           ) -> jax.Array:
    out = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out


def mlp(x: jax.Array, p: dict, cfg: ModelConfig, mesh=None) -> jax.Array:
    """MLP block on the fused runtime path (kernels/fused_mlp).

    On TPU (or under ``force_kernels``) the whole up-proj -> activation ->
    down-proj chain runs as one Pallas grid with the intermediate staged
    in VMEM — the runtime twin of the FusedOp the fusion pass hands the
    scheduler; on a multi-device ``mesh`` it runs once per shard.  The CPU
    fallback is the exact einsum composition this function used before
    fusion existed, so outputs are unchanged."""
    from repro.kernels.fused_mlp.ops import fused_mlp
    if cfg.gated_mlp:
        return fused_mlp(x, p["w_up"], p["w_down"], w_gate=p["w_gate"],
                         act=cfg.mlp_act, mesh=mesh)
    return fused_mlp(x, p["w_up"], p["w_down"], b_up=p.get("b_up"),
                     b_down=p.get("b_down"), act=cfg.mlp_act, mesh=mesh)


def mlp_params(rng, cfg: ModelConfig, d: int, ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = d ** -0.5
    s_ff = ff ** -0.5
    if cfg.gated_mlp:
        return {
            "w_gate": jax.random.normal(k1, (d, ff), dtype) * s_in,
            "w_up": jax.random.normal(k2, (d, ff), dtype) * s_in,
            "w_down": jax.random.normal(k3, (ff, d), dtype) * s_ff,
        }
    p = {
        "w_up": jax.random.normal(k1, (d, ff), dtype) * s_in,
        "w_down": jax.random.normal(k2, (ff, d), dtype) * s_ff,
    }
    if cfg.qkv_bias:   # opt-style fc biases travel with qkv_bias configs
        p["b_up"] = jnp.zeros((ff,), dtype)
        p["b_down"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None,
                 z_loss: float = 0.0) -> jax.Array:
    """Mean token cross-entropy in fp32; optional z-loss regularizer."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * lse ** 2
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
