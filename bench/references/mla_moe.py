"""Plain float32 forward of the DeepSeek-V3 layer equations (Kimi-K2) at
one chip's share of its experts.

Written from the published description (transformers'
``modeling_deepseek.py`` as Kimi-K2 ships it), with nothing of the
program: pre-norm residual layers with RMS norms; latent attention with a
low-rank query (``q_a``, its norm, ``q_b``) and a joint projection to the
latent and one rope key shared by every head (``kv_a``), the latent
normed and expanded per head to keys and values (``kv_b``), computed
expanded (the program computes it absorbed); yarn rope on the rope dims
(``yarn_find_correction_range``, ``yarn_linear_ramp_mask``,
``yarn_get_mscale``), after de-interleaving each pair as
``apply_rotary_pos_emb`` does; the softmax scale times yarn's mscale
squared; the first layers a dense SwiGLU, the rest ``noaux_tc`` routing
(float32 sigmoid scores, the top k of scores plus the selection bias,
their scores renormalised and scaled) and one shared expert.

The share: the router scores every expert, and only the experts
``bench/weights_mla_moe.py`` holds here add their part, as in the
program; what the absent experts would add is left out on both sides.

The weights are drawn again here from the run's seed one layer at a
time and widened to float32; every product runs at ``highest``
precision.  Sequences are padded to a bucket of ``BUCKET`` tokens and
attention runs over ``QBLOCK`` queries at a time, so the check fits one
chip.  The controls are ``bench/references/dense_gqa.py``'s, the same
forward in a lower precision (``fp8_matmul``, ``int8_matmul``, ``fp8``),
the ``bf16`` witness and the ``token_plus_one`` fault.
"""

from __future__ import annotations

import functools
import json
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights as W
from bench import weights_mla_moe as WM
from bench.references.dense_gqa import (BUCKET, CONTROLS, FAULTS, QBLOCK,
                                        ROWS, _gap, _kind, _logits, _ops,
                                        _round)

__all__ = ["CONTROLS", "served_gaps"]


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(hf: dict) -> tuple[np.ndarray, float]:
    """The rope dims' inverse frequencies (rope / 2,) and the factor on
    cos and sin, as ``DeepseekV3YarnRotaryEmbedding`` sets them."""
    rs, dim, base = hf["rope_scaling"], hf["qk_rope_head_dim"], hf[
        "rope_theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / dim))
    keep = 1.0 - ramp
    inv = inter * (1 - keep) + extra * keep
    return inv.astype(np.float32), (_mscale(factor, rs["mscale"])
                                    / _mscale(factor, rs["mscale_all_dim"]))


def softmax_scale(hf: dict) -> float:
    rs = hf["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, pos, hf):
    """x: (S, [H,] rope): pairs (x[2i], x[2i+1]) laid out as two halves,
    then each half rotated with ``rotate_half``."""
    inv, m = yarn(hf)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, w, hf, mode=None):
    """The residual stream after one layer's latent attention."""
    r, mm = _ops(mode)
    z = WM.dims(hf)
    s, nh, nope, rope, dv = h.shape[0], z["h"], z["nope"], z["rope"], z["dv"]
    eps = float(hf["rms_norm_eps"])
    pos = jnp.arange(s)

    x = r(_rms(h, 1.0 + w["attn_norm"], eps))
    qa = r(_rms(r(mm(x, w["q_a"])), 1.0 + w["q_a_norm"], eps))
    q = r(mm(qa, w["q_b"])).reshape(s, nh, nope + rope)
    kv = r(mm(x, w["kv_a"]))
    latent = r(_rms(kv[:, :z["rkv"]], 1.0 + w["kv_a_norm"], eps))
    kvb = r(mm(latent, w["kv_b"])).reshape(s, nh, nope + dv)
    k_pe = r(_rope(kv[:, z["rkv"]:], pos, hf))
    q = jnp.concatenate([q[..., :nope], r(_rope(q[..., nope:], pos, hf))], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, None], (s, nh, rope))], -1)
    v = kvb[..., nope:]
    scale = softmax_scale(hf)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        scores = jnp.where(pos[None, None, :] <= qpos[None, :, None],
                           scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)), v)

    att = r(lax.map(block, jnp.arange(s // QBLOCK)).reshape(s, nh * dv))
    return r(h + r(mm(att, w["o"])))


def _swiglu(x, w, mm, r):
    return mm(r(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"])), w["down"])


def _dense(h, w, hf, mode=None):
    r, mm = _ops(mode)
    x = r(_rms(h, 1.0 + w["mlp_norm"], float(hf["rms_norm_eps"])))
    return r(h + r(_swiglu(x, w, mm, r)))


def route(x, w, hf, mm=lambda x, w: x @ w):
    """``noaux_tc``: each token's weight on each held expert (S, held),
    zero where it is not among the token's top k."""
    z = WM.dims(hf)
    scores = jax.nn.sigmoid(mm(x, w["router"]))
    _, idx = lax.top_k(scores + w["router_bias"], z["k"])
    top = jnp.take_along_axis(scores, idx, -1)
    if hf["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * hf["routed_scaling_factor"]
    held = jnp.arange(z["offset"], z["offset"] + z["held"])
    return jnp.einsum("sk,ske->se", top,
                      (idx[..., None] == held).astype(jnp.float32))


def _moe(h, w, hf, mode=None):
    r, mm = _ops(mode)
    x = r(_rms(h, 1.0 + w["mlp_norm"], float(hf["rms_norm_eps"])))
    gates = route(x, w, hf, mm)

    def expert(y, e):
        g, ew = e
        return y + g[:, None] * r(_swiglu(x, ew, mm, r)), None

    y, _ = lax.scan(expert, jnp.zeros_like(x),
                    (gates.T, {k: w[k] for k in ("gate", "up", "down")}))
    return r(h + r(r(y) + r(_swiglu(x, w["shared"], mm, r))))


def _quant(w, kind):
    """Weights: one scale per output column of each matrix (in, out), and
    of each expert's.  Vectors stay."""
    return jax.tree.map(lambda a: _round(a, -2, kind) if a.ndim >= 2 else a,
                        w)


@functools.lru_cache(maxsize=4)
def _programs(hf_json: str) -> types.SimpleNamespace:
    """The jitted pieces for one configuration, built once per process.
    The seed's key is an argument, never a constant: one program for every
    seed, and for every layer."""
    hf = json.loads(hf_json)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    return types.SimpleNamespace(
        outer=jax.jit(lambda b: f32(WM.outer_weights(b, hf))),
        attn_w=jax.jit(lambda b, i: f32(WM.attn_weights(b, i, hf))),
        dense_w=jax.jit(lambda b, i: f32(WM.dense_weights(b, i, hf))),
        moe_w=jax.jit(lambda b, i: f32(WM.moe_weights(b, i, hf))),
        attn=jax.jit(lambda h, w, mode: _attention(h, w, hf, mode),
                     static_argnums=2),
        dense=jax.jit(lambda h, w, mode: _dense(h, w, hf, mode),
                      static_argnums=2),
        moe=jax.jit(lambda h, w, mode: _moe(h, w, hf, mode),
                    static_argnums=2),
        quant=jax.jit(_quant, static_argnums=1),
        embed=jax.jit(lambda e, t, mode: _round(
            jnp.take(e, t, axis=0), 0, "fp8",
            jnp.max(jnp.abs(e), axis=0, keepdims=True)) if mode == "fp8"
            else jnp.take(e, t, axis=0), static_argnums=2),
        logits=jax.jit(lambda h, o, r, mode: _logits(h, o, r, hf, mode),
                       static_argnums=3),
        gap=jax.jit(_gap))


def _forward(p, base, hf, padded, mode):
    """Hidden states of every padded sequence after the last layer, and
    the output weights, in float32 or in ``mode``."""
    z = WM.dims(hf)
    outer = p.outer(base)
    embed = outer.pop("embed")
    hs = [p.embed(embed, t, mode) for t in padded]
    del embed

    def weights(w):
        return w if mode is None else p.quant(w, _kind(mode))

    for i in range(z["layers"]):
        a = weights(p.attn_w(base, i))
        hs = [p.attn(h, a, mode) for h in hs]
        dense = i < z["dense"]
        w = weights(p.dense_w(base, i) if dense else p.moe_w(base, i))
        w["mlp_norm"] = a["mlp_norm"]
        del a
        hs = [(p.dense if dense else p.moe)(h, w, mode) for h in hs]
        del w
    return hs, outer if mode is None else p.quant(outer, _kind(mode))


def served_gaps(hf: dict, seed: int, seqs, idx, *, controls=()):
    """The reference over each ``(tokens, first)`` in ``seqs``, as
    ``bench/references/dense_gqa.py``'s ``served_gaps`` documents it: per
    sequence the gap below the best of each served token and the logits at
    the entries ``idx``, for the float32 forward and for each control."""
    base = W.seed_key(seed)
    p = _programs(json.dumps(hf, sort_keys=True))
    idx = jnp.asarray(idx)
    padded, rows = [], []
    for tokens, first in seqs:
        n = -(-len(tokens) // BUCKET) * BUCKET
        padded.append(jnp.asarray(np.pad(np.asarray(tokens, np.int32),
                                         (0, n - len(tokens)))))
        n = len(tokens) - first
        pad = -(-n // ROWS) * ROWS
        r = jnp.asarray(np.minimum(np.arange(first - 1, first - 1 + pad),
                                   len(tokens) - 2))
        rows.append((r, jnp.asarray(np.asarray(tokens, np.int32))[r + 1], n))
    out = {m: [] for m in ("reference",) + tuple(controls)}
    picks = {}
    with jax.default_matmul_precision("highest"):
        for m in controls:
            if m in FAULTS:
                continue
            hs, head = _forward(p, base, hf, padded, m)
            for j, (r, _, n) in enumerate(rows):
                lg = p.logits(hs[j], head, r, m)
                picks[m, j] = (jnp.argmax(lg, -1), np.asarray(lg[:, idx])[:n])
            del hs, head
        hs, head = _forward(p, base, hf, padded, None)
        for j, (r, served, n) in enumerate(rows):
            ref = p.logits(hs[j], head, r, None)
            at = np.asarray(ref[:, idx])[:n]
            out["reference"].append((np.asarray(p.gap(ref, served))[:n], at))
            for m in controls:
                if m == "token_plus_one":
                    pick = jnp.argmax(ref, -1)
                    pick = jnp.where(jnp.arange(len(r)) % 3 == 2,
                                     (pick + 1) % ref.shape[-1], pick)
                    lg_at = at
                else:
                    pick, lg_at = picks[m, j]
                out[m].append((np.asarray(p.gap(ref, pick))[:n], lg_at))
    return out
