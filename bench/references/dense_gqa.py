"""Plain float32 forward of a dense GQA decoder: Llama / Mistral / Qwen3.

Written from the published description, with nothing of the program:
pre-norm residual layers, RMS norm, rotary embeddings on the two halves
of each head (``rotate_half``), grouped-query causal attention (with a
sliding window where the config has one: a query at ``i`` sees keys
``j`` with ``i - window < j <= i``), Qwen3's per-head RMS norm on q and k
before the rotation, a SwiGLU MLP, a final norm and an untied output head.

The weights are drawn again here from the run's seed (``bench/weights.py``)
one layer at a time and widened to float32; every matrix product runs at
``highest`` precision.  A sequence is one row of activations, padded to a
bucket of ``BUCKET`` tokens (causal attention never lets a padded tail
reach an earlier position), and attention runs over ``QBLOCK`` queries at
a time, so the whole check fits one chip beside nothing else.

With ``controls`` it also runs the same forward in a precision below
bfloat16, the step that a later change could be tempted to take, beside
the float32 one (``CONTROLS``):

* ``fp8_matmul``: the inputs of every product with a weight matrix are
  rounded to float8_e4m3fn (one absmax scale per output channel of the
  weight and per row of the activation), the products accumulate in
  float32 and everything else stays float32: what an fp8 weight path in
  the program would compute;
* ``int8_matmul``: the same in int8 (W8A8);
* ``fp8``: every tensor the program holds in bfloat16 (weights, the
  residual stream and every activation between operations, the KV ring,
  attention probabilities, the logits) is held in fp8 instead.

``bf16`` is a witness, not a control: the plain forward with every
tensor held in bfloat16, as the program holds it, to tell a token that
bfloat16 itself computes badly from a fault of the program's path.

``token_plus_one`` is a fault, not a precision: the float32 forward put
in the program's place, with every third token it puts first moved to
the next vocabulary entry (a token altered where it is produced).
"""

from __future__ import annotations

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights as W

QBLOCK = 512
BUCKET = 2048
ROWS = 256


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _round(x, axis, kind="fp8", amax=None):
    """Round ``x`` to fp8 (float8_e4m3fn) or int8 with one absmax scale
    along ``axis`` (or the ``amax`` given) mapped to the type's largest
    value, and widen it back to float32."""
    if amax is None:
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if kind == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if kind == "int8":
        s = jnp.maximum(amax, 1e-12) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    s = jnp.maximum(amax, 1e-12) / 448.0
    # float8_e4m3fn has no infinity: a value the division leaves a hair
    # above 448 would round to NaN
    x = jnp.clip(x / s, -448.0, 448.0)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


CONTROLS = ("fp8_matmul", "int8_matmul", "fp8", "token_plus_one")
FAULTS = ("token_plus_one",)


def _kind(mode) -> str:
    return {"int8_matmul": "int8", "bf16": "bf16"}.get(mode, "fp8")


def _quant(w, kind):
    """Weights: one scale per output column of each matrix (in, out).
    Vectors stay."""
    return jax.tree.map(lambda a: _round(a, 0, kind) if a.ndim == 2 else a, w)


def _ops(mode):
    """How a forward in ``mode`` keeps a tensor the program holds in
    bfloat16 (``keep``), and how it multiplies an activation by a weight
    matrix (``mm``): float32 throughout for the reference (``None``)."""
    if mode in ("fp8", "bf16"):
        return (lambda t: _round(t, -1, _kind(mode))), (lambda x, w: x @ w)
    if mode in ("fp8_matmul", "int8_matmul"):
        return (lambda t: t), (lambda x, w: _round(x, -1, _kind(mode)) @ w)
    return (lambda t: t), (lambda x, w: x @ w)


def _layer(h, w, hf, mode=None):
    """One decoder layer over one padded sequence h: (S, d) float32, in
    float32 or in a control's ``mode`` (weights already rounded)."""
    r, mm = _ops(mode)
    z = W.dims(hf)
    s = h.shape[0]
    nh, nkv, hd = z["h"], z["kv"], z["hd"]
    eps = float(hf["rms_norm_eps"])
    window = W.window(hf)
    pos = jnp.arange(s)

    x = r(_rms(h, 1.0 + w["attn_norm"], eps))
    q = r(mm(x, w["q"])).reshape(s, nh, hd)
    k = r(mm(x, w["k"])).reshape(s, nkv, hd)
    v = r(mm(x, w["v"])).reshape(s, nkv, hd)
    if "q_norm" in w:
        q = _rms(q, 1.0 + w["q_norm"], eps)
        k = _rms(k, 1.0 + w["k_norm"], eps)
    q = r(_rope(q, pos, float(hf["rope_theta"])))
    k = r(_rope(k, pos, float(hf["rope_theta"])))
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)

    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * hd ** -0.5
        ok = pos[None, :] <= qpos[:, None]
        if window:
            ok &= pos[None, :] > qpos[:, None] - window
        scores = jnp.where(ok[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(scores, -1)), v)

    att = r(lax.map(block, jnp.arange(s // QBLOCK)).reshape(s, nh * hd))
    h = r(h + r(mm(att, w["o"])))
    x = r(_rms(h, 1.0 + w["mlp_norm"], eps))
    mid = r(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]))
    return r(h + r(mm(mid, w["down"])))


def _logits(h, outer, rows, hf, mode=None):
    """Logits at ``rows`` of h."""
    r, mm = _ops(mode)
    x = r(_rms(h[rows], 1.0 + outer["final_norm"], float(hf["rms_norm_eps"])))
    return r(mm(x, outer["lm_head"]))


def _gap(logits, tokens):
    """How far each row's logit of ``tokens`` lies below the row's best."""
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return jnp.max(logits, -1) - got


@functools.lru_cache(maxsize=4)
def _programs(hf_json: str) -> types.SimpleNamespace:
    """The jitted pieces for one configuration, built once per process.
    The seed's key is an argument, never a constant: one program for every
    seed, and for every layer."""
    hf = json.loads(hf_json)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    return types.SimpleNamespace(
        outer=jax.jit(lambda b: f32(W.outer_weights(b, hf))),
        layer=jax.jit(lambda b, i: f32(W.layer_weights(b, i, hf))),
        step=jax.jit(lambda h, w, mode: _layer(h, w, hf, mode),
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
    outer = p.outer(base)
    embed = outer.pop("embed")
    hs = [p.embed(embed, t, mode) for t in padded]
    del embed
    for i in range(W.dims(hf)["layers"]):
        w = p.layer(base, i)
        if mode is not None:
            w = p.quant(w, _kind(mode))
        hs = [p.step(h, w, mode) for h in hs]
        del w
    return hs, outer if mode is None else p.quant(outer, _kind(mode))


def served_gaps(hf: dict, seed: int, seqs, idx, *, controls=()):
    """The reference over each ``(tokens, first)`` in ``seqs``: the whole
    served sequence and the index of its first served token.

    Returns ``{"reference": [(gap, at), ...]}``, one pair per sequence: the
    gap by which each served token's logit lies below the best, and the
    logits at the vocabulary entries ``idx`` (served tokens x entries).
    Each mode of ``controls`` adds its own list, the same forward in that
    precision put in the program's place: the gap of the token it puts
    first at each of the same positions (below the float32 best), and its
    logits at ``idx``.  One precision's states are on the chip at a time."""
    base = W.seed_key(seed)
    p = _programs(json.dumps(hf, sort_keys=True))
    idx = jnp.asarray(idx)
    padded, rows = [], []
    for tokens, first in seqs:
        n = -(-len(tokens) // BUCKET) * BUCKET
        padded.append(jnp.asarray(np.pad(np.asarray(tokens, np.int32),
                                         (0, n - len(tokens)))))
        # rows padded to a multiple of ROWS (the last one repeated), so few
        # shapes compile; the padding is cut off again
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
