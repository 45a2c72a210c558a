"""Single-token decode attention Pallas TPU kernel.

The serving hot spot: one new query per request against a long KV cache
(the ``decode_32k`` / ``long_500k`` dry-run cells).  Decode attention is
purely memory-bound — arithmetic intensity ~= 1 FLOP/byte — so the kernel
is organized around *streaming* the KV cache HBM->VMEM once at full
bandwidth, the exact regime the ELK paper's preload engine targets (the KV
cache is the paper's canonical memory-intensive operator, §3.2).

Grid: (B, Hkv, C/bk), KV innermost with the online-softmax (max, sum, acc)
carry in VMEM scratch.  The GQA q-group (G = Hq/Hkv queries sharing one KV
head) rides along the row axis of the score tile, so the MXU sees a
(G, bk) matmul per step instead of G vector products.

Ring-buffer masking: ``slot_pos`` (absolute position per cache slot,
sentinel for unwritten slots) streams with the same block index; causal +
window predicates are evaluated against the scalar-prefetched query
position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bk: int, window: int,
                   scale: float, kv_steps: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)
    spos = pos_ref[...]                            # (1, bk) int32
    q_pos = qpos_ref[0]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G,bk)
    ok = spos <= q_pos
    if window:
        ok &= spos > q_pos - window
    s = jnp.where(ok, s, _NEG_INF)                 # (1,bk) broadcasts over G

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == kv_steps - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     slot_pos: jax.Array, q_pos: jax.Array, *,
                     window: int = 0, bk: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); caches: (B, Hkv, C, D); slot_pos: (C,) int32;
    q_pos: scalar int32.  Returns (B, Hq, D)."""
    b, hq, d = q.shape
    hkv, c = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    bk = min(bk, c)
    assert c % bk == 0, (c, bk)
    kv_steps = c // bk
    scale = d ** -0.5

    qg = q.reshape(b, hkv, g, d)
    pos2d = slot_pos.reshape(1, c)
    qpos_arr = jnp.asarray(q_pos, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, bk=bk, window=window,
                               scale=scale, kv_steps=kv_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bb, h, j, *_: (bb, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, j, *_: (bb, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, j, *_: (bb, h, j, 0)),
            pl.BlockSpec((1, bk), lambda bb, h, j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bb, h, j, *_: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qpos_arr, qg, k_cache, v_cache, pos2d)
    return out.reshape(b, hq, d)
