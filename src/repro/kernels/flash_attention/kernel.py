"""Flash attention Pallas TPU kernel (causal, GQA, sliding-window).

ELK view (DESIGN.md §3B): KV blocks stream HBM->VMEM exactly like the
paper's operator preloads — the (bq, D) query tile and (bq, bk) score tile
are the *execution space*, the in-flight KV blocks the *preload space*.
The online-softmax running (max, sum) carry is what lets the KV "preload"
depth stay O(1) in sequence length.

Grid: (B, Hq, S/bq, S/bk) with the KV axis innermost.  Causal + window
pruning is done twice: whole blocks that cannot contribute are masked via
a cheap block-level predicate (the index map still walks them — Mosaic
skips the body under ``pl.when``), and the diagonal blocks get an exact
element mask from iota.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, causal: bool, window: int, scale: float,
                  kv_steps: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * bq                    # first q position of this block
    k_lo = kj * bk
    # block-level prune: any (q, k) pair in range?
    live = True
    if causal:
        live = jnp.logical_and(live, k_lo <= q_lo + bq - 1)
    if window:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok &= kpos <= qpos
        if window:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, _NEG_INF)

        m_prev = m_ref[...]                          # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
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


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    scale = d ** -0.5
    kv_steps = s // bk

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, causal=causal, window=window,
        scale=scale, kv_steps=kv_steps)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, s // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, j, g=g: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, j, g=g: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, h, i, j: (bb, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
