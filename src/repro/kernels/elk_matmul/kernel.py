"""ELK-blocked matmul Pallas TPU kernel.

The chip-level ELK realization (DESIGN.md §3B): VMEM is the ICCA "on-chip
SRAM", HBM the "off-chip memory", and the Pallas grid pipeline is exactly
the paper's double buffer — the (bm, bn) output tile + (bm, bk)/(bk, bn)
operand tiles are the *execution space*; the pipeline's prefetched next
blocks are the *preload space*.  ``core/integration.vmem_plan()`` picks
(bm, bn, bk) by running the paper's cost-aware allocation against the VMEM
budget, trading larger K blocks (fewer accumulator flushes, more reuse)
against deeper HBM prefetch.

Grid is (M/bm, N/bn, K/bk) with the K axis innermost: the fp32 accumulator
lives in VMEM scratch across K steps and the output tile is written once —
one HBM write per tile, the ELK "execute-state" residency.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def elk_matmul(x: jax.Array, y: jax.Array, *, bm: int = 256, bn: int = 256,
               bk: int = 512, interpret: bool = False) -> jax.Array:
    """(M, K) @ (K, N) -> (M, N), fp32 accumulate, dtype-of-x output.

    Block sizes must divide the padded operand shapes; operands are padded
    up to block multiples (zero padding is exact for matmul)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)

    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // bk) * bk
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        y = jnp.pad(y, ((0, kp - k), (0, np_ - n)))

    out = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, y)
    return out[:m, :n]
