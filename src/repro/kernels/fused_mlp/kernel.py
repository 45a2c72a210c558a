"""Fused MLP (up-proj -> activation -> down-proj) Pallas TPU kernel.

The kernel-level realization of the inter-core fusion pass
(``core/fusion.py``, DESIGN.md §8): the whole MLP chain runs in ONE grid,
so the intermediate activation ``h = act(x @ w_up)`` never round-trips
through HBM — it lives in VMEM for exactly one grid step, the Pallas
analogue of the ICCA chip staging the intermediate in aggregate SRAM.

Grid is (M/bm, FF/bf) with the FF axis innermost ("arbitrary"): each step
computes a (bm, bf) slab of the intermediate, applies the activation
register-resident, and accumulates its down-projection into a persistent
fp32 (bm, d_out) VMEM scratch.  Both weight matrices stream through VMEM
exactly once per M block — the "one HBM pass for both weights" the fused
cost curve prices.

Variants cover every fusable chain the pass emits: plain MLP (optional
fc biases, OPT-style), GLU (separate gate matrix, LLaMA-style), and the
RWKV channel-mix / MoE shared-expert forms (structurally plain/GLU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_mlp.ref import _ACT

# VMEM a grid step's blocks may fill.  v5e and v6e have 128 MiB per core;
# the compiler's default scoped limit (16 MiB on v5e) is far below what one
# double-buffered (d, bf) weight slab needs at 5k-8k model widths, so the
# kernel sizes its own limit from the blocks it picked.
_VMEM_BUDGET = 64 << 20


def _vmem_bytes(bm: int, bf: int, d: int, dout: int, n_in: int,
                itemsize: int) -> int:
    """Per-step VMEM: double-buffered x, ``n_in`` (d, bf) input slabs, the
    (bf, dout) down slab and the out block, plus the fp32 accumulator and
    the fp32 (bm, bf) intermediates."""
    blocks = bm * d + n_in * d * bf + bf * dout + bm * dout
    return 2 * itemsize * blocks + 4 * bm * dout + 4 * n_in * bm * bf


def _ff_block(bf: int, bm: int, d: int, ff: int, dout: int, n_in: int,
              itemsize: int) -> int:
    """Largest multiple of 128 that is <= ``bf``, divides ``ff`` (so no
    weight is padded per call) and keeps the step within the VMEM budget.
    Widths with no such divisor keep ``min(bf, ff)`` and pad."""
    for cand in range(bf - bf % 128, 127, -128):
        if ff % cand == 0 and _vmem_bytes(bm, cand, d, dout, n_in,
                                          itemsize) <= _VMEM_BUDGET:
            return cand
    return min(bf, ff)


def _make_kernel(act_fn, gated: bool, bias: bool):
    def kernel(*refs):
        x_ref, wu_ref = refs[0], refs[1]
        i = 2
        wg_ref = None
        if gated:
            wg_ref, i = refs[i], i + 1
        wd_ref, i = refs[i], i + 1
        bu_ref = bd_ref = None
        if bias:
            bu_ref, bd_ref, i = refs[i], refs[i + 1], i + 2
        o_ref, acc_ref = refs[i], refs[i + 1]

        @pl.when(pl.program_id(1) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        h = jnp.dot(x_ref[...], wu_ref[...],
                    preferred_element_type=jnp.float32)
        if bias:
            h = h + bu_ref[...].astype(jnp.float32)
        if gated:
            g = jnp.dot(x_ref[...], wg_ref[...],
                        preferred_element_type=jnp.float32)
            h = act_fn(g) * h
        else:
            h = act_fn(h)
        acc_ref[...] += jnp.dot(h.astype(o_ref.dtype), wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _flush():
            out = acc_ref[...]
            if bias:
                out = out + bd_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("act", "bm", "bf", "interpret"))
def fused_mlp_kernel(x: jax.Array, w_up: jax.Array, w_down: jax.Array,
                     w_gate=None, b_up=None, b_down=None, *,
                     act: str = "silu", bm: int = 128, bf: int = 512,
                     interpret: bool = False) -> jax.Array:
    """act(x @ w_up [+ b_up]) [* gate] @ w_down [+ b_down] in one grid.

    ``x``: (..., d); ``w_up``/``w_gate``: (d, ff); ``w_down``: (ff, d_out).
    fp32 accumulation throughout; the intermediate slab is cast back to the
    activation dtype before the down-projection (matching the composed
    per-matmul reference).  Operands are zero-padded up to block multiples
    — exact for every supported activation because padded ``w_down`` rows
    are zero."""
    lead, d = x.shape[:-1], x.shape[-1]
    m = math.prod(lead)
    ff, dout = w_up.shape[1], w_down.shape[1]
    assert w_down.shape[0] == ff, (w_up.shape, w_down.shape)
    if m == 0:
        return jnp.zeros((*lead, dout), x.dtype)
    x2 = x.reshape(m, d)
    gated, bias = w_gate is not None, b_up is not None
    n_in = 2 if gated else 1
    bm = min(bm, m)
    bf = _ff_block(bf, bm, d, ff, dout, n_in, x.dtype.itemsize)
    mp = -(-m // bm) * bm
    ffp = -(-ff // bf) * bf
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    if ffp != ff:
        w_up = jnp.pad(w_up, ((0, 0), (0, ffp - ff)))
        w_down = jnp.pad(w_down, ((0, ffp - ff), (0, 0)))
        if gated:
            w_gate = jnp.pad(w_gate, ((0, 0), (0, ffp - ff)))
        if bias:
            b_up = jnp.pad(b_up, (0, ffp - ff))

    args = [x2, w_up]
    in_specs = [pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                pl.BlockSpec((d, bf), lambda i, j: (0, j))]
    if gated:
        args.append(w_gate)
        in_specs.append(pl.BlockSpec((d, bf), lambda i, j: (0, j)))
    args.append(w_down)
    in_specs.append(pl.BlockSpec((bf, dout), lambda i, j: (j, 0)))
    if bias:
        args += [b_up.reshape(1, ffp), b_down.reshape(1, dout)]
        in_specs += [pl.BlockSpec((1, bf), lambda i, j: (0, j)),
                     pl.BlockSpec((1, dout), lambda i, j: (0, 0))]

    out = pl.pallas_call(
        _make_kernel(_ACT[act], gated, bias),
        grid=(mp // bm, ffp // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, dout), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bm, dout), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, dout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, _vmem_bytes(
                bm, bf, d, dout, n_in, x.dtype.itemsize) * 5 // 4)),
        interpret=interpret,
    )(*args)
    return out[:m].reshape(*lead, dout)
