"""Jit'd public wrapper for the fused MLP (TPU kernel / CPU fallback)."""

from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.dispatch import dispatch
from repro.kernels.fused_mlp.kernel import fused_mlp_kernel
from repro.kernels.fused_mlp.ref import fused_mlp_ref


def fused_mlp(x: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
              w_gate: Optional[jax.Array] = None,
              b_up: Optional[jax.Array] = None,
              b_down: Optional[jax.Array] = None,
              act: str = "silu", bm: int = 128, bf: int = 512,
              force_kernel: bool = False,
              mesh: Optional[Mesh] = None) -> jax.Array:
    """up-proj -> activation -> down-proj without storing the intermediate.

    GLU when ``w_gate`` is given, plain MLP (optional fc biases) otherwise.
    On a mesh of more than one device the kernel runs per shard (the
    compiler cannot partition a Mosaic call): see ``_per_shard``.
    """
    def kernel(interpret):
        def call(x, w_up, w_down, w_gate, b_up, b_down):
            return fused_mlp_kernel(x, w_up, w_down, w_gate, b_up, b_down,
                                    act=act, bm=bm, bf=bf,
                                    interpret=interpret)
        if mesh is None or mesh.size == 1:
            return call(x, w_up, w_down, w_gate, b_up, b_down)
        return _per_shard(call, mesh, x, w_up, w_down, w_gate, b_up, b_down)

    return dispatch(
        kernel,
        lambda: fused_mlp_ref(x, w_up, w_down, w_gate=w_gate, b_up=b_up,
                              b_down=b_down, act=act),
        force_kernel=force_kernel)


def _per_shard(call, mesh: Mesh, x, w_up, w_down, w_gate, b_up, b_down):
    """``shard_map`` the kernel with rows split over the batch axes (pod,
    data) where the leading dim divides; the weights are gathered whole to
    every shard."""
    bp = tuple(ax for ax in ("pod", "data") if ax in mesh.shape)
    dp = math.prod(mesh.shape[ax] for ax in bp)
    rows = bp if bp and x.shape[0] % dp == 0 else None
    x_spec = P(rows, *([None] * (x.ndim - 1)))
    names = ("w_up", "w_down", "w_gate", "b_up", "b_down")
    given = {k: v for k, v in zip(names, (w_up, w_down, w_gate, b_up, b_down))
             if v is not None}

    def body(x, given):
        return call(x, *(given.get(k) for k in names))

    return jax.shard_map(body, mesh=mesh, in_specs=(x_spec, P()),
                         out_specs=x_spec, check_vma=False)(x, given)
