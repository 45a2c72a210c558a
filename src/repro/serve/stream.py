"""ELK weight-streaming decoder — the paper's technique at pod level.

Mapping (DESIGN.md §3A): layer weights live *sharded over the data axis*
(preload state, each device holds 1/k); before a layer executes its weights
are all-gathered to replicated-over-data (execute state).  The gather of
block ``i + p`` is issued while block ``i`` computes — ``p`` is the paper's
*preload number*, chosen by the ELK scheduler
(``core/integration.pod_plan``), and the rolling window of ``p`` gathered
blocks is the *preload space* (the on-chip memory capacity contention ① is
now an HBM capacity contention; the ICI contention between these gathers
and TP collectives is contention ②).

Mechanically: a ``lax.scan`` whose carry holds the ``p`` gathered blocks;
``with_sharding_constraint`` forces the preload->execute transition, and
XLA's latency-hiding scheduler overlaps the gather with the previous
block's compute because they have no data dependency.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import _path_str, param_pspec
from repro.models import transformer as tfm
from repro.models.config import ModelConfig

PyTree = Any


def _drop_axis(spec: P, axis: str) -> P:
    return P(*[(None if ax == axis else ax) for ax in spec])


def execute_state_shardings(params_blocks, mesh: Mesh) -> PyTree:
    """Sharding of one *gathered* block (preload state minus the data axis
    and the stacked leading dim)."""
    def one(path, leaf):
        spec = param_pspec("blocks/" + _path_str(path), jnp.shape(leaf),
                           mesh, fsdp=True)
        spec = _drop_axis(spec, "data")
        return NamedSharding(mesh, P(*spec[1:]))   # drop stacked dim
    return jax.tree_util.tree_map_with_path(one, params_blocks)


def _index_block(params_blocks, i):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        params_blocks)


def _gather(params_blocks, i, exec_shardings):
    blk = _index_block(params_blocks, i)
    return jax.tree.map(lax.with_sharding_constraint, blk, exec_shardings)


def streaming_decoder(params, cfg: ModelConfig, x, ctx, cache,
                      mesh: Mesh, prefetch: int = 2):
    """Drop-in replacement for ``transformer._run_decoder`` that streams
    block weights with an ELK gather-ahead window of depth ``prefetch``.

    Returns (x, the updated stacked cache leaves) with the same contract:
    the cache rides in the carry and each layer writes it in place."""
    prefix, period, n_blocks = tfm.block_structure(cfg)
    kinds = tfm.layer_kinds(cfg)
    spec = tfm.attn_spec(cfg)
    state = tfm._layer_state(cache)

    for li in range(prefix):
        x, state = tfm._decoder_layer(x, params["prefix"][li], cfg,
                                      kinds[li], spec, ctx, state, li)
    if not n_blocks:
        return x, state

    pblocks = tuple(params["blocks"])
    exec_sh = execute_state_shardings(pblocks, mesh)
    p = max(1, min(prefetch, n_blocks))

    # preload window: blocks 0..p-1 gathered up front (the paper's initial
    # pipeline fill)
    window = [_gather(pblocks, jnp.int32(i), exec_sh) for i in range(p)]
    window = jax.tree.map(lambda *xs: jnp.stack(xs), *window)

    def body(carry, i):
        x, win, state = carry
        cur = jax.tree.map(lambda a: a[0], win)
        for slot in range(period):
            x, state = tfm._decoder_layer(x, cur[slot], cfg,
                                          kinds[prefix + slot], spec, ctx,
                                          state, prefix + i * period + slot)
        # issue the gather of block i+p (clamped; tail gathers are no-ops
        # on already-resident data)
        nxt = _gather(pblocks, jnp.minimum(i + p, n_blocks - 1), exec_sh)
        win = jax.tree.map(
            lambda a, n: jnp.concatenate([a[1:], n[None]], axis=0),
            win, nxt)
        return (x, win, state), None

    idxs = jnp.arange(n_blocks, dtype=jnp.int32)
    (x, _, state), _ = lax.scan(body, (x, window, state), idxs)
    return x, state


def streaming_decode_slots(params, cfg: ModelConfig, token, cache,
                           mesh: Mesh, prefetch: int = 2):
    """ELK-streaming version of ``transformer.decode_slots``: one
    continuous-batching step over a per-slot cache, with block weights
    gathered ahead through the same preload window as the lock-step path.
    """
    if cfg.encoder_layers:
        raise ValueError("decode_slots does not support enc-dec models")
    x = jnp.take(params["embed"], token[:, None], axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    pos = cache["pos"]                                  # (B,)
    ctx, slot_pos = tfm._slots_ctx(cache, pos[:, None], mesh)
    x, state = streaming_decoder(params, cfg, x, ctx, cache, mesh, prefetch)
    new_cache = tfm._merge_cache(cache, state, pos + 1, slot_pos)
    return tfm._logits(params, cfg, x), new_cache


def streaming_decode_step(params, cfg: ModelConfig, token, cache,
                          mesh: Mesh, prefetch: int = 2):
    """ELK-streaming version of ``transformer.decode_step``.

    Enc-dec models fall back to the plain decode path: their decoders are
    tiny (whisper-tiny: 37M) and cross-attention K/V lives in the cache —
    nothing worth streaming."""
    if cfg.encoder_layers:
        return tfm.decode_step(params, cfg, token, cache)
    x = jnp.take(params["embed"], token[:, None], axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    pos = cache["pos"]
    slot_pos = None
    if "slot_pos" in cache:
        c = cache["slot_pos"].shape[0]
        slot_pos = cache["slot_pos"].at[pos % c].set(pos)
    ctx = {"mode": "decode", "pos": pos, "slot_pos": slot_pos,
           "enc_out": None, "mesh": mesh}
    x, state = streaming_decoder(params, cfg, x, ctx, cache, mesh, prefetch)
    new_cache = tfm._merge_cache(cache, state, pos + 1, slot_pos)
    return tfm._logits(params, cfg, x), new_cache
