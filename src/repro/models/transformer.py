"""Unified model definition for every assigned architecture.

One functional model covers dense / GQA / SWA / qk-norm / GeGLU / MoE /
RWKV6 / hybrid-SSM / enc-dec / VLM-prefix families, driven entirely by
``ModelConfig``.  Three entry points:

* ``forward_train``  — full-sequence causal forward, returns logits.
* ``prefill``        — forward that also fills a serving cache.
* ``decode_step``    — one token against the cache (``serve_step`` shapes).

Compile-time scalability (the multi-pod dry-run lowers 61-layer trillion-
parameter configs for 512 devices): decoder layers run under ``lax.scan``
over *layer blocks* with stacked parameters.  A block is ``period`` layers,
where ``period`` is the MoE interleave (llama4: dense/MoE alternation => 2)
— so the scanned body is structurally identical across blocks and the HLO
stays O(period), not O(num_layers).  Leading non-periodic layers
(kimi's dense first layer) run unrolled.

Caches are stacked over layers (leading dim ``num_layers``) and ride
through the layer loop in its carry: layer ``li`` reads its slice where it
lies and writes back only what it changed — each row's new K/V, or a whole
layer's state — in place, so a step never copies the stacked cache.  KV
caches are ring buffers of capacity
``C``: exact attention while ``pos < C`` and sliding-window semantics
beyond — full-attention serving sizes ``C = seq_len``, SWA archs size
``C = window`` (how hymba/danube hold ``long_500k`` state in O(window)).

Every op carries a named scope in its metadata, so a profiler trace can
split a step by them: ``layer`` (one decoder layer), ``attn``, ``mlp``,
``head``, and ``kv_ring`` for every read and write of the cache (a
layer's slice, the in-place writes of new K/V and state, and the position
tags).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (AttnSpec, apply_rope, attn_mask_bias,
                                 chunked_gqa_attention, gqa_attention,
                                 linear, mlp, mlp_params, qk_head_norm,
                                 rms_norm, rope_tables, softmax_xent)

PyTree = Any
_POS_SENTINEL = jnp.int32(2 ** 30)   # cache slots not yet written


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

def layer_kinds(cfg: ModelConfig) -> list[str]:
    if cfg.rwkv:
        return ["rwkv"] * cfg.num_layers
    return ["moe" if cfg.is_moe_layer(i) else "dense"
            for i in range(cfg.num_layers)]


def block_structure(cfg: ModelConfig) -> tuple[int, int, int]:
    """(prefix_len, period, n_blocks): prefix layers run unrolled, then
    n_blocks scan iterations of `period` layers each."""
    kinds = layer_kinds(cfg)
    prefix = cfg.moe_first_dense if cfg.moe_experts else 0
    body = kinds[prefix:]
    period = max(cfg.moe_every, 1) if cfg.moe_experts else 1
    if len(body) % period:
        # ragged tail: fold it into the prefix from the far end is wrong —
        # instead shrink the scanned part and unroll the tail as prefix2.
        # Keep it simple: grow prefix until divisible.
        extra = len(body) % period
        prefix += extra
        body = kinds[prefix:]
    assert all(body[i] == body[i % period] for i in range(len(body)))
    return prefix, period, len(body) // period


def attn_spec(cfg: ModelConfig) -> AttnSpec:
    return AttnSpec(
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim,
        causal=True,
        sliding_window=(cfg.sliding_window
                        if cfg.swa_layers == "all" else 0),
        qk_norm=cfg.qk_norm,
    )


# ---------------------------------------------------------------------------
# per-layer parameters
# ---------------------------------------------------------------------------

def _attn_params(rng, cfg: ModelConfig, dtype, cross: bool = False) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 4)
    s = d ** -0.5
    p = {
        "w_q": jax.random.normal(ks[0], (d, nq * hd), dtype) * s,
        "w_k": jax.random.normal(ks[1], (d, nkv * hd), dtype) * s,
        "w_v": jax.random.normal(ks[2], (d, nkv * hd), dtype) * s,
        "w_o": jax.random.normal(ks[3], (nq * hd, d), dtype) * (nq * hd) ** -0.5,
    }
    if cfg.qkv_bias and not cross:
        p["b_q"] = jnp.zeros((nq * hd,), dtype)
        p["b_k"] = jnp.zeros((nkv * hd,), dtype)
        p["b_v"] = jnp.zeros((nkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def decoder_layer_params(rng, cfg: ModelConfig, kind: str, dtype) -> dict:
    if kind == "rwkv":
        return rwkv_mod.rwkv_layer_params(rng, cfg, dtype)
    ks = jax.random.split(rng, 5)
    d = cfg.d_model
    p = {"ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
         "attn": _attn_params(ks[0], cfg, dtype)}
    if kind == "moe":
        p["moe"] = moe_mod.moe_params(ks[1], cfg, dtype)
        if cfg.moe_shared_d_ff:
            p["shared_mlp"] = mlp_params(ks[2], cfg, d,
                                         cfg.moe_shared_d_ff, dtype)
    else:
        p["mlp"] = mlp_params(ks[1], cfg, d, cfg.d_ff, dtype)
    if cfg.hybrid_parallel_ssm:
        p["ssm"] = ssm_mod.ssm_params(ks[3], cfg, dtype)
    if cfg.encoder_layers:
        p["ln_x"] = jnp.zeros((d,), dtype)
        p["xattn"] = _attn_params(ks[4], cfg, dtype, cross=True)
    return p


def encoder_layer_params(rng, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(rng, 2)
    d = cfg.d_model
    return {"ln1": jnp.zeros((d,), dtype), "ln2": jnp.zeros((d,), dtype),
            "attn": _attn_params(ks[0], cfg, dtype),
            "mlp": mlp_params(ks[1], cfg, d, cfg.d_ff, dtype)}


def init_params(rng, cfg: ModelConfig,
                decode_positions: int = 0) -> PyTree:
    """Full parameter pytree.  ``decode_positions`` sizes whisper's learned
    decoder position table (0 -> 4096)."""
    dtype = jnp.dtype(cfg.param_dtype)
    kE, kH, kenc, kpre, kblk, kpos = jax.random.split(rng, 6)
    d, v = cfg.d_model, cfg.vocab_size
    prefix, period, n_blocks = block_structure(cfg)
    kinds = layer_kinds(cfg)

    params: dict = {
        "embed": jax.random.normal(kE, (v, d), dtype) * d ** -0.5,
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(kH, (d, v), dtype) * d ** -0.5

    if cfg.encoder_layers:
        enc_keys = jax.random.split(kenc, cfg.encoder_layers)
        params["encoder"] = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[encoder_layer_params(k, cfg, dtype) for k in enc_keys])
        params["enc_norm"] = jnp.zeros((d,), dtype)
        params["enc_pos"] = (jax.random.normal(
            kpos, (max(cfg.encoder_seq, 1), d), dtype) * 0.02)
        npos = decode_positions or 4096
        params["dec_pos"] = jax.random.normal(kpos, (npos, d), dtype) * 0.02

    pre_keys = jax.random.split(kpre, max(prefix, 1))
    params["prefix"] = [decoder_layer_params(pre_keys[i], cfg, kinds[i], dtype)
                        for i in range(prefix)]

    # vmap over the block keys builds each stacked leaf directly (the same
    # values as stacking per-block draws), so no per-layer copy is ever
    # live beside the stack
    blocks = []
    blk_keys = jax.random.split(kblk, max(n_blocks * period, 1))
    for slot in range(period):
        kind = kinds[prefix + slot]
        blocks.append(jax.vmap(
            lambda k, kind=kind: decoder_layer_params(k, cfg, kind, dtype))(
                blk_keys[slot::period][:n_blocks]))
    params["blocks"] = blocks
    return params


# ---------------------------------------------------------------------------
# serving cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CacheSpec:
    capacity: int                  # KV slots per layer (ring buffer)
    batch: int
    kv_dtype: Any = jnp.bfloat16   # bf16 | int8 (quantized serving cache)
    per_slot: bool = False         # independent per-request slots
                                   # (pos: (B,), slot_pos: (B, C))


def init_cache(cfg: ModelConfig, spec: CacheSpec) -> dict:
    L, B, C = cfg.num_layers, spec.batch, spec.capacity
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    if spec.per_slot and cfg.encoder_layers:
        raise ValueError("per-slot caches do not support enc-dec models")
    cache: dict = {"pos": (jnp.zeros((B,), jnp.int32) if spec.per_slot
                           else jnp.zeros((), jnp.int32))}
    if not cfg.rwkv:
        kv_shape = (L, B, nkv, C, hd)
        cache["k"] = jnp.zeros(kv_shape, spec.kv_dtype)
        cache["v"] = jnp.zeros(kv_shape, spec.kv_dtype)
        if spec.kv_dtype == jnp.int8:
            cache["k_scale"] = jnp.zeros((L, B, nkv, C, 1), jnp.bfloat16)
            cache["v_scale"] = jnp.zeros((L, B, nkv, C, 1), jnp.bfloat16)
        cache["slot_pos"] = jnp.full((B, C) if spec.per_slot else (C,),
                                     _POS_SENTINEL, jnp.int32)
    if cfg.rwkv:
        h = cfg.num_heads
        cache["rwkv_state"] = jnp.zeros((L, B, h, cfg.d_model // h,
                                         cfg.d_model // h), jnp.float32)
    if cfg.hybrid_parallel_ssm:
        cache["ssm_state"] = jnp.zeros((L, B, cfg.d_model, cfg.ssm_state),
                                       jnp.float32)
    if cfg.encoder_layers:
        E = cfg.encoder_seq
        cache["cross_k"] = jnp.zeros((L, B, nkv, E, hd), jnp.bfloat16)
        cache["cross_v"] = jnp.zeros((L, B, nkv, E, hd), jnp.bfloat16)
    return cache


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def _dequantize_kv(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)
            ).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _project_qkv(x, p, cfg: ModelConfig):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    q = heads(linear(x, p["w_q"], p.get("b_q")), nq)
    k = heads(linear(x, p["w_k"], p.get("b_k")), nkv)
    v = heads(linear(x, p["w_v"], p.get("b_v")), nkv)
    if cfg.qk_norm:
        q = qk_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = qk_head_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


@jax.named_scope("attn")
def _self_attention_full(x, p, cfg: ModelConfig, spec: AttnSpec,
                         sin, cos, positions, mesh=None, layout="tp"):
    """Training/prefill attention over the whole sequence.

    Sharding: attention internals are *sequence-parallel* over the model
    axis (q's seq dim sharded, K/V replicated within the group) — head
    counts like 40 or kv=8 don't divide a 16-way model axis, and a seq
    split keeps the score tile exactly N-way sharded for every arch.  The
    q-chunked path bounds the live score tile (Pallas flash kernel
    replaces it on real TPU)."""
    b, s, d = x.shape
    q, k, v = _project_qkv(x, p, cfg)
    if not cfg.rwkv and cfg.num_heads:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    seq_tp = mesh is not None and "model" in getattr(mesh, "shape", {}) \
        and layout == "tp"
    if seq_tp:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import batch_axes
        bp = batch_axes(mesh)
        bq = bp if b % max(
            int(np.prod([mesh.shape[a] for a in bp])), 1) == 0 else None
        # K/V batch-sharded, replicated over model (seq-parallel q does the
        # model-axis sharding; partial head shardings would reshard every
        # layer for head counts like 40 or kv=8 on a 16-way axis).  Under
        # the fsdp2d layout activations are replicated over model (weights
        # gather instead) and these constraints would only churn reshards.
        k = jax.lax.with_sharding_constraint(
            k, NamedSharding(mesh, P(bq, None, None, None)))
        v = jax.lax.with_sharding_constraint(
            v, NamedSharding(mesh, P(bq, None, None, None)))
    out = chunked_gqa_attention(q, k, v, spec, positions, positions,
                                chunk=cfg.attn_chunk,
                                unroll=cfg.unroll_scan,
                                mesh=mesh if seq_tp else None)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return linear(out, p["w_o"]), k, v


@jax.named_scope("kv_ring")
def _at(buf: jax.Array, li) -> jax.Array:
    """Layer ``li``'s slice of a cache leaf stacked over layers."""
    return lax.dynamic_index_in_dim(buf, li, 0, keepdims=False)


@jax.named_scope("kv_ring")
def _put(buf: jax.Array, val: jax.Array, li) -> jax.Array:
    """Replace layer ``li`` of a stacked cache leaf, in place."""
    return lax.dynamic_update_index_in_dim(buf, val.astype(buf.dtype), li, 0)


@jax.named_scope("kv_ring")
def _put_slot(buf: jax.Array, val: jax.Array, li, slot) -> jax.Array:
    """Write val (B,H,1,*) at ring slot ``slot`` of layer ``li``, every
    row, in place."""
    return lax.dynamic_update_slice(buf, val[None].astype(buf.dtype),
                                    (li, 0, 0, slot, 0))


@jax.named_scope("kv_ring")
def _put_rows(buf: jax.Array, val: jax.Array, li,
              positions: jax.Array) -> jax.Array:
    """Write new rows val (B,H,T,*) into layer ``li`` of a stacked ring
    (L,B,H,C,*): row r's T tokens sit at the consecutive positions
    ``positions[r]`` (T <= C), each at its position modulo C.  In-place
    updates only, one run per row (B is static): a scatter here makes the
    compiler copy the whole stacked ring around the layer loop.  A run
    that wraps past slot C-1 is written as two windows, [C-T, C) and
    [0, T), each keeping the old rows it does not own."""
    b, h, t, e = val.shape
    c = buf.shape[3]
    val = val.astype(buf.dtype)

    def write(buf, run, r, start):
        return lax.dynamic_update_slice(buf, run[None, None],
                                        (li, r, 0, start, 0))

    for r in range(b):
        first = positions[r, 0] % c
        if t == 1:
            buf = write(buf, val[r], r, first)
            continue
        start = jnp.minimum(first, c - t)   # the window stays inside C
        wrap = first - start                # the run's last `wrap` tokens
        run = jnp.roll(val[r], wrap, axis=1)    # land in slots [0, wrap)
        idx = jnp.arange(t)[None, :, None]
        for lo, mine in ((start, idx >= wrap), (0, idx < wrap)):
            old = lax.dynamic_slice(buf, (li, r, 0, lo, 0),
                                    (1, 1, h, t, e))[0, 0]
            buf = write(buf, jnp.where(mine, run, old), r, lo)
    return buf


def _store_kv(state: dict, k_new, v_new, put) -> dict:
    """Write one layer's new K/V (B,H,T,hd) into the stacked cache with
    ``put(buf, val)``; an int8 cache stores them quantized, with scales."""
    if "k_scale" in state:
        (k_new, k_sc), (v_new, v_sc) = _quantize_kv(k_new), _quantize_kv(v_new)
        new = {"k": k_new, "v": v_new, "k_scale": k_sc, "v_scale": v_sc}
    else:
        new = {"k": k_new, "v": v_new}
    return {**state, **{key: put(state[key], val) for key, val in new.items()}}


def _load_kv(state: dict, li) -> tuple[jax.Array, jax.Array]:
    """Layer ``li``'s K/V rings (B,H,C,hd), dequantized if int8."""
    k, v = _at(state["k"], li), _at(state["v"], li)
    if "k_scale" in state:
        k = _dequantize_kv(k, _at(state["k_scale"], li))
        v = _dequantize_kv(v, _at(state["v_scale"], li))
    return k, v


@jax.named_scope("attn")
def _self_attention_decode(x, p, cfg: ModelConfig, spec: AttnSpec,
                           state: dict, li, slot_pos, pos):
    """x: (B,1,d) one token at absolute position ``pos`` against layer
    ``li`` of the stacked ring cache (L,B,Hkv,C,hd).  Returns (out, state)
    with the token's K/V written at ring slot ``pos % C``."""
    b, s, d = x.shape
    c = state["k"].shape[3]
    q, k_new, v_new = _project_qkv(x, p, cfg)         # (B,H,1,hd)
    sin, cos = rope_tables(jnp.full((1,), pos), spec.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k_new = apply_rope(k_new, sin, cos)
    state = _store_kv(state, k_new, v_new,
                      lambda buf, val: _put_slot(buf, val, li, pos % c))
    k, v = _load_kv(state, li)
    bias = attn_mask_bias(spec, jnp.full((1,), pos), slot_pos)
    out = gqa_attention(q, k.astype(q.dtype), v.astype(q.dtype), bias, spec)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return linear(out, p["w_o"]), state


@jax.named_scope("attn")
def _self_attention_slots(x, p, cfg: ModelConfig, spec: AttnSpec,
                          state: dict, li, slot_pos, positions):
    """Per-slot cached attention: every batch row sits at its own absolute
    position.  x: (B,T,d), positions: (B,T) per-row token positions,
    slot_pos: (B,C) per-row ring tags (already updated for this step's
    writes), ``state``'s rings (L,B,Hkv,C,hd), read and written at layer
    ``li``.  Serves both the continuous-batching decode step (T=1,
    B=slots) and chunked prefill (B=1, T=chunk).  Returns (out, state)."""
    b, t, d = x.shape
    q, k_new, v_new = _project_qkv(x, p, cfg)          # (B,H,T,hd)
    sin, cos = rope_tables(positions, spec.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k_new = apply_rope(k_new, sin, cos)

    # only the T written slots of each row are touched, so with donated
    # buffers the per-token write is O(T), not O(C)
    state = _store_kv(state, k_new, v_new,
                      lambda buf, val: _put_rows(buf, val, li, positions))
    k, v = _load_kv(state, li)

    # per-row additive mask from the ring tags (sentinel slots mask out)
    ok = slot_pos[:, None, :] <= positions[:, :, None]
    if spec.sliding_window:
        ok &= slot_pos[:, None, :] > positions[:, :, None] - spec.sliding_window
    bias = jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)
    bias = bias[:, None, None, :, :]                   # (B,1,1,T,C)
    out = gqa_attention(q, k.astype(q.dtype), v.astype(q.dtype), bias, spec)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, -1)
    return linear(out, p["w_o"]), state


@jax.named_scope("attn")
def _cross_attention(x, p, cfg: ModelConfig, ck, cv, mesh=None):
    """Cross-attention, q-chunked like self-attention: the unchunked
    (B,H,Sq,Senc) fp32 score tensor dominated whisper training memory
    (EXPERIMENTS.md §Perf iteration 6)."""
    b, s, d = x.shape
    hd, nq = cfg.resolved_head_dim, cfg.num_heads
    q = linear(x, p["w_q"]).reshape(b, s, nq, hd).transpose(0, 2, 1, 3)
    spec = AttnSpec(nq, cfg.num_kv_heads, hd, causal=False)
    senc = ck.shape[2]
    q_pos = jnp.zeros((s,), jnp.int32)       # non-causal: mask is all-open
    k_pos = jnp.zeros((senc,), jnp.int32)
    out = chunked_gqa_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                spec, q_pos, k_pos, chunk=cfg.attn_chunk,
                                unroll=cfg.unroll_scan, mesh=mesh)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return linear(out, p["w_o"])


@jax.named_scope("mlp")
def _ffn(x, p, cfg: ModelConfig, kind: str, mode: str = "train",
         mesh=None):
    if kind == "moe":
        b, s, d = x.shape
        out = moe_mod.moe_ffn(x.reshape(b * s, d), p["moe"], cfg,
                              dropless=(mode in ("decode", "slots")),
                              mesh=mesh)
        out = out.reshape(b, s, d)
        if cfg.moe_shared_d_ff:
            out = out + mlp(x, p["shared_mlp"], cfg, mesh)
        return out
    return mlp(x, p["mlp"], cfg, mesh)


@jax.named_scope("layer")
def _decoder_layer(x, p, cfg: ModelConfig, kind: str, spec: AttnSpec,
                   ctx: dict, state: Optional[dict], li):
    """Apply decoder layer ``li``.  ``state`` holds the cache leaves stacked
    over layers (None without a cache); the layer writes what it changed
    into them in place.  Returns (x, state)."""
    if kind == "rwkv":
        prev = None if state is None else _at(state["rwkv_state"], li)
        x, new = rwkv_mod.rwkv_block(x, p, cfg, prev)
        if state is not None:
            state = {**state, "rwkv_state": _put(state["rwkv_state"], new, li)}
        return x, state

    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if ctx["mode"] == "decode":
        attn_out, state = _self_attention_decode(
            h, p["attn"], cfg, spec, state, li, ctx["slot_pos"], ctx["pos"])
    elif ctx["mode"] == "slots":
        attn_out, state = _self_attention_slots(
            h, p["attn"], cfg, spec, state, li, ctx["slot_pos"],
            ctx["positions"])
    else:
        attn_out, k, v = _self_attention_full(
            h, p["attn"], cfg, spec, ctx["sin"], ctx["cos"],
            ctx["positions"], ctx.get("mesh"), ctx.get("layout", "tp"))
        if state is not None:   # prefill: write the layer's whole ring
            c, s = state["k"].shape[3], k.shape[2]
            state = _store_kv(
                state, k[:, :, -c:, :], v[:, :, -c:, :],
                lambda buf, val: _put(buf, _fill_ring(val, c, s), li))

    if cfg.hybrid_parallel_ssm:
        prev = None if state is None else _at(state["ssm_state"], li)
        ssm_out, new = ssm_mod.ssm_branch(h, p["ssm"], cfg, prev)
        attn_out = attn_out + ssm_out
        if state is not None:
            state = {**state, "ssm_state": _put(state["ssm_state"], new, li)}
    x = x + attn_out

    if cfg.encoder_layers:
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps)
        x = x + _cross_attention(hx, p["xattn"], cfg,
                                 ctx["cross_k"], ctx["cross_v"],
                                 ctx.get("mesh"))

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _ffn(h2, p, cfg, kind, ctx["mode"], ctx.get("mesh"))
    return x, state


@jax.named_scope("kv_ring")
def _fill_ring(val: jax.Array, c: int, total_seq: int) -> jax.Array:
    """Lay a prefill's last-C tokens val (B,H,min(S,C),*) out as a ring of
    capacity ``c``: position ``p`` lands at slot ``p % C`` (so later decode
    steps evict exactly the token leaving the window)."""
    s = val.shape[2]           # = min(total_seq, c)
    if s < c:
        val = jnp.pad(val, ((0, 0), (0, 0), (0, c - s), (0, 0)))
    shift = (total_seq - s) % c
    if shift:
        val = jnp.roll(val, shift, axis=2)
    return val


# ---------------------------------------------------------------------------
# full forward passes
# ---------------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens, embeds):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if embeds is not None and cfg.frontend == "vision_stub":
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    return x


@jax.named_scope("head")
def _logits(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def _encode(params, cfg: ModelConfig, enc_embeds):
    """Whisper encoder over precomputed (stub) frame embeddings."""
    x = enc_embeds + params["enc_pos"][None, :enc_embeds.shape[1], :]
    s = x.shape[1]
    spec = AttnSpec(cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
                    causal=False)
    positions = jnp.arange(s)
    sin, cos = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)

    zero_pos = jnp.zeros((s,), jnp.int32)   # non-causal: all-open mask

    def body(x, p):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(h, p["attn"], cfg)
        out = chunked_gqa_attention(q, k, v, spec, zero_pos, zero_pos,
                                    chunk=cfg.attn_chunk,
                                    unroll=cfg.unroll_scan)
        b, hq, sq, hd = out.shape
        out = out.transpose(0, 2, 1, 3).reshape(b, sq, hq * hd)
        x = x + linear(out, p["attn"]["w_o"])
        x = x + mlp(rms_norm(x, p["ln2"], cfg.norm_eps), p["mlp"], cfg)
        return x, None

    if cfg.unroll_scan:
        for li in range(cfg.encoder_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[li], params["encoder"]))
    else:
        x, _ = lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _seq_shard_hidden(x, mesh):
    """Sequence-parallel residual stream: the (B,S,d) hidden state carried
    between blocks is sharded (batch->data, seq->model).  This is what the
    remat scan *saves* per block — unsharded it dominates training HBM."""
    if mesh is None or "model" not in getattr(mesh, "shape", {}) \
            or x.ndim != 3 or x.shape[1] % mesh.shape["model"]:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    bp = tuple(ax for ax in ("pod", "data") if ax in mesh.shape)
    dp = 1
    for ax in bp:
        dp *= mesh.shape[ax]
    b_ax = bp if x.shape[0] % max(dp, 1) == 0 else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(b_ax, "model", None)))


_LAYER_KEYS = ("k", "v", "k_scale", "v_scale", "rwkv_state", "ssm_state")


def _layer_state(cache: Optional[dict]) -> Optional[dict]:
    """The cache leaves stacked over layers, which the layer loop carries."""
    if cache is None:
        return None
    return {key: cache[key] for key in _LAYER_KEYS if key in cache}


def _run_decoder(params, cfg: ModelConfig, x, ctx, cache, remat=False):
    """Apply prefix layers then the scanned blocks.  The cache's stacked
    leaves ride in the loop's carry: layer ``prefix + block * period +
    slot`` reads its slice at that index and writes its new K/V rows (or
    its whole state) there in place, so the ring is neither sliced into
    the scan's xs nor restacked from its ys.  Returns (x, the updated
    stacked leaves, or None without a cache)."""
    prefix, period, n_blocks = block_structure(cfg)
    kinds = layer_kinds(cfg)
    spec = attn_spec(cfg)
    state = _layer_state(cache)

    for li in range(prefix):
        x, state = _decoder_layer(x, params["prefix"][li], cfg, kinds[li],
                                  spec, ctx, state, li)
    if not n_blocks:
        return x, state

    def block_body(carry, xs):
        x, state = carry
        bparams, bi = xs
        for slot in range(period):
            x, state = _decoder_layer(x, bparams[slot], cfg,
                                      kinds[prefix + slot], spec, ctx, state,
                                      prefix + bi * period + slot)
        x = _seq_shard_hidden(x, ctx.get("mesh"))
        return (x, state), None

    if remat:
        block_body = jax.checkpoint(block_body)
    bparams = tuple(params["blocks"])
    if cfg.unroll_scan:
        # Python loop: accounting variants (cost_analysis counts every
        # unrolled body; a while/scan body is counted once)
        for bi in range(n_blocks):
            (x, state), _ = block_body(
                (x, state), (jax.tree.map(lambda a: a[bi], bparams), bi))
    else:
        (x, state), _ = lax.scan(
            block_body, (x, state),
            (bparams, jnp.arange(n_blocks, dtype=jnp.int32)))
    return x, state


def _merge_cache(cache: dict, state: Optional[dict], new_pos,
                 slot_pos=None) -> dict:
    """The step's cache: the layer loop's updated leaves and new tags."""
    out = {**cache, **(state or {}), "pos": new_pos}
    if slot_pos is not None:
        out["slot_pos"] = slot_pos
    return out


def forward_train(params, cfg: ModelConfig, tokens: jax.Array,
                  embeds: Optional[jax.Array] = None,
                  enc_embeds: Optional[jax.Array] = None,
                  remat: bool = True, mesh=None,
                  layout: str = "tp") -> jax.Array:
    """Causal forward over (B, S) tokens -> (B, S[, +patches], V) logits."""
    x = _embed(params, cfg, tokens, embeds)
    s = x.shape[1]
    positions = jnp.arange(s)
    sin, cos = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
    ctx = {"mode": "train", "sin": sin, "cos": cos, "positions": positions,
           "mesh": mesh, "layout": layout}
    if cfg.encoder_layers:
        ctx["enc_out"] = _encode(params, cfg, enc_embeds)
        x = x + params["dec_pos"][None, :s, :]
    x, _ = _run_decoder_with_cross(params, cfg, x, ctx, None, remat)
    return _logits(params, cfg, x)


def _run_decoder_with_cross(params, cfg, x, ctx, cache, remat=False):
    """Wrapper that materializes per-layer cross-attention K/V lazily.

    For enc-dec models the layer body projects enc_out with its own xattn
    weights, so ctx carries enc_out; _decoder_layer reads cross_k/cross_v —
    we monkey-patch them per layer via a ctx copy.  Cleanest without
    breaking the scan: precompute nothing, let the layer project."""
    if not cfg.encoder_layers:
        return _run_decoder(params, cfg, x, ctx, cache, remat)
    # enc-dec models are small (whisper-tiny): run layers unrolled with
    # per-layer cross K/V computed from enc_out or read from the cache.
    kinds = layer_kinds(cfg)
    spec = attn_spec(cfg)
    state = _layer_state(cache)
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    all_params = ([params["prefix"][i] for i in range(len(params["prefix"]))]
                  + _unstack_blocks(params, cfg))
    cross = []
    for li in range(cfg.num_layers):
        p = all_params[li]
        if "enc_out" in ctx and ctx["enc_out"] is not None:
            eo = ctx["enc_out"]
            b, es, d = eo.shape
            ck = linear(eo, p["xattn"]["w_k"]).reshape(
                b, es, nkv, hd).transpose(0, 2, 1, 3)
            cv = linear(eo, p["xattn"]["w_v"]).reshape(
                b, es, nkv, hd).transpose(0, 2, 1, 3)
            cross.append((ck, cv))
        else:
            ck = cache["cross_k"][li]
            cv = cache["cross_v"][li]
        lctx = dict(ctx)
        lctx["cross_k"], lctx["cross_v"] = ck, cv
        if remat and ctx["mode"] == "train" and state is None:
            # remat per layer; only array leaves may cross the checkpoint
            stat = {k: v for k, v in lctx.items() if not hasattr(v, "ndim")}
            arrs = {k: v for k, v in lctx.items() if hasattr(v, "ndim")}
            kind_i = kinds[li]

            def f(x, p, actx, _stat=stat, _kind=kind_i, _li=li):
                return _decoder_layer(x, p, cfg, _kind, spec,
                                      {**_stat, **actx}, None, _li)

            x, _ = jax.checkpoint(f)(x, p, arrs)
        else:
            x, state = _decoder_layer(x, p, cfg, kinds[li], spec, lctx,
                                      state, li)
    if state is not None and cross:     # prefill: the cache keeps cross K/V
        state["cross_k"] = jnp.stack([ck for ck, _ in cross])
        state["cross_v"] = jnp.stack([cv for _, cv in cross])
    return x, state


def _unstack_blocks(params, cfg: ModelConfig) -> list:
    prefix, period, n_blocks = block_structure(cfg)
    out = []
    for b in range(n_blocks):
        for slot in range(period):
            out.append(jax.tree.map(lambda a: a[b], params["blocks"][slot]))
    return out


def prefill(params, cfg: ModelConfig, tokens: jax.Array, cache: dict,
            embeds=None, enc_embeds=None, mesh=None
            ) -> tuple[jax.Array, dict]:
    """Process the prompt, fill the cache, return last-token logits."""
    x = _embed(params, cfg, tokens, embeds)
    s = x.shape[1]
    positions = jnp.arange(s)
    sin, cos = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
    ctx = {"mode": "prefill", "sin": sin, "cos": cos, "positions": positions,
           "mesh": mesh}
    if cfg.encoder_layers:
        ctx["enc_out"] = _encode(params, cfg, enc_embeds)
        x = x + params["dec_pos"][None, :s, :]
    x, state = _run_decoder_with_cross(params, cfg, x, ctx, cache)
    slot_pos = None
    if "slot_pos" in cache:
        cap = cache["slot_pos"].shape[0]
        idx = jnp.arange(cap)
        if s <= cap:
            slot_pos = jnp.where(idx < s, idx, _POS_SENTINEL)
        else:       # ring layout: slot j holds position p=start+((j-start)%C)
            start = s - cap
            slot_pos = start + (idx - start) % cap
    new_cache = _merge_cache(cache, state, jnp.int32(s), slot_pos)
    return _logits(params, cfg, x[:, -1:, :]), new_cache


def decode_step(params, cfg: ModelConfig, token: jax.Array, cache: dict,
                mesh=None) -> tuple[jax.Array, dict]:
    """One serving step: token (B,) int32 -> (logits (B,1,V), new cache)."""
    x = jnp.take(params["embed"], token[:, None], axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    pos = cache["pos"]
    if cfg.encoder_layers:
        npos = params["dec_pos"].shape[0]
        x = x + params["dec_pos"][jnp.minimum(pos, npos - 1)][None, None, :]
    slot_pos = None
    if "slot_pos" in cache:   # tag the new token's slot *before* attention
        c = cache["slot_pos"].shape[0]
        slot_pos = cache["slot_pos"].at[pos % c].set(pos)
    ctx = {"mode": "decode", "pos": pos, "slot_pos": slot_pos,
           "enc_out": None, "mesh": mesh}
    x, state = _run_decoder_with_cross(params, cfg, x, ctx, cache)
    new_cache = _merge_cache(cache, state, pos + 1, slot_pos)
    return _logits(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# slot-addressable serving (continuous batching)
# ---------------------------------------------------------------------------

@jax.named_scope("kv_ring")
def _tag_slots(slot_pos: jax.Array, positions: jax.Array) -> jax.Array:
    """Tag each row's ring slots with this step's absolute positions.
    slot_pos: (B,C), positions: (B,T) -> updated (B,C).  One batched
    scatter (T <= C keeps the slots distinct, like the K/V scatter)."""
    b, t = positions.shape
    c = slot_pos.shape[1]
    rows = jnp.arange(b)[:, None]
    return slot_pos.at[rows, positions % c].set(positions,
                                                unique_indices=True)


def _slots_ctx(cache: dict, positions: jax.Array, mesh) -> tuple[dict, Any]:
    slot_pos = None
    if "slot_pos" in cache:
        slot_pos = _tag_slots(cache["slot_pos"], positions)
    ctx = {"mode": "slots", "pos": cache["pos"], "positions": positions,
           "slot_pos": slot_pos, "enc_out": None, "mesh": mesh}
    return ctx, slot_pos


def decode_slots(params, cfg: ModelConfig, token: jax.Array, cache: dict,
                 mesh=None) -> tuple[jax.Array, dict]:
    """One continuous-batching step: token (B,) against a per-slot cache
    (``CacheSpec(per_slot=True)``: pos (B,), slot_pos (B,C)).  Every slot
    advances by one token at its *own* position -> (logits (B,1,V), cache).
    """
    if cfg.encoder_layers:
        raise ValueError("decode_slots does not support enc-dec models")
    x = jnp.take(params["embed"], token[:, None], axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    pos = cache["pos"]                                  # (B,)
    positions = pos[:, None]                            # (B,1)
    ctx, slot_pos = _slots_ctx(cache, positions, mesh)
    x, state = _run_decoder(params, cfg, x, ctx, cache)
    new_cache = _merge_cache(cache, state, pos + 1, slot_pos)
    return _logits(params, cfg, x), new_cache


def chunk_prefill(params, cfg: ModelConfig, tokens: jax.Array, cache: dict,
                  mesh=None) -> tuple[jax.Array, dict]:
    """Advance a per-slot cache by a chunk of T prompt tokens.

    tokens: (B,T) starting at each row's ``cache["pos"]``.  Attention runs
    against the ring cache (earlier chunks' K/V plus this chunk's, tagged
    by absolute position), so while the prompt fits the ring (total length
    <= C) any chunking — interleaved with other requests' decode steps —
    is bit-identical to a single pass.  Beyond capacity the ring is
    already a sliding-window approximation and a chunk's writes land
    before its tokens attend, so chunk boundaries decide which of the
    oldest in-window keys survive — the same class of approximation as
    lock-step ``prefill`` keeping the last C rows of full attention.
    Returns (last-token logits (B,1,V), cache)."""
    if cfg.encoder_layers:
        raise ValueError("chunk_prefill does not support enc-dec models")
    b, t = tokens.shape
    x = _embed(params, cfg, tokens, None)
    pos = cache["pos"]                                  # (B,)
    positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    if "slot_pos" in cache and t > cache["slot_pos"].shape[1]:
        raise ValueError(f"chunk of {t} tokens exceeds cache capacity "
                         f"{cache['slot_pos'].shape[1]}")
    ctx, slot_pos = _slots_ctx(cache, positions, mesh)
    x, state = _run_decoder(params, cfg, x, ctx, cache)
    new_cache = _merge_cache(cache, state, pos + t, slot_pos)
    return _logits(params, cfg, x[:, -1:, :]), new_cache


def _slot_batch_axis(key: str) -> int:
    """Axis of the request/slot dim in a cache leaf."""
    return 0 if key in ("pos", "slot_pos") else 1


def cache_insert_slot(cache: dict, slot: jax.Array, req_cache: dict) -> dict:
    """Insert a prefilled single-request cache (batch 1) into ``slot`` of a
    per-slot batch cache.  Shapes must agree except the slot/batch dim."""
    out = {}
    for key, buf in cache.items():
        out[key] = lax.dynamic_update_slice_in_dim(
            buf, req_cache[key].astype(buf.dtype), slot,
            axis=_slot_batch_axis(key))
    return out


def cache_extract_slot(cache: dict, slot: jax.Array) -> dict:
    """Inverse of ``cache_insert_slot``: slice one request's batch-1 cache
    (KV ring plus ``pos``/``slot_pos`` metadata) out of a per-slot batch
    cache.  Extract-then-insert round-trips bit-identically — the spill
    path of the serve engine's ``offload_slot``/``refill_slot``."""
    return {key: lax.dynamic_slice_in_dim(buf, slot, 1,
                                          axis=_slot_batch_axis(key))
            for key, buf in cache.items()}


def cache_evict_slot(cache: dict, slot: jax.Array) -> dict:
    """Free a slot: reset its position and mask every ring tag so the stale
    K/V is unreachable.  The buffers themselves are left in place."""
    out = dict(cache)
    out["pos"] = lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.zeros((1,), jnp.int32), slot, axis=0)
    if "slot_pos" in cache:
        c = cache["slot_pos"].shape[1]
        out["slot_pos"] = lax.dynamic_update_slice_in_dim(
            cache["slot_pos"], jnp.full((1, c), _POS_SENTINEL, jnp.int32),
            slot, axis=0)
    return out


def loss_fn(params, cfg: ModelConfig, batch: dict,
            remat: bool = True, mesh=None, layout: str = "tp") -> jax.Array:
    logits = forward_train(params, cfg, batch["tokens"],
                           embeds=batch.get("embeds"),
                           enc_embeds=batch.get("enc_embeds"), remat=remat,
                           mesh=mesh, layout=layout)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:   # vision prefix: score text only
        logits = logits[:, -labels.shape[1]:, :]
    return softmax_xent(logits, labels, batch.get("mask"))
