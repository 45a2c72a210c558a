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

Caches are stacked over layers (leading dim ``num_layers``) and threaded
through the scan as xs/ys slices.  KV caches are ring buffers of capacity
``C``: exact attention while ``pos < C`` and sliding-window semantics
beyond — full-attention serving sizes ``C = seq_len``, SWA archs size
``C = window`` (how hymba/danube hold ``long_500k`` state in O(window)).

Every op carries a named scope in its metadata, so a profiler trace can
split a step by them: ``layer`` (one decoder layer), ``attn``, ``mlp``,
``head``, and ``kv_ring`` for every move of the cache (the ring stacked
into and out of the layer scan, the per-layer slice, the merge, the
position tags and the write of new K/V).
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


@jax.named_scope("attn")
def _self_attention_decode(x, p, cfg: ModelConfig, spec: AttnSpec,
                           k_cache, v_cache, kq_scales, slot_pos, pos):
    """x: (B,1,d) one token at absolute position ``pos`` against the ring
    cache (B,Hkv,C,hd).  Returns (out, new_k_slice, new_v_slice)."""
    b, s, d = x.shape
    c = k_cache.shape[2]
    q, k_new, v_new = _project_qkv(x, p, cfg)         # (B,H,1,hd)
    sin, cos = rope_tables(jnp.full((1,), pos), spec.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k_new = apply_rope(k_new, sin, cos)
    slot = pos % c
    if kq_scales is not None:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        with jax.named_scope("kv_ring"):
            k_cache = lax.dynamic_update_slice_in_dim(k_cache, kq, slot, 2)
            v_cache = lax.dynamic_update_slice_in_dim(v_cache, vq, slot, 2)
            k_sc = lax.dynamic_update_slice_in_dim(kq_scales[0], ks, slot, 2)
            v_sc = lax.dynamic_update_slice_in_dim(kq_scales[1], vs, slot, 2)
        k = _dequantize_kv(k_cache, k_sc)
        v = _dequantize_kv(v_cache, v_sc)
        new_scales = (k_sc, v_sc)
    else:
        with jax.named_scope("kv_ring"):
            k_cache = lax.dynamic_update_slice_in_dim(
                k_cache, k_new.astype(k_cache.dtype), slot, axis=2)
            v_cache = lax.dynamic_update_slice_in_dim(
                v_cache, v_new.astype(v_cache.dtype), slot, axis=2)
        k, v = k_cache, v_cache
        new_scales = None
    bias = attn_mask_bias(spec, jnp.full((1,), pos), slot_pos)
    out = gqa_attention(q, k.astype(q.dtype), v.astype(q.dtype), bias, spec)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return linear(out, p["w_o"]), k_cache, v_cache, new_scales


@jax.named_scope("attn")
def _self_attention_slots(x, p, cfg: ModelConfig, spec: AttnSpec,
                          k_cache, v_cache, kq_scales, slot_pos, positions):
    """Per-slot cached attention: every batch row sits at its own absolute
    position.  x: (B,T,d), positions: (B,T) per-row token positions,
    slot_pos: (B,C) per-row ring tags (already updated for this step's
    writes), caches (B,Hkv,C,hd).  Serves both the continuous-batching
    decode step (T=1, B=slots) and chunked prefill (B=1, T=chunk)."""
    b, t, d = x.shape
    c = k_cache.shape[2]
    q, k_new, v_new = _project_qkv(x, p, cfg)          # (B,H,T,hd)
    sin, cos = rope_tables(positions, spec.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k_new = apply_rope(k_new, sin, cos)

    # scatter the T new K/V rows into each row's ring slots: touches only
    # the T written slots (T <= C keeps them distinct), so with donated
    # buffers the per-token write is O(T), not O(C)
    rows = jnp.arange(b)[:, None]                      # (B,1)
    slots = positions % c                              # (B,T)

    @jax.named_scope("kv_ring")
    def scatter(buf, val):                             # val: (B,H,T,*)
        return buf.at[rows, :, slots, :].set(
            val.astype(buf.dtype).transpose(0, 2, 1, 3),
            unique_indices=True)

    if kq_scales is not None:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        k_cache = scatter(k_cache, kq)
        v_cache = scatter(v_cache, vq)
        k_sc = scatter(kq_scales[0], ks)
        v_sc = scatter(kq_scales[1], vs)
        k = _dequantize_kv(k_cache, k_sc)
        v = _dequantize_kv(v_cache, v_sc)
        new_scales = (k_sc, v_sc)
    else:
        k_cache = scatter(k_cache, k_new)
        v_cache = scatter(v_cache, v_new)
        k, v = k_cache, v_cache
        new_scales = None

    # per-row additive mask from the ring tags (sentinel slots mask out)
    ok = slot_pos[:, None, :] <= positions[:, :, None]
    if spec.sliding_window:
        ok &= slot_pos[:, None, :] > positions[:, :, None] - spec.sliding_window
    bias = jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)
    bias = bias[:, None, None, :, :]                   # (B,1,1,T,C)
    out = gqa_attention(q, k.astype(q.dtype), v.astype(q.dtype), bias, spec)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, -1)
    return linear(out, p["w_o"]), k_cache, v_cache, new_scales


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
                   ctx: dict, layer_cache: Optional[dict]):
    """Apply one decoder layer.  Returns (x, updated layer cache slices)."""
    new_cache: dict = {}
    if kind == "rwkv":
        state = layer_cache.get("rwkv_state") if layer_cache else None
        x, new_state = rwkv_mod.rwkv_block(x, p, cfg, state)
        if layer_cache is not None:
            new_cache["rwkv_state"] = new_state
        return x, new_cache

    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if ctx["mode"] == "decode":
        attn_out, k_c, v_c, scales = _self_attention_decode(
            h, p["attn"], cfg, spec, layer_cache["k"], layer_cache["v"],
            layer_cache.get("scales"), ctx["slot_pos"], ctx["pos"])
        new_cache.update(k=k_c, v=v_c)
        if scales is not None:
            new_cache["scales"] = scales
    elif ctx["mode"] == "slots":
        attn_out, k_c, v_c, scales = _self_attention_slots(
            h, p["attn"], cfg, spec, layer_cache["k"], layer_cache["v"],
            layer_cache.get("scales"), ctx["slot_pos"], ctx["positions"])
        new_cache.update(k=k_c, v=v_c)
        if scales is not None:
            new_cache["scales"] = scales
    else:
        attn_out, k, v = _self_attention_full(
            h, p["attn"], cfg, spec, ctx["sin"], ctx["cos"],
            ctx["positions"], ctx.get("mesh"), ctx.get("layout", "tp"))
        if layer_cache is not None:   # prefill: write the cache
            c = layer_cache["k"].shape[2]
            s = k.shape[2]
            kw = k[:, :, -c:, :]
            vw = v[:, :, -c:, :]
            if layer_cache["k"].dtype == jnp.int8:
                kq, ks = _quantize_kv(kw)
                vq, vs = _quantize_kv(vw)
                new_cache.update(
                    k=_fill_ring(layer_cache["k"], kq, s),
                    v=_fill_ring(layer_cache["v"], vq, s),
                    scales=(_fill_ring(layer_cache["scales"][0], ks, s),
                            _fill_ring(layer_cache["scales"][1], vs, s)))
            else:
                new_cache.update(
                    k=_fill_ring(layer_cache["k"], kw, s),
                    v=_fill_ring(layer_cache["v"], vw, s))

    if cfg.hybrid_parallel_ssm:
        state = layer_cache.get("ssm_state") if layer_cache else None
        ssm_out, new_state = ssm_mod.ssm_branch(h, p["ssm"], cfg, state)
        attn_out = attn_out + ssm_out
        if layer_cache is not None:
            new_cache["ssm_state"] = new_state
    x = x + attn_out

    if cfg.encoder_layers:
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps)
        x = x + _cross_attention(hx, p["xattn"], cfg,
                                 ctx["cross_k"], ctx["cross_v"],
                                 ctx.get("mesh"))

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + _ffn(h2, p, cfg, kind, ctx["mode"], ctx.get("mesh"))
    return x, new_cache


@jax.named_scope("kv_ring")
def _fill_ring(buf: jax.Array, val: jax.Array, total_seq: int) -> jax.Array:
    """Write a prefill's last-C tokens into the ring with the true ring
    layout: position ``p`` lands at slot ``p % C`` (so later decode steps
    evict exactly the token leaving the window)."""
    c = buf.shape[2]
    s = val.shape[2]           # = min(total_seq, c)
    if s < c:
        val = jnp.pad(val, ((0, 0), (0, 0), (0, c - s), (0, 0)))
    shift = (total_seq - s) % c
    if shift:
        val = jnp.roll(val, shift, axis=2)
    return val.astype(buf.dtype)


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


def _run_decoder(params, cfg: ModelConfig, x, ctx, cache, remat=False):
    """Apply prefix layers then the scanned blocks; threads per-layer cache
    slices in/out.  Returns (x, new_cache_layers: list aligned to layers)."""
    prefix, period, n_blocks = block_structure(cfg)
    kinds = layer_kinds(cfg)
    spec = attn_spec(cfg)
    new_layers: list[Optional[dict]] = [None] * cfg.num_layers

    @jax.named_scope("kv_ring")
    def cache_slice(li):
        if cache is None:
            return None
        out = {}
        for key in ("k", "v", "rwkv_state", "ssm_state"):
            if key in cache:
                out[key] = cache[key][li]
        if "k_scale" in cache:
            out["scales"] = (cache["k_scale"][li], cache["v_scale"][li])
        return out

    for li in range(prefix):
        lc = cache_slice(li)
        x, nc = _decoder_layer(x, params["prefix"][li], cfg, kinds[li],
                               spec, ctx, lc)
        new_layers[li] = nc

    if n_blocks:
        xs_cache = None
        if cache is not None:
            @jax.named_scope("kv_ring")
            def stack_blocks(arr):
                L = arr.shape[0]
                body = arr[prefix:prefix + n_blocks * period]
                return body.reshape((n_blocks, period) + arr.shape[1:])
            xs_cache = {}
            for key in ("k", "v", "rwkv_state", "ssm_state"):
                if key in cache:
                    xs_cache[key] = stack_blocks(cache[key])
            if "k_scale" in cache:
                xs_cache["scales"] = (stack_blocks(cache["k_scale"]),
                                      stack_blocks(cache["v_scale"]))

        def block_body(x, xs):
            bparams, bcache = xs
            outs = []
            for slot in range(period):
                kind = kinds[prefix + slot]
                with jax.named_scope("kv_ring"):
                    lc = (jax.tree.map(lambda a: a[slot], bcache)
                          if bcache is not None else None)
                x, nc = _decoder_layer(x, bparams[slot], cfg, kind,
                                       spec, ctx, lc)
                outs.append(nc)
            with jax.named_scope("kv_ring"):
                ys = (jax.tree.map(lambda *zs: jnp.stack(zs), *outs)
                      if outs[0] else None)
            x = _seq_shard_hidden(x, ctx.get("mesh"))
            return x, ys

        if remat:
            block_body = jax.checkpoint(block_body)
        bparams = tuple(params["blocks"])
        if cfg.unroll_scan:
            # Python loop: accounting variants (cost_analysis counts every
            # unrolled body; a while/scan body is counted once)
            ys_list = []
            for bi in range(n_blocks):
                xs_i = jax.tree.map(lambda a: a[bi], (bparams, xs_cache))
                x, ys_i = block_body(x, xs_i)
                ys_list.append(ys_i)
            ys = (jax.tree.map(lambda *zs: jnp.stack(zs), *ys_list)
                  if ys_list and ys_list[0] is not None else None)
        else:
            x, ys = lax.scan(block_body, x, (bparams, xs_cache))
        if ys is not None:
            # unstack ys back into per-layer entries
            with jax.named_scope("kv_ring"):
                flat = jax.tree.map(lambda a: a.reshape(
                    (n_blocks * period,) + a.shape[2:]), ys)
                for off in range(n_blocks * period):
                    new_layers[prefix + off] = jax.tree.map(
                        lambda a: a[off], flat)
    return x, new_layers


@jax.named_scope("kv_ring")
def _merge_cache(cfg: ModelConfig, cache: dict, new_layers, new_pos,
                 slot_pos=None) -> dict:
    out = dict(cache)
    if new_layers[0] is None and all(nl is None for nl in new_layers):
        out["pos"] = new_pos
        return out

    def gather(key, sub=None):
        vals = []
        for nl in new_layers:
            v = nl[key]
            if sub is not None:
                v = v[sub]
            vals.append(v)
        return jnp.stack(vals)

    any_layer = new_layers[0]
    if "k" in any_layer:
        out["k"] = gather("k")
        out["v"] = gather("v")
        if "scales" in any_layer:
            out["k_scale"] = gather("scales", 0)
            out["v_scale"] = gather("scales", 1)
    for key in ("rwkv_state", "ssm_state"):
        if key in any_layer:
            out[key] = gather(key)
    out["pos"] = new_pos
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
    new_layers = [None] * cfg.num_layers
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    all_params = ([params["prefix"][i] for i in range(len(params["prefix"]))]
                  + _unstack_blocks(params, cfg))
    for li in range(cfg.num_layers):
        p = all_params[li]
        if "enc_out" in ctx and ctx["enc_out"] is not None:
            eo = ctx["enc_out"]
            b, es, d = eo.shape
            ck = linear(eo, p["xattn"]["w_k"]).reshape(
                b, es, nkv, hd).transpose(0, 2, 1, 3)
            cv = linear(eo, p["xattn"]["w_v"]).reshape(
                b, es, nkv, hd).transpose(0, 2, 1, 3)
        else:
            ck = cache["cross_k"][li]
            cv = cache["cross_v"][li]
        lctx = dict(ctx)
        lctx["cross_k"], lctx["cross_v"] = ck, cv
        lc = None
        if cache is not None:
            lc = {"k": cache["k"][li], "v": cache["v"][li]}
            if "k_scale" in cache:
                lc["scales"] = (cache["k_scale"][li], cache["v_scale"][li])
        if remat and ctx["mode"] == "train" and lc is None:
            # remat per layer; only array leaves may cross the checkpoint
            stat = {k: v for k, v in lctx.items() if not hasattr(v, "ndim")}
            arrs = {k: v for k, v in lctx.items() if hasattr(v, "ndim")}
            kind_i = kinds[li]

            def f(x, p, actx, _stat=stat, _kind=kind_i):
                return _decoder_layer(x, p, cfg, _kind, spec,
                                      {**_stat, **actx}, None)

            x, nc = jax.checkpoint(f)(x, p, arrs)
        else:
            x, nc = _decoder_layer(x, p, cfg, kinds[li], spec, lctx, lc)
        if cache is not None:
            nc["cross_k"], nc["cross_v"] = ck, cv
        new_layers[li] = nc
    return x, new_layers


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
    x, new_layers = _run_decoder_with_cross(params, cfg, x, ctx, cache)
    slot_pos = None
    if "slot_pos" in cache:
        cap = cache["slot_pos"].shape[0]
        idx = jnp.arange(cap)
        if s <= cap:
            slot_pos = jnp.where(idx < s, idx, _POS_SENTINEL)
        else:       # ring layout: slot j holds position p=start+((j-start)%C)
            start = s - cap
            slot_pos = start + (idx - start) % cap
    new_cache = _merge_cache(cfg, cache, new_layers, jnp.int32(s), slot_pos)
    if cfg.encoder_layers and new_layers[0] is not None:
        new_cache["cross_k"] = jnp.stack([nl["cross_k"] for nl in new_layers])
        new_cache["cross_v"] = jnp.stack([nl["cross_v"] for nl in new_layers])
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
    x, new_layers = _run_decoder_with_cross(params, cfg, x, ctx, cache)
    new_cache = _merge_cache(cfg, cache, new_layers, pos + 1, slot_pos)
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
    x, new_layers = _run_decoder(params, cfg, x, ctx, cache)
    new_cache = _merge_cache(cfg, cache, new_layers, pos + 1, slot_pos)
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
    x, new_layers = _run_decoder(params, cfg, x, ctx, cache)
    new_cache = _merge_cache(cfg, cache, new_layers, pos + t, slot_pos)
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
