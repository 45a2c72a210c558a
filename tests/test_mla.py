"""Latent attention and the sigmoid router with a held share of experts
(Kimi-K2 / DeepSeek-V3): each piece against the source's formulas,
written out here in numpy or in expanded form."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.layers import Yarn, rope_tables


def _source_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV3YarnRotaryEmbedding._set_cos_sin_cache``'s inverse
    frequencies, line by line."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


@pytest.mark.parametrize("beta_fast,beta_slow", [(1.0, 1.0), (32.0, 1.0)])
def test_yarn_tables_match_the_source(beta_fast, beta_slow):
    """Kimi-K2's yarn (factor 32 over 4096 positions, base 50000, 64 rope
    dims): dims 0-19 keep their frequency and 20-31 are divided by 32
    with beta 1/1; a ramp lies between with 32/1 (Kimi-K2.5)."""
    cfg = get_config("kimi_k2_1t_a32b")
    yarn = Yarn(32.0, 4096, beta_fast, beta_slow, 1.0, 1.0)
    pos = jnp.array([0, 1, 3, 4095, 4096])
    sin, cos = rope_tables(pos, cfg.qk_rope_head_dim, cfg.rope_theta, yarn)
    inv = _source_inv_freq(64, 50000.0, 32.0, 4096, beta_fast, beta_slow)
    # position 1's angle is each frequency itself
    np.testing.assert_allclose(np.arctan2(np.asarray(sin[1]),
                                          np.asarray(cos[1])), inv, rtol=1e-5)
    ang = np.asarray(pos, np.float64)[:, None] * inv
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=1e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=1e-3)
    plain = 1.0 / 50000.0 ** (np.arange(32) / 32)
    if beta_fast == 1.0:
        np.testing.assert_allclose(inv[:20], plain[:20], rtol=1e-6)
        np.testing.assert_allclose(inv[20:], plain[20:] / 32, rtol=1e-6)
    else:
        ratio = inv / plain
        assert np.all((ratio <= 1 + 1e-6) & (ratio >= 1 / 32 - 1e-6))
        assert np.any((ratio < 0.99) & (ratio > 1 / 32 + 0.01))  # the ramp


def test_kimi_softmax_scale():
    """192^-0.5 times (0.1 ln 32 + 1)^2."""
    cfg = get_config("kimi_k2_1t_a32b")
    want = 192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2
    assert T.mla_softmax_scale(cfg) == pytest.approx(want, rel=1e-12)
    assert (0.1 * math.log(32) + 1) ** 2 == pytest.approx(1.8133, abs=1e-4)


def _kimi_tiny(**kw):
    return dataclasses.replace(get_smoke_config("kimi_k2_1t_a32b"),
                               param_dtype="float32", **kw)


def test_sigmoid_routing_matches_numpy_top_k():
    """Float32 logits, sigmoid scores, the top k of scores plus the bias,
    and weights that are the chosen scores (the bias picks, it does not
    weigh), renormalised and scaled."""
    cfg = _kimi_tiny(moe_experts=16, moe_top_k=4, moe_experts_held=4)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, cfg.d_model)).astype(np.float32)
    router = (rng.standard_normal((cfg.d_model, 16)) * 0.2).astype(np.float32)
    bias = (rng.standard_normal(16) * 0.3).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        w, idx = M.sigmoid_route(jnp.asarray(x), {
            "router": jnp.asarray(router),
            "router_bias": jnp.asarray(bias)}, cfg)
    w, idx = np.asarray(w), np.asarray(idx)
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ router)))
    want_idx = np.argsort(-(scores + bias), axis=1)[:, :4]
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want_idx, 1))
    top = np.take_along_axis(scores, idx, 1)
    want_w = top / top.sum(1, keepdims=True) * cfg.moe_routed_scale
    np.testing.assert_allclose(w, want_w, rtol=2e-5)
    # the bias changed the choice somewhere, so it was tested
    assert np.any(np.sort(np.argsort(-scores, 1)[:, :4], 1)
                  != np.sort(want_idx, 1))


def test_absorbed_scores_equal_expanded_attention():
    """q_nope through w_uk scored against the latent, plus q_pe against
    the shared rope key, equals expanded per-head keys k = latent @ w_uk
    and values latent @ w_uv, in float32."""
    cfg = _kimi_tiny()
    p = T.init_params(jax.random.PRNGKey(3), cfg)["prefix"][0]["attn"]
    h, nope, rope, dv = 4, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    b, t, s = 2, 3, 7
    q_nope = jax.random.normal(ks[0], (b, t, h, nope))
    q_pe = jax.random.normal(ks[1], (b, t, h, rope))
    ckv = jax.random.normal(ks[2], (b, s, cfg.kv_lora_rank))
    kpe = jax.random.normal(ks[3], (b, s, rope))
    bias = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(t)[:, None] + 4,
                     0.0, -jnp.inf)[None, None]
    with jax.default_matmul_precision("highest"):
        got = T.mla_attend(q_nope, q_pe, ckv, kpe, bias, p, cfg)
        k_nope = jnp.einsum("bsc,hnc->bshn", ckv, p["w_uk"])
        v = jnp.einsum("bsc,hvc->bshv", ckv, p["w_uv"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            kpe[:, :, None], (b, s, h, rope))], -1)
        q = jnp.concatenate([q_nope, q_pe], -1)
        scores = jnp.einsum("bthe,bshe->bhts", q, k) * T.mla_softmax_scale(
            cfg) + bias
        want = jnp.einsum("bhts,bshv->bthv", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_latent_ring_round_trips_through_slot_ops():
    """``cache_extract_slot`` then ``cache_insert_slot`` move a slot's
    ``ckv``/``kpe`` (and its tags) bit for bit; eviction masks its tags."""
    cfg = get_smoke_config("kimi_k2_1t_a32b")
    spec = T.CacheSpec(capacity=8, batch=3, per_slot=True)
    cache = T.init_cache(cfg, spec)
    assert cache["ckv"].shape == (cfg.num_layers, 3, 8, cfg.kv_lora_rank)
    assert cache["kpe"].shape == (cfg.num_layers, 3, 8, cfg.qk_rope_head_dim)
    assert "k" not in cache and "v" not in cache
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    cache = {**cache,
             "ckv": jax.random.normal(keys[0], cache["ckv"].shape,
                                      cache["ckv"].dtype),
             "kpe": jax.random.normal(keys[1], cache["kpe"].shape,
                                      cache["kpe"].dtype),
             "slot_pos": jnp.arange(24, dtype=jnp.int32).reshape(3, 8),
             "pos": jnp.array([5, 6, 7], jnp.int32)}
    one = T.cache_extract_slot(cache, jnp.int32(1))
    assert one["ckv"].shape == (cfg.num_layers, 1, 8, cfg.kv_lora_rank)
    moved = T.cache_insert_slot(T.cache_evict_slot(cache, jnp.int32(2)),
                                jnp.int32(2), one)
    for key in ("ckv", "kpe", "slot_pos", "pos"):
        axis = 0 if key in ("pos", "slot_pos") else 1
        np.testing.assert_array_equal(
            np.take(np.asarray(moved[key]), 2, axis),
            np.take(np.asarray(cache[key]), 1, axis))
        np.testing.assert_array_equal(
            np.take(np.asarray(moved[key]), 0, axis),
            np.take(np.asarray(cache[key]), 0, axis))
    evicted = T.cache_evict_slot(cache, jnp.int32(0))
    assert np.all(np.asarray(evicted["slot_pos"][0]) == T._POS_SENTINEL)


def test_unimplemented_modes_raise():
    """An int8 latent ring and a softmax router over a held share are not
    implemented: each says so instead of computing something else."""
    cfg = get_smoke_config("kimi_k2_1t_a32b")
    with pytest.raises(ValueError):
        T.init_cache(cfg, T.CacheSpec(capacity=8, batch=1,
                                      kv_dtype=jnp.int8))
    soft = dataclasses.replace(get_smoke_config("llama4_maverick_400b_a17b"),
                               moe_experts_held=2)
    p = T.init_params(jax.random.PRNGKey(0), soft)
    with pytest.raises(ValueError):
        T.forward_train(p, soft, jnp.zeros((1, 4), jnp.int32), remat=False)


def test_mla_slot_step_counts_rows_per_held_expert():
    """The counted slot step: each expert layer's routed rows that land on
    each held expert, at most one row per slot per expert; its logits and
    cache are the plain step's.  A config with no held share refuses."""
    cfg = dataclasses.replace(get_smoke_config("kimi_k2_1t_a32b"),
                              moe_experts=16, moe_top_k=4,
                              moe_experts_held=4, moe_held_offset=4)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    def cache():
        return T.init_cache(cfg, T.CacheSpec(capacity=8, batch=3,
                                             per_slot=True))
    tok = jnp.array([1, 2, 3])
    logits, counted, rows = T.decode_slots_counted(params, cfg, tok, cache())
    rows = np.asarray(rows)
    assert rows.shape == (cfg.num_layers - cfg.moe_first_dense, 4)
    assert np.all(rows <= 3) and rows.sum() > 0
    ref, plain = T.decode_slots(params, cfg, tok, cache())
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))
    assert sorted(counted) == sorted(plain)
    for key in plain:
        np.testing.assert_array_equal(np.asarray(counted[key]),
                                      np.asarray(plain[key]))
    with pytest.raises(ValueError):
        dense = get_smoke_config("qwen3_14b")
        T.decode_slots_counted(
            T.init_params(jax.random.PRNGKey(0), dense), dense, tok,
            T.init_cache(dense, T.CacheSpec(capacity=8, batch=3,
                                            per_slot=True)))


def test_engine_counted_step_decodes_as_the_plain_step(mesh11):
    """``ServeEngine.step(count_experts=True)`` hands back the tokens and
    logits the plain step gives and keeps the rows; the plain step keeps
    none."""
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke_config("kimi_k2_1t_a32b"),
                              moe_experts=16, moe_top_k=4,
                              moe_experts_held=4, moe_held_offset=4)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(batch=3, cache_capacity=16, prefill_chunk=8)
    counted, plain = (ServeEngine(cfg, mesh11, params, scfg)
                      for _ in range(2))
    tok = jnp.array([1, 2, 3], jnp.int32)
    np.testing.assert_array_equal(np.asarray(counted.step(tok, True)),
                                  np.asarray(plain.step(tok)))
    np.testing.assert_array_equal(np.asarray(counted.last_logits),
                                  np.asarray(plain.last_logits))
    assert counted.last_expert_rows.shape == (cfg.num_layers - 1, 4)
    assert plain.last_expert_rows is None


def test_latent_ring_bytes_drive_the_tier_budget():
    """``tier_kv_capacity`` prices a token by the rings ``init_cache``
    builds: the latent and its rope key, not 2 * heads * head_dim."""
    from repro.serve.engine import _ring_bytes_per_token

    cfg = get_config("kimi_k2_1t_a32b")
    assert _ring_bytes_per_token(cfg, "bfloat16") == 61 * (512 + 64) * 2
    gqa = get_config("qwen3_14b")
    assert _ring_bytes_per_token(gqa, "bfloat16") == 40 * 2 * 8 * 128 * 2
