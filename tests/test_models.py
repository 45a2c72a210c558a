"""Per-arch smoke tests (assignment requirement) + model invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, PAPER_MODEL_IDS, get_config, \
    get_smoke_config
from repro.models import transformer as T
from repro.models.frontends import frontend_embeddings


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, rng):
    """Reduced same-family config: one forward + one train step on CPU,
    output shapes verified, no NaNs (the assignment's per-arch smoke)."""
    cfg = get_smoke_config(arch)
    params = T.init_params(rng, cfg)
    B, S = 2, 16
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    batch.update(frontend_embeddings(cfg, B))

    logits = T.forward_train(params, cfg, tokens, remat=False,
                             **frontend_embeddings(cfg, B))
    exp_s = S + (cfg.vision_patches if cfg.frontend == "vision_stub" else 0)
    assert logits.shape == (B, exp_s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    loss, grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, cfg, batch, remat=False))(params)
    assert bool(jnp.isfinite(loss))
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert gn > 0, "gradients all zero"


@pytest.mark.parametrize("arch", ["qwen3_14b", "whisper_tiny"])
def test_forward_with_forced_fused_mlp(arch, rng):
    """The fused-MLP runtime path (interpret-mode Pallas, GLU and plain
    variants) must agree with the CPU einsum path at kernel tolerance —
    the model-level twin of the kernels/fused_mlp parity sweep, proving
    the §8 runtime wiring in models.layers.mlp changes backend, not
    semantics."""
    from repro.kernels.dispatch import force_kernels
    cfg = get_smoke_config(arch)
    params = T.init_params(rng, cfg)
    B, S = 1, 8
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    fe = frontend_embeddings(cfg, B)
    ref = T.forward_train(params, cfg, tokens, remat=False, **fe)
    with force_kernels():
        out = T.forward_train(params, cfg, tokens, remat=False, **fe)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-9
    assert err / scale < 2e-2, f"{arch}: fused path diverges ({err})"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch, rng):
    """prefill(S-1) + decode(1) last-token logits == forward(S) last-token
    logits — the serving-correctness invariant (MoE: dropless capacity)."""
    cfg = get_smoke_config(arch)
    if cfg.moe_experts:
        cfg = dataclasses.replace(cfg,
                                  moe_capacity_factor=float(cfg.moe_experts))
    params = T.init_params(rng, cfg)
    B, S = 2, 12
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    fe = frontend_embeddings(cfg, B)
    full = T.forward_train(params, cfg, tokens, remat=False, **fe)
    cache = T.init_cache(cfg, T.CacheSpec(capacity=S + 4, batch=B))
    _, cache = T.prefill(params, cfg, tokens[:, :S - 1], cache, **fe)
    ld, _ = T.decode_step(params, cfg, tokens[:, S - 1], cache)
    err = float(jnp.max(jnp.abs(full[:, -1].astype(jnp.float32)
                                - ld[:, 0].astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(full[:, -1]))) + 1e-9
    assert err / scale < 0.02, f"{arch}: decode diverges from forward"


SLOT_ARCHS = [a for a in ARCH_IDS if a != "whisper_tiny"]   # no enc-dec slots


def _slot_cache(params, cfg, prompts, capacity, chunk, kv_dtype):
    """Prefill each row's prompt with ``chunk_prefill`` in chunks of
    ``chunk`` into a request cache and insert it as slot r.  Returns the
    per-slot cache and each prompt's last-token logits."""
    step = jax.jit(lambda p, t, c: T.chunk_prefill(p, cfg, t, c))
    spec = dict(capacity=capacity, kv_dtype=kv_dtype, per_slot=True)
    cache = T.init_cache(cfg, T.CacheSpec(batch=len(prompts), **spec))
    last = []
    for r, prompt in enumerate(prompts):
        rc = T.init_cache(cfg, T.CacheSpec(batch=1, **spec))
        for a in range(0, len(prompt), chunk):
            logits, rc = step(params, prompt[None, a:a + chunk], rc)
        cache = T.cache_insert_slot(cache, jnp.int32(r), rc)
        last.append(logits[0, -1])
    return cache, last


def _slots_against_forward(cfg, rng, kv_dtype, tol):
    """Rows at different positions: chunked prefill, then decode steps,
    every logit against ``forward_train`` while the prompts fit the ring."""
    params = T.init_params(rng, cfg)
    lens, new = (5, 9, 12), 4
    tokens = jax.random.randint(rng, (len(lens), max(lens) + new), 0,
                                cfg.vocab_size)
    full = T.forward_train(params, cfg, tokens, remat=False)

    def check(r, p, got):
        ref = full[r, p].astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        assert err / scale < tol, (cfg.name, r, p, err / scale)

    cache, last = _slot_cache(params, cfg, [tokens[r, :n] for r, n in
                                            enumerate(lens)], 32, 4, kv_dtype)
    for r, n in enumerate(lens):
        check(r, n - 1, last[r])
    step = jax.jit(lambda p, t, c: T.decode_slots(p, cfg, t, c))
    for i in range(new):
        tok = jnp.stack([tokens[r, n + i] for r, n in enumerate(lens)])
        logits, cache = step(params, tok, cache)
        for r, n in enumerate(lens):
            check(r, n + i, logits[r, 0])


def _slots_wrap_like_lockstep(rng):
    """Sliding window past a full ring, the prompt's second chunk wrapping
    inside it: the chunks lay the first layer's K/V (which depend on no
    attention) where lock-step ``prefill`` does, and from the same ring
    the slot step is bit-identical to lock-step ``decode_step``, logits
    and cache."""
    cfg = get_smoke_config("h2o_danube_1_8b")
    params = T.init_params(rng, cfg)
    c = cfg.sliding_window
    toks = jax.random.randint(rng, (2, c + 5 + 2 * c), 0, cfg.vocab_size)
    slots, _ = _slot_cache(params, cfg, list(toks[:, :c + 5]), c, c - 3,
                           jnp.bfloat16)
    _, filled = T.prefill(params, cfg, toks[:, :c + 5],
                          T.init_cache(cfg, T.CacheSpec(capacity=c, batch=2)))
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(slots[key][0]),
                                      np.asarray(filled[key][0]))
    np.testing.assert_array_equal(np.asarray(slots["slot_pos"]),
                                  np.tile(np.asarray(filled["slot_pos"]),
                                          (2, 1)))
    lock = {**slots, "pos": slots["pos"][0], "slot_pos": slots["slot_pos"][0]}
    step = jax.jit(lambda p, t, c: T.decode_slots(p, cfg, t, c))
    lock_step = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))
    for t in range(c + 5, toks.shape[1]):
        got, slots = step(params, toks[:, t], slots)
        want, lock = lock_step(params, toks[:, t], lock)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(slots[key]),
                                      np.asarray(lock[key]))
    np.testing.assert_array_equal(np.asarray(slots["slot_pos"][0]),
                                  np.asarray(lock["slot_pos"]))


@pytest.mark.parametrize("case", SLOT_ARCHS + ["swa_ring_wrap", "int8_kv"])
def test_slot_path_matches_reference(case, rng):
    """The continuous-batching path (``chunk_prefill``, ``decode_slots``)
    against references that do not run it: ``forward_train`` for every
    family (MoE: dropless capacity), lock-step decode through a ring wrap,
    and an int8 KV ring within ``test_int8_kv_cache_close_to_bf16``'s
    tolerance."""
    if case == "swa_ring_wrap":
        _slots_wrap_like_lockstep(rng)
        return
    arch, kv_dtype, tol = case, jnp.bfloat16, 0.02
    if case == "int8_kv":
        arch, kv_dtype, tol = "qwen3_14b", jnp.int8, 0.15
    cfg = get_smoke_config(arch)
    if cfg.moe_experts:
        cfg = dataclasses.replace(cfg,
                                  moe_capacity_factor=float(cfg.moe_experts))
    _slots_against_forward(cfg, rng, kv_dtype, tol)


@pytest.mark.parametrize("t,first", [(1, 5), (4, 0), (4, 13), (7, 13),
                                     (16, 9)])
def test_ring_rows_written_in_place_like_a_scatter(t, first):
    """``_put_rows`` writes each row's run of T tokens at its positions
    modulo C, wrapping past the ring's end, exactly as a scatter would."""
    L, B, H, C, D = 3, 2, 2, 16, 4
    key = jax.random.PRNGKey(t * 100 + first)
    buf = jax.random.normal(key, (L, B, H, C, D))
    val = jax.random.normal(jax.random.fold_in(key, 1), (B, H, t, D))
    positions = (jnp.array([[first], [first + 3 * C + 2]])
                 + jnp.arange(t)[None, :])
    want = buf.at[1, jnp.arange(B)[:, None], :, positions % C, :].set(
        val.transpose(0, 2, 1, 3))
    got = T._put_rows(buf, val, 1, positions)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_swa_ring_buffer_eviction(rng):
    """Sliding-window arch with cache capacity == window: decoding past the
    window stays finite and attends only within the window."""
    cfg = get_smoke_config("h2o_danube_1_8b")   # window 16 after shrink
    params = T.init_params(rng, cfg)
    B = 2
    W = cfg.sliding_window
    cache = T.init_cache(cfg, T.CacheSpec(capacity=W, batch=B))
    toks = jax.random.randint(rng, (B, 3 * W), 0, cfg.vocab_size)
    _, cache = T.prefill(params, cfg, toks[:, :W], cache)
    for t in range(W, 3 * W):
        logits, cache = T.decode_step(params, cfg, toks[:, t], cache)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert int(cache["pos"]) == 3 * W


def test_unroll_scan_equivalence(rng):
    """unroll_scan (accounting variants) is numerically identical."""
    cfg = get_smoke_config("qwen3_14b")
    params = T.init_params(rng, cfg)
    tokens = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    l1 = T.loss_fn(params, cfg, batch, remat=False)
    cfg_u = dataclasses.replace(cfg, unroll_scan=True, attn_chunk=8)
    l2 = T.loss_fn(params, cfg_u, batch, remat=False)
    assert abs(float(l1) - float(l2)) < 5e-3


def test_int8_kv_cache_close_to_bf16(rng):
    cfg = get_smoke_config("qwen3_14b")
    params = T.init_params(rng, cfg)
    B, S = 2, 10
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    outs = {}
    for dt in (jnp.bfloat16, jnp.int8):
        cache = T.init_cache(cfg, T.CacheSpec(capacity=16, batch=B,
                                              kv_dtype=dt))
        _, cache = T.prefill(params, cfg, tokens[:, :S - 1], cache)
        ld, _ = T.decode_step(params, cfg, tokens[:, S - 1], cache)
        outs[str(dt)] = ld
    a = outs["<class 'jax.numpy.bfloat16'>"].astype(jnp.float32)
    b = outs["<class 'jax.numpy.int8'>"].astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(a - b))) / (float(jnp.max(jnp.abs(a)))
                                            + 1e-9)
    assert rel < 0.15, f"int8 KV cache too lossy: {rel}"


@pytest.mark.parametrize("arch", ARCH_IDS + PAPER_MODEL_IDS)
def test_full_config_param_counts(arch):
    """Full configs instantiate structurally (eval_shape, no allocation)
    and parameter counts are in the advertised ballpark."""
    import math
    cfg = get_config(arch)
    tree = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(tree))
    expected = cfg.param_count()
    assert abs(total - expected) / expected < 0.25, (arch, total, expected)


EXPECTED_SCALE = {                 # sanity: advertised model scale
    "qwen1_5_32b": 32e9, "qwen3_14b": 14e9, "gemma_7b": 8.5e9,
    "h2o_danube_1_8b": 1.8e9, "internvl2_1b": 0.6e9,
    "llama4_maverick_400b_a17b": 400e9, "kimi_k2_1t_a32b": 1.0e12,
    "rwkv6_7b": 7e9, "whisper_tiny": 37e6, "hymba_1_5b": 1.5e9,
}


@pytest.mark.parametrize("arch", list(EXPECTED_SCALE))
def test_param_scale(arch):
    got = get_config(arch).param_count()
    want = EXPECTED_SCALE[arch]
    assert want / 2.5 < got < want * 2.5, (arch, got, want)
