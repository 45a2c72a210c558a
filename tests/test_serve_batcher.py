"""Continuous-batching serve path: donated (copy-free) cache steps, the
slot-addressable cache ops, chunked prefill, and the request scheduler's
greedy parity with lock-step serving."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serve.batcher import (ContinuousBatcher, Request, make_trace,
                                 run_static_trace, summarize)
from repro.serve.engine import ServeConfig, ServeEngine


def _engine(mesh, cfg, rng, **kw):
    params = T.init_params(rng, cfg)
    scfg = ServeConfig(**{"batch": 2, "cache_capacity": 64,
                          "prefill_chunk": 8, **kw})
    return ServeEngine(cfg, mesh, params, scfg)


def _step_temps(step, per_slot: bool, layers: int) -> tuple[int, int]:
    """(temporaries of the compiled one-token step, bytes of one layer's
    K+V ring) for the smoke qwen3 config at ``layers`` layers with a
    4096-token ring.  Held in float32: XLA's CPU backend computes bfloat16
    dots in float32 and converts the whole stacked ring around each
    layer's read (the TPU compiler does not: tests/test_chip_compile.py
    bounds the bfloat16 step there)."""
    cfg = dataclasses.replace(get_smoke_config("qwen3_14b"),
                              param_dtype="float32", num_layers=layers)
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, T.CacheSpec(
        capacity=4096, batch=2, per_slot=per_slot, kv_dtype=jnp.float32)))
    compiled = jax.jit(lambda p, t, c: step(p, cfg, t, c),
                       donate_argnums=(2,)).lower(
        params, jax.ShapeDtypeStruct((2,), jnp.int32), cache).compile()
    ring = cache["k"]
    return (compiled.memory_analysis().temp_size_in_bytes,
            2 * ring.size // layers * ring.dtype.itemsize)


def _assert_ring_stays(step, per_slot: bool) -> None:
    """The step writes the stacked ring in place: its temporaries hold one
    layer's worth (the CPU backend lays the layer it attends over out for
    its dots, beside the scores), under one and a half layers' K+V ring
    and the same at any depth."""
    temps, layer_ring = _step_temps(step, per_slot, 2)
    assert temps < 1.5 * layer_ring, (temps, layer_ring)
    assert _step_temps(step, per_slot, 6)[0] == temps


class TestDonation:
    def test_decode_step_is_copy_free(self, mesh11, rng):
        """The compiled decode step must alias the cache input to the cache
        output: no donation warnings, the input buffer is consumed, and on
        a single device the output reuses the very same buffer."""
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        prompts = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
        logits, cache = eng.prefill(prompts)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        jax.block_until_ready(cache)
        k_ptr = cache["k"].unsafe_buffer_pointer()

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            logits, cache2 = eng.decode(tok, cache)
            jax.block_until_ready(cache2)
        donation_warnings = [w for w in caught
                             if "donat" in str(w.message).lower()]
        assert not donation_warnings, donation_warnings
        assert cache["k"].is_deleted()          # input was consumed...
        assert cache2["k"].unsafe_buffer_pointer() == k_ptr   # ...in place

        hlo = eng._decode.lower(eng.params, tok, cache2).compile().as_text()
        assert "input_output_alias" in hlo
        _assert_ring_stays(T.decode_step, per_slot=False)

    def test_slot_step_is_copy_free(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        eng._ensure_slots()
        jax.block_until_ready(eng.slot_cache)
        k_ptr = eng.slot_cache["k"].unsafe_buffer_pointer()
        eng.step(jnp.zeros((2,), jnp.int32))
        jax.block_until_ready(eng.slot_cache)
        assert eng.slot_cache["k"].unsafe_buffer_pointer() == k_ptr
        _assert_ring_stays(T.decode_slots, per_slot=True)


class TestSlotCacheOps:
    def test_chunked_prefill_invariant_to_chunking(self, mesh11, rng):
        """Any chunking of the prompt yields the same next token and the
        same ring contents as a single-chunk pass."""
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        prompt = jax.random.randint(rng, (1, 13), 0, cfg.vocab_size)
        outs = {}
        for chunks in ((13,), (8, 4, 1), (4, 4, 4, 1)):
            rc = eng.new_request_cache()
            off = 0
            for t in chunks:
                tok, rc = eng.prefill_chunk(rc, prompt[:, off:off + t])
                off += t
            outs[chunks] = (int(tok[0]), jax.tree.map(np.asarray, rc))
        ref_tok, ref_cache = outs[(13,)]
        for chunks, (tok, cache) in outs.items():
            assert tok == ref_tok, chunks
            for key in ("k", "v", "pos", "slot_pos"):
                np.testing.assert_array_equal(cache[key], ref_cache[key],
                                              err_msg=f"{chunks}/{key}")

    def test_insert_evict_isolation(self, mesh11, rng):
        """Inserting/evicting one slot never perturbs the other slot's
        decode stream."""
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        pa = jax.random.randint(rng, (1, 9), 0, cfg.vocab_size)
        pb = jax.random.randint(jax.random.fold_in(rng, 1), (1, 5), 0,
                                cfg.vocab_size)

        def solo(prompt, steps):
            ref = np.asarray(eng.generate(jnp.tile(prompt, (2, 1)),
                                          steps=steps))
            return ref[0, prompt.shape[1]:]

        ref_a = solo(pa, 6)
        # slot 0 runs request A; request B joins at slot 1 mid-decode and
        # leaves before A finishes
        tok_a, rc = eng.prefill_chunk(eng.new_request_cache(), pa)
        eng.insert_slot(0, rc)
        toks = jnp.zeros((2,), jnp.int32).at[0].set(tok_a[0])
        got_a = [int(tok_a[0])]
        for i in range(5):
            if i == 1:
                tok_b, rcb = eng.prefill_chunk(eng.new_request_cache(), pb)
                eng.insert_slot(1, rcb)
                toks = toks.at[1].set(tok_b[0])
            if i == 3:
                eng.evict_slot(1)
            toks = eng.step(toks)
            got_a.append(int(toks[0]))
        np.testing.assert_array_equal(np.asarray(got_a, np.int32), ref_a)

    def test_slot_pos_wraparound(self, mesh11, rng):
        """cache_capacity < prompt_len + steps: the ring tags must hold
        exactly the last C positions, in both cache layouts."""
        cfg = get_smoke_config("qwen3_14b")
        cap, s0, steps = 16, 12, 10
        eng = _engine(mesh11, cfg, rng, cache_capacity=cap)
        prompt = jax.random.randint(rng, (2, s0), 0, cfg.vocab_size)

        # lock-step layout: shared (C,) tags
        logits, cache = eng.prefill(prompt)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        for _ in range(steps):
            logits, cache = eng.decode(tok, cache)
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        end = s0 + steps
        np.testing.assert_array_equal(
            np.sort(np.asarray(cache["slot_pos"])),
            np.arange(end - cap, end))

        # per-slot layout: (B,C) tags, one row per request
        tokc, rc = eng.prefill_chunk(eng.new_request_cache(), prompt[:1])
        eng.insert_slot(0, rc)
        cur = jnp.zeros((2,), jnp.int32).at[0].set(tokc[0])
        for _ in range(steps):
            cur = eng.step(cur)
        sp = np.asarray(eng.slot_cache["slot_pos"])
        np.testing.assert_array_equal(np.sort(sp[0]),
                                      np.arange(end - cap, end))
        # eviction masks the whole row again (stale K/V unreachable)
        eng.evict_slot(0)
        sp = np.asarray(eng.slot_cache["slot_pos"])
        assert (sp[0] == T._POS_SENTINEL).all()
        assert int(np.asarray(eng.slot_cache["pos"])[0]) == 0

    def test_wraparound_stream_equals_gspmd(self, mesh11, rng):
        """elk_stream and gspmd agree under ring-buffer wraparound too."""
        cfg = get_smoke_config("h2o_danube_1_8b")   # SWA family
        params = T.init_params(rng, cfg)
        prompts = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)
        outs = {}
        for mode in ("gspmd", "elk_stream"):
            eng = ServeEngine(cfg, mesh11, params, ServeConfig(
                batch=2, cache_capacity=16, mode=mode))
            outs[mode] = np.asarray(eng.generate(prompts, steps=10))
        np.testing.assert_array_equal(outs["gspmd"], outs["elk_stream"])


class TestContinuousBatching:
    @pytest.mark.parametrize("mode", ["gspmd", "elk_stream"])
    def test_greedy_parity_and_out_of_order_completion(self, mode, mesh11,
                                                       rng):
        """Mixed-length trace through the scheduler: requests complete out
        of arrival order, and every request's greedy continuation is
        bit-identical to (a) serving it alone and (b) the lock-step
        ``generate`` path."""
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng, mode=mode, batch=2,
                      cache_capacity=64, prefill_chunk=8)
        lens = [(9, 8), (5, 2), (13, 5), (4, 9), (7, 1), (6, 3)]
        reqs = [Request(rid=i,
                        prompt=np.asarray(jax.random.randint(
                            jax.random.fold_in(rng, i), (s0,), 0,
                            cfg.vocab_size), np.int32),
                        max_new_tokens=new)
                for i, (s0, new) in enumerate(lens)]
        completions = ContinuousBatcher(eng).run(reqs)

        assert sorted(c.rid for c in completions) == list(range(len(reqs)))
        finish = [c.rid for c in completions]
        assert finish != sorted(finish), finish   # out of arrival order

        by_rid = {c.rid: c for c in completions}
        for r in reqs:
            got = by_rid[r.rid].tokens
            assert got.shape == (len(r.prompt) + r.max_new_tokens,)
            alone = ContinuousBatcher(eng).run(
                [Request(r.rid, r.prompt, r.max_new_tokens)])[0]
            np.testing.assert_array_equal(got, alone.tokens)
            ref = np.asarray(eng.generate(
                jnp.tile(jnp.asarray(r.prompt)[None, :], (2, 1)),
                steps=r.max_new_tokens))[0]
            np.testing.assert_array_equal(got, ref)

    # slot path exercises every cache family: RWKV state recurrence,
    # hybrid attention+SSM state, MoE dropless routing
    @pytest.mark.parametrize("arch", ["rwkv6_7b", "hymba_1_5b",
                                      "llama4_maverick_400b_a17b"])
    def test_slot_path_parity_across_families(self, arch, mesh11, rng):
        cfg = get_smoke_config(arch)
        eng = _engine(mesh11, cfg, rng, prefill_chunk=8)
        prompt = np.asarray(jax.random.randint(rng, (11,), 0,
                                               cfg.vocab_size), np.int32)
        got = ContinuousBatcher(eng).run([Request(0, prompt, 5)])[0].tokens
        ref = np.asarray(eng.generate(
            jnp.tile(jnp.asarray(prompt)[None, :], (2, 1)), steps=5))[0]
        np.testing.assert_array_equal(got, ref)

    def test_chunk_budget_clamped_to_capacity(self, mesh11, rng):
        """A prompt longer than the cache must prefill in sub-capacity
        chunks (ring wraps *between* chunks, never inside one)."""
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng, cache_capacity=16,
                      prefill_chunk=32)
        reqs = [Request(0, np.arange(2, 26, dtype=np.int32) % 9, 4)]
        out = ContinuousBatcher(eng).run(reqs)[0]
        assert out.tokens.shape == (28,)

    def test_empty_prompt_rejected(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        with pytest.raises(ValueError, match="empty prompt"):
            ContinuousBatcher(eng).submit(
                Request(0, np.zeros((0,), np.int32), 4))

    def test_zero_and_one_token_requests(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        reqs = [Request(0, np.arange(5, dtype=np.int32), 0),
                Request(1, np.arange(6, dtype=np.int32), 1),
                Request(2, np.arange(4, dtype=np.int32), 3)]
        out = {c.rid: c for c in ContinuousBatcher(eng).run(reqs)}
        np.testing.assert_array_equal(out[0].tokens, reqs[0].prompt)
        assert out[1].tokens.shape == (7,)
        assert out[2].tokens.shape == (7,)
        ref = np.asarray(eng.generate(
            jnp.tile(jnp.asarray(reqs[1].prompt)[None, :], (2, 1)),
            steps=1))[0]
        np.testing.assert_array_equal(out[1].tokens, ref)

    def test_int8_kv_slot_path_runs(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng, kv_dtype="int8")
        reqs = [Request(0, np.arange(6, dtype=np.int32), 4),
                Request(1, np.arange(9, dtype=np.int32), 2)]
        out = ContinuousBatcher(eng).run(reqs)
        assert sorted(c.rid for c in out) == [0, 1]
        for c in out:
            assert c.tokens.shape == (c.prompt_len + (4 if c.rid == 0
                                                      else 2),)

    def test_static_trace_baseline_accounts_all_requests(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        trace = make_trace(5, vocab_size=cfg.vocab_size,
                           prompt_lens=(6, 9), max_new=(2, 4))
        out = run_static_trace(eng, trace)
        assert sorted(c.rid for c in out) == list(range(5))
        stats = summarize(out, 1.0)
        assert stats["requests"] == 5
        assert stats["gen_tok_s"] == pytest.approx(
            sum(r.max_new_tokens for r in trace))
