"""Fleet-tier serving (DESIGN.md §12): the SLO-aware router over
prefill/decode-disaggregated pods.  A one-mixed-pod fleet is value-
identical to the direct batcher; cross-pod KV migration is bit-identical
and charged (plan within 2x of the fleet simulator); shedding against the
predicted TTFT keeps admitted p99 under the target; and the trace/summary
tooling grows multi-tenant knobs without disturbing old outputs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chip.config import ipu_pod4_hbm
from repro.chip.dse import fleet_sweep
from repro.chip.simulator import simulate_fleet_traffic
from repro.chip.topology import FleetSpec, fleet_spec
from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serve.batcher import (ContinuousBatcher, Request, make_trace,
                                 summarize)
from repro.serve.engine import (PREFILL_SAT, ServeConfig, ServeEngine,
                                elk_serve_config)
from repro.serve.fleet import (FleetPod, FleetRouter, PodCosts,
                               VirtualClock, predict_fleet_rates,
                               prefill_passes, run_virtual_trace)

COSTS = PodCosts(decode_step_s=1e-3, tick_overhead_s=5e-4)


def _engine(mesh, cfg, rng, **kw):
    params = T.init_params(rng, cfg)
    scfg = ServeConfig(**{"batch": 2, "cache_capacity": 64,
                          "prefill_chunk": 8, **kw})
    return ServeEngine(cfg, mesh, params, scfg)


def _solo(eng, prompt, steps):
    """Cold-path greedy reference for one request."""
    return np.asarray(eng.generate(
        jnp.tile(jnp.asarray(prompt)[None, :], (eng.scfg.batch, 1)),
        steps=steps))[0]


def _trace(cfg, n=6, **kw):
    return make_trace(n, vocab_size=cfg.vocab_size,
                      **{"prompt_lens": (8, 12, 16), "max_new": (3, 4, 5),
                         **kw})


class TestFleetSpec:
    def test_homogeneous_fleet_derives_inter_pod_tier(self):
        fl = fleet_spec(ipu_pod4_hbm(), 4)
        assert fl.num_pods == 4
        # the fleet tier is thinner and slower than any pod's own fabric
        assert 0 < fl.inter_pod_bw < min(p.topo.bisection_bw
                                         for p in fl.pods)
        assert fl.inter_pod_latency > max(p.link_latency for p in fl.pods)
        assert fl.link().name == "pod"

    def test_migration_spans_three_legs(self):
        fl = fleet_spec(ipu_pod4_hbm(), 2)
        nbytes = 1 << 20
        wire = fl.transfer_time(nbytes)
        mig = fl.migration_time(nbytes, 0, 1)
        assert wire > 0 and mig > wire     # offload + refill on top
        assert fl.transfer_time(0) == 0.0

    def test_rejects_degenerate_shapes(self):
        with pytest.raises(ValueError):
            fleet_spec(ipu_pod4_hbm(), 0)
        with pytest.raises(ValueError):
            FleetSpec(pods=())

    def test_signature_distinguishes_fleet_tier(self):
        a = fleet_spec(ipu_pod4_hbm(), 2)
        b = dataclasses.replace(a, inter_pod_bw=a.inter_pod_bw / 2)
        assert a.signature() != b.signature()


class TestPodCosts:
    def test_tick_cost_arithmetic(self):
        c = PodCosts(decode_step_s=1.0, tick_overhead_s=0.5,
                     prefill_sat=128, spill_s=0.25)
        assert c.tick_cost(decoded=False, prefill_tokens=0) == 0.5
        assert c.tick_cost(decoded=True, prefill_tokens=0) == 1.5
        # any chunk up to the saturating pass costs one weight pass
        assert c.tick_cost(decoded=True, prefill_tokens=16) == 2.5
        assert c.tick_cost(decoded=True, prefill_tokens=128) == 2.5
        assert c.tick_cost(decoded=False, prefill_tokens=129) == 2.5
        assert c.tick_cost(decoded=False, prefill_tokens=0,
                           spill_moves=2) == 1.0

    def test_from_serve_config_prefers_plan_interval(self):
        scfg = ServeConfig(batch=2, cache_capacity=64,
                           steady_interval_s=2e-3, slot_spill_s=1e-4)
        c = PodCosts.from_serve_config(scfg)
        assert c.decode_step_s == 2e-3
        assert c.tick_overhead_s == pytest.approx(1e-3)
        assert c.spill_s == 1e-4
        # no plan interval -> nominal decode quantum
        assert PodCosts.from_serve_config(
            ServeConfig(batch=2, cache_capacity=64)).decode_step_s == 1e-3

    def test_prefill_passes_replays_pow2_chunking(self):
        # 96 @ budget 16: 6 full chunks; @ budget 128: 64 + 32
        assert prefill_passes(96, 16) == 6
        assert prefill_passes(96, 128) == 2
        assert prefill_passes(1, 16) == 1
        assert prefill_passes(0, 16) == 0


class TestDegenerateFleet:
    def test_one_mixed_pod_equals_direct_batcher(self, mesh11, rng):
        """The acceptance pin: a FleetRouter over one mixed pod must be a
        pure pass-through — same completions (tokens, timestamps, order)
        and same summary as driving the batcher directly on the same
        virtual clock."""
        cfg = get_smoke_config("qwen3_14b")
        fr = FleetRouter([FleetPod(_engine(mesh11, cfg, rng), "mixed",
                                   costs=COSTS)])
        got = fr.run(_trace(cfg, arrival_spacing_s=2e-3))

        vc = VirtualClock()
        bat = ContinuousBatcher(_engine(mesh11, cfg, rng), vc)
        ref = run_virtual_trace(bat, _trace(cfg, arrival_spacing_s=2e-3),
                                COSTS)
        assert [c.rid for c in got] == [c.rid for c in ref]
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            assert a.finish_s == pytest.approx(b.finish_s, abs=1e-12)
            assert a.first_token_s == pytest.approx(b.first_token_s,
                                                    abs=1e-12)
            assert a.finish_order == b.finish_order
        direct = summarize(ref, vc.t)
        merged = fr.summary()
        for k, v in direct.items():
            assert merged[k] == v, k
        assert merged["routed"] == [len(ref)]
        assert merged["migrations"] == 0 and merged["shed"] == 0

    def test_router_validates_roles(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        with pytest.raises(ValueError):
            FleetRouter([FleetPod(eng, "decode")])
        with pytest.raises(ValueError):
            FleetRouter([FleetPod(eng, "warp")])
        with pytest.raises(ValueError):
            FleetRouter([])


class TestMigration:
    def test_cross_pod_offload_refill_is_bit_identical(self, mesh11, rng):
        """The primitive under the fleet tier: offload a slot on pod A,
        refill it on a *different engine* B, and the continued decode
        equals the never-migrated stream."""
        cfg = get_smoke_config("qwen3_14b")
        ea = _engine(mesh11, cfg, rng)
        eb = _engine(mesh11, cfg, rng)
        prompt = np.asarray(
            jax.random.randint(rng, (1, 9), 0, cfg.vocab_size))
        ref = _solo(ea, prompt[0], 6)[9:]

        tok, rc = ea.prefill_chunk(ea.new_request_cache(),
                                   jnp.asarray(prompt))
        ea.insert_slot(0, rc)
        state = ea.offload_slot(0)
        eb.refill_slot(1, state)        # different pod, different slot
        toks = jnp.zeros((2,), jnp.int32).at[1].set(tok[0])
        got = [int(tok[0])]
        for _ in range(5):
            toks = eb.step(toks)
            got.append(int(toks[1]))
        np.testing.assert_array_equal(np.asarray(got, np.int32), ref)

    def test_disagg_fleet_preserves_greedy_parity(self, mesh11, rng):
        """End-to-end through the router: every request served by the
        prefill->migrate->decode path produces the tokens of serving it
        alone, and TTFT comes from the prefill pod (first token exists
        before the migration lands)."""
        cfg = get_smoke_config("qwen3_14b")
        ref_eng = _engine(mesh11, cfg, rng)
        fr = FleetRouter(
            [FleetPod(_engine(mesh11, cfg, rng, prefill_chunk=64),
                      "prefill", costs=COSTS),
             FleetPod(_engine(mesh11, cfg, rng), "decode", costs=COSTS)])
        trace = _trace(cfg)
        got = fr.run(_trace(cfg))
        assert len(got) == len(trace)
        assert fr.migrations == len(trace)
        by_rid = {r.rid: r for r in trace}
        for c in got:
            r = by_rid[c.rid]
            np.testing.assert_array_equal(
                c.tokens, _solo(ref_eng, r.prompt, r.max_new_tokens))
            assert 0 <= c.first_token_s < c.finish_s

    def test_migration_is_charged_and_sim_matches_plan(self, mesh11, rng):
        """Acceptance gate: migration is not free — a fleet-priced router
        records planned wire+endpoint time per migration, and the fleet
        simulator re-serves the same event list within 2x of the plan
        (it only *adds* queueing, so the ratio can only push up)."""
        cfg = get_smoke_config("qwen3_14b")
        fl = fleet_spec(ipu_pod4_hbm(), 2)
        fr = FleetRouter(
            [FleetPod(_engine(mesh11, cfg, rng, prefill_chunk=64),
                      "prefill", costs=COSTS),
             FleetPod(_engine(mesh11, cfg, rng), "decode", costs=COSTS)],
            fleet=fl)
        fr.run(_trace(cfg))
        assert fr.migrations > 0
        assert fr.planned_migration_s > 0
        assert len(fr.migration_events) == fr.migrations
        res = simulate_fleet_traffic(fl, fr.migration_events)
        sim = sum(f - at for f, (_, at, _, _) in
                  zip(res.finish, fr.migration_events))
        ratio = sim / fr.planned_migration_s
        assert 0.5 <= ratio <= 2.0, ratio
        assert res.busy["fleet"] > 0

    def test_unpriced_fleet_migrates_for_free_but_counts(self, mesh11,
                                                         rng):
        cfg = get_smoke_config("qwen3_14b")
        fr = FleetRouter(
            [FleetPod(_engine(mesh11, cfg, rng, prefill_chunk=64),
                      "prefill", costs=COSTS),
             FleetPod(_engine(mesh11, cfg, rng), "decode", costs=COSTS)])
        fr.run(_trace(cfg))
        assert fr.migrations > 0
        assert fr.planned_migration_s == 0.0
        assert fr.migration_events == []


class TestSLO:
    def test_shedding_keeps_admitted_p99_under_target(self, mesh11, rng):
        """Acceptance pin: a burst that would blow the target gets shed
        down to what the pod can serve in time — admitted p99 TTFT meets
        the target at reduced admitted throughput; without the SLO the
        same burst all completes (and violates it)."""
        cfg = get_smoke_config("qwen3_14b")
        n = 8
        burst = _trace(cfg, n=n, prompt_lens=(32,), max_new=(3,))
        slo = 15e-3
        fr = FleetRouter([FleetPod(_engine(mesh11, cfg, rng, batch=1,
                                           prefill_chunk=16),
                                   "mixed", costs=COSTS)],
                         ttft_slo_s=slo)
        done = fr.run(burst)
        assert 0 < len(done) < n            # shed some, served some
        assert len(fr.shed) == n - len(done)
        assert max(c.ttft_s for c in done) <= slo + 1e-9
        assert fr.summary()["shed"] == len(fr.shed)

        fr2 = FleetRouter([FleetPod(_engine(mesh11, cfg, rng, batch=1,
                                            prefill_chunk=16),
                                    "mixed", costs=COSTS)])
        done2 = fr2.run(_trace(cfg, n=n, prompt_lens=(32,), max_new=(3,)))
        assert len(done2) == n              # no SLO: everything completes
        assert max(c.ttft_s for c in done2) > slo

    def test_prediction_upper_bounds_realized_ttft(self, mesh11, rng):
        """The shedding decision is only sound if predict_ttft never
        under-estimates: route a staggered trace and check every realized
        TTFT against the prediction made at routing time."""
        cfg = get_smoke_config("qwen3_14b")
        eng = _engine(mesh11, cfg, rng)
        fr = FleetRouter([FleetPod(eng, "mixed", costs=COSTS)])
        preds = {}
        orig = fr.predict_ttft

        def spy(index, plen, now):
            t = orig(index, plen, now)
            preds.setdefault((index, plen, round(now, 9)), []).append(t)
            return t

        fr.predict_ttft = spy
        trace = _trace(cfg, arrival_spacing_s=1e-3)
        done = fr.run(trace)
        by_rid = {r.rid: r for r in trace}
        for c in done:
            plen = len(by_rid[c.rid].prompt)
            pred = max(t for (_, p, _), ts in preds.items()
                       for t in ts if p == plen)
            assert c.ttft_s <= pred + 1e-9


class TestMultiTenantTrace:
    def test_defaults_reproduce_old_traces_byte_identically(self):
        cfg = get_smoke_config("qwen3_14b")
        old = make_trace(8, vocab_size=cfg.vocab_size, seed=3,
                         arrival_spacing_s=0.01, burst=2)
        new = make_trace(8, vocab_size=cfg.vocab_size, seed=3,
                         arrival_spacing_s=0.01, burst=2,
                         tenant_rates=(), tail_frac=0.0)
        for a, b in zip(old, new):
            np.testing.assert_array_equal(a.prompt, b.prompt)
            assert (a.rid, a.max_new_tokens, a.arrival_s, a.tenant) == \
                (b.rid, b.max_new_tokens, b.arrival_s, b.tenant)
            assert a.tenant == 0

    def test_tenant_rates_label_and_merge_poisson(self):
        cfg = get_smoke_config("qwen3_14b")
        reqs = make_trace(400, vocab_size=cfg.vocab_size, seed=5,
                          arrival_spacing_s=0.01,
                          tenant_rates=(3.0, 1.0))
        labels = np.asarray([r.tenant for r in reqs])
        assert set(labels) == {0, 1}
        # labels follow the rate shares (3:1)
        assert 0.6 < (labels == 0).mean() < 0.9
        arr = np.asarray([r.arrival_s for r in reqs])
        assert (np.diff(arr) >= 0).all() and arr[0] > 0
        # exponential gaps with the requested mean
        assert np.mean(np.diff(arr)) == pytest.approx(0.01, rel=0.3)
        # seeded: same knobs -> same trace
        again = make_trace(400, vocab_size=cfg.vocab_size, seed=5,
                           arrival_spacing_s=0.01,
                           tenant_rates=(3.0, 1.0))
        assert [r.arrival_s for r in again] == [r.arrival_s for r in reqs]
        with pytest.raises(ValueError):
            make_trace(4, vocab_size=8, tenant_rates=(1.0, 0.0))

    def test_tail_frac_stretches_and_caps_prompts(self):
        cfg = get_smoke_config("qwen3_14b")
        base = make_trace(200, vocab_size=cfg.vocab_size, seed=7,
                          prompt_lens=(16,))
        tailed = make_trace(200, vocab_size=cfg.vocab_size, seed=7,
                            prompt_lens=(16,), tail_frac=0.3,
                            tail_mult=4.0)
        lens_b = np.asarray([len(r.prompt) for r in base])
        lens_t = np.asarray([len(r.prompt) for r in tailed])
        assert (lens_b == 16).all()
        stretched = lens_t > 16
        assert 0.1 < stretched.mean() < 0.5      # ~tail_frac of them
        assert lens_t.max() <= 64                # capped at tail_mult x
        # untouched requests keep the grid length
        assert (lens_t[~stretched] == 16).all()


class TestSummarize:
    def test_new_keys_ride_alongside_old_ones(self, mesh11, rng):
        cfg = get_smoke_config("qwen3_14b")
        vc = VirtualClock()
        bat = ContinuousBatcher(_engine(mesh11, cfg, rng), vc)
        done = run_virtual_trace(bat, _trace(cfg, arrival_spacing_s=1e-3),
                                 COSTS)
        s = summarize(done, vc.t)
        for k in ("requests", "wall_s", "gen_tok_s", "p50_latency_s",
                  "p99_latency_s", "p50_ttft_s", "p99_ttft_s"):
            assert k in s                       # pre-existing keys intact
        assert s["p99_queue_wait_s"] >= s["p50_queue_wait_s"] >= 0
        gen = sum(len(c.tokens) - c.prompt_len for c in done)
        span = (max(c.finish_s for c in done)
                - min(c.first_token_s for c in done
                      if c.first_token_s >= 0))
        assert s["decode_tok_s"] == pytest.approx(gen / span, abs=0.01)
        # steady decode rate excludes the queue-drain ramp: >= whole-wall
        assert s["decode_tok_s"] >= s["gen_tok_s"]

    def test_queue_wait_from_admission_stamp(self):
        from repro.serve.batcher import Completion
        c = Completion(rid=0, tokens=np.zeros(4, np.int32), prompt_len=2,
                       arrival_s=1.0, finish_s=3.0, finish_order=0,
                       admitted_s=1.5)
        assert c.queue_wait_s == pytest.approx(0.5)
        c2 = dataclasses.replace(c, admitted_s=-1.0)
        assert c2.queue_wait_s == 0.0


class TestRoleSizing:
    def test_prefill_role_opens_chunk_budget(self, mesh11):
        cfg = get_smoke_config("qwen3_14b")
        mixed = elk_serve_config(cfg, batch=2, cache_capacity=256)
        pf = elk_serve_config(cfg, batch=2, cache_capacity=256,
                              role="prefill")
        dec = elk_serve_config(cfg, batch=2, cache_capacity=256,
                               role="decode")
        assert pf.prefill_chunk == min(PREFILL_SAT, 256)
        assert pf.prefill_chunk >= mixed.prefill_chunk
        assert dec.prefill_chunk == 16
        # mixed is byte-identical to the role-less call
        assert mixed == elk_serve_config(cfg, batch=2, cache_capacity=256,
                                         role="mixed")
        with pytest.raises(ValueError):
            elk_serve_config(cfg, batch=2, cache_capacity=256,
                             role="router")

    def test_mode_follows_the_local_mesh(self, mesh11):
        """One device on the batch axes: nothing to gather, so gspmd with
        the saturating chunk.  A wider data axis keeps the plan's window."""
        from jax.sharding import AbstractMesh
        cfg = get_smoke_config("qwen3_14b")
        plan = elk_serve_config(cfg, batch=2, cache_capacity=256)
        one = elk_serve_config(cfg, batch=2, cache_capacity=256, mesh=mesh11)
        four = elk_serve_config(cfg, batch=2, cache_capacity=256,
                                mesh=AbstractMesh((4, 1), ("data", "model")))
        assert (one.mode, one.prefill_chunk) == ("gspmd", PREFILL_SAT)
        assert four == plan and plan.mode == "elk_stream"
        dec = elk_serve_config(cfg, batch=2, cache_capacity=256,
                               role="decode", mesh=mesh11)
        assert (dec.mode, dec.prefill_chunk) == ("gspmd", 16)


class TestFleetSweep:
    def test_smoke_rows_and_disagg_verdict(self):
        rows = fleet_sweep(smoke=True, prompt_len=1024,
                           n_prefill_list=(1, 2),
                           inter_bw_ratios=(0.25,))
        assert len(rows) == 2
        for r in rows:
            assert r["migration_ms"] > 0
            assert r["disagg_prefill_req_s"] > r["mixed_prefill_req_s"]
        # the 1-prefill split keeps more decode pods than mixed pays in
        # interference -> wins both axes at long prompts
        one = next(r for r in rows if r["n_prefill"] == 1)
        assert one["disagg_won"]

    def test_predict_fleet_rates_validates_split(self):
        with pytest.raises(ValueError):
            predict_fleet_rates(COSTS, num_pods=4, n_prefill=0, slots=4,
                                prompt_len=64)
        with pytest.raises(ValueError):
            predict_fleet_rates(COSTS, num_pods=4, n_prefill=4, slots=4,
                                prompt_len=64)
