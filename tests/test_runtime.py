"""Distributed-runtime tests: trainer fault tolerance, checkpoint
round-trip/resharding, serving engine equivalence, compression,
simulator/emulator sanity."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import compression as comp
from repro.models import transformer as T
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import ServeConfig, ServeEngine
from repro.train.trainer import Trainer, TrainerConfig

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
_FOUR_DEVICE_SERVE = """
import dataclasses, json, sys
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.kernels.dispatch import force_kernels
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.serve.batcher import ContinuousBatcher, make_trace
from repro.serve.engine import ServeConfig, ServeEngine

def serve(cfg, params, mesh, mode, depth, cap, prompt_len, steps):
    eng = ServeEngine(cfg, mesh, params, ServeConfig(
        batch=4, cache_capacity=cap, mode=mode, prefetch_depth=depth,
        prefill_chunk=8))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, prompt_len), 0,
                                 cfg.vocab_size)
    trace = make_trace(6, vocab_size=cfg.vocab_size, seed=0)
    return {"generate": np.asarray(eng.generate(prompts, steps)).tolist(),
            "slots": {c.rid: c.tokens.tolist()
                      for c in ContinuousBatcher(eng).run(trace)}}

def per_shard_mlp(gated, rows):
    from repro.kernels.fused_mlp.ops import fused_mlp
    from repro.kernels.fused_mlp.ref import fused_mlp_ref
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    d, ff = 128, 256
    x = jax.random.normal(k[0], (rows, d))
    w = {"w_up": jax.random.normal(k[1], (d, ff)) * d ** -0.5,
         "w_down": jax.random.normal(k[2], (ff, d)) * ff ** -0.5}
    if gated:
        w["w_gate"] = jax.random.normal(k[3], (d, ff)) * d ** -0.5
    else:
        w["b_up"] = jax.random.normal(k[4], (ff,))
        w["b_down"] = jax.random.normal(k[5], (d,))
    got = jax.jit(lambda x, w: fused_mlp(x, act="gelu", force_kernel=True,
                                         mesh=make_local_mesh(4, 1), **w))(x, w)
    ref = fused_mlp_ref(x, act="gelu", **w)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))

out, refs = {}, {}
out["mlp"] = {f"{g} {m}": per_shard_mlp(g == "gated", m)
              for g, m in (("gated", 8), ("ungated", 8), ("ungated", 6))}
with force_kernels():
    for name, (arch, depth, cap, prompt_len, steps) in json.loads(
            sys.argv[1]).items():
        cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32",
                                  param_dtype="float32")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        shape = (arch, cap, prompt_len, steps)
        if shape not in refs:
            refs[shape] = serve(cfg, params, make_local_mesh(), "gspmd", 1,
                                cap, prompt_len, steps)
        out[name] = {"gspmd x1": refs[shape],
                     "elk_stream x4": serve(cfg, params, make_local_mesh(4, 1),
                                            "elk_stream", depth, cap,
                                            prompt_len, steps)}
print(json.dumps(out))
"""
# name -> (arch, prefetch depth, cache capacity, prompt length, steps):
# prefetch depths 1/2/4 on the dense model, one arch per other cache family
# (RWKV state, hybrid attention+SSM, MoE), and SWA under ring wraparound
_FOUR_DEVICE_CASES = {
    "qwen3 p1": ("qwen3_14b", 1, 64, 8, 5),
    "qwen3 p2": ("qwen3_14b", 2, 64, 8, 5),
    "qwen3 p4": ("qwen3_14b", 4, 64, 8, 5),
    "rwkv6": ("rwkv6_7b", 2, 64, 8, 5),
    "hymba": ("hymba_1_5b", 2, 64, 8, 5),
    "llama4 moe": ("llama4_maverick_400b_a17b", 2, 64, 8, 5),
    "h2o_danube wraparound": ("h2o_danube_1_8b", 2, 16, 12, 10),
}


def _mk_trainer(wd, mesh, steps=20, ckpt_every=5, failure_hook=None,
                **tkw):
    cfg = get_smoke_config("qwen3_14b")
    return Trainer(
        cfg, DataConfig(batch=4, seq=16, vocab_size=cfg.vocab_size),
        AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=60),
        TrainerConfig(workdir=str(wd), total_steps=steps,
                      ckpt_every=ckpt_every, **tkw),
        mesh, failure_hook=failure_hook)


class TestTrainer:
    def test_loss_decreases(self, tmp_path, mesh11):
        t = _mk_trainer(tmp_path, mesh11, steps=25)
        log = t.run()
        assert log[-1]["loss"] < log[0]["loss"]

    def test_crash_restart_resumes(self, tmp_path, mesh11):
        class Crash(RuntimeError):
            pass

        def bomb(step):
            if step == 7:
                raise Crash()

        t = _mk_trainer(tmp_path, mesh11, steps=20, ckpt_every=3,
                        failure_hook=bomb)
        with pytest.raises(Crash):
            t.run()
        t.store.wait()
        # a fresh trainer resumes from the last checkpoint (step 6)
        t2 = _mk_trainer(tmp_path, mesh11, steps=20, ckpt_every=3)
        assert t2.step == 6
        t2.run()
        assert t2.step == 20

    def test_restart_is_deterministic(self, tmp_path, mesh11):
        """Resumed run reproduces the uninterrupted run exactly (the
        deterministic data pipeline + checkpointed state)."""
        ta = _mk_trainer(tmp_path / "a", mesh11, steps=12, ckpt_every=6)
        log_a = ta.run()

        tb = _mk_trainer(tmp_path / "b", mesh11, steps=6, ckpt_every=6)
        tb.run()
        tb.store.wait()
        tb2 = _mk_trainer(tmp_path / "b", mesh11, steps=12, ckpt_every=6)
        assert tb2.step == 6
        log_b = tb2.run()
        assert log_a[-1]["loss"] == pytest.approx(log_b[-1]["loss"],
                                                  rel=1e-5)

    def test_heartbeat_written(self, tmp_path, mesh11):
        t = _mk_trainer(tmp_path, mesh11, steps=6)
        t.run()
        import json
        hb = json.load(open(os.path.join(str(tmp_path), "heartbeat.json")))
        assert hb["step"] == 6

    def test_straggler_hook_fires(self, tmp_path, mesh11):
        import time
        t = _mk_trainer(tmp_path, mesh11, steps=14,
                        straggler_factor=1e-9, straggler_patience=2)
        t.run()
        assert t.straggler_events >= 1

    def test_grad_accum_matches_full_batch(self, mesh11):
        """ga=2 over batch B == ga=1 over batch B (same update direction)."""
        cfg = get_smoke_config("qwen3_14b")
        from repro.train.step import jit_train_step
        data = SyntheticLM(DataConfig(batch=8, seq=16,
                                      vocab_size=cfg.vocab_size))
        batch = data.batch_at(0)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        params_np = jax.tree.map(np.asarray, params)   # survives donation
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
        outs = {}
        for ga in (1, 2):
            f, sh = jit_train_step(cfg, mesh11, ocfg, params, batch,
                                   grad_accum=ga)
            fresh = jax.tree.map(jnp.asarray, params_np)
            p = jax.device_put(fresh, sh["params"])
            s = jax.device_put(adamw.init_state(fresh, ocfg), sh["opt"])
            p2, s2, m, _ = f(p, s, batch, None)
            outs[ga] = (float(m["loss"]), p2)
        assert outs[1][0] == pytest.approx(outs[2][0], rel=3e-2)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
            outs[1][1], outs[2][1])
        assert max(jax.tree.leaves(d)) < 1e-1


class TestCheckpoint:
    def test_atomic_roundtrip(self, tmp_path):
        store = CheckpointStore(str(tmp_path), keep=2)
        tree = {"a": jnp.arange(6.0).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        store.save(3, tree)
        store.save(7, tree)
        store.save(9, tree)
        assert store.steps() == [7, 9]      # keep=2 garbage-collects
        out = store.restore(9, tree)
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))

    def test_restore_with_resharding(self, tmp_path, mesh11):
        """Checkpoint written unsharded restores onto a mesh (elastic)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        store = CheckpointStore(str(tmp_path))
        tree = {"w": jnp.ones((8, 4))}
        store.save(1, tree)
        sh = {"w": NamedSharding(mesh11, P("data", None))}
        out = store.restore(1, tree, sh)
        assert out["w"].sharding == sh["w"]

    def test_crash_during_write_keeps_previous(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        tree = {"a": jnp.zeros((2,))}
        store.save(1, tree)
        # simulate a torn write: stale tmp dir must not count as a ckpt
        os.makedirs(os.path.join(str(tmp_path), "step_000000002.tmp"))
        assert store.latest_step() == 1


class TestServe:
    # one arch per cache family: dense GQA, RWKV state, hybrid-SSM, MoE
    @pytest.mark.parametrize("arch", ["qwen3_14b", "rwkv6_7b", "hymba_1_5b",
                                      "llama4_maverick_400b_a17b"])
    def test_stream_equals_gspmd(self, arch, mesh11, rng):
        cfg = get_smoke_config(arch)
        params = T.init_params(rng, cfg)
        prompts = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
        outs = {}
        for mode in ("gspmd", "elk_stream"):
            eng = ServeEngine(cfg, mesh11, params, ServeConfig(
                batch=2, cache_capacity=32, mode=mode, prefetch_depth=2))
            outs[mode] = np.asarray(eng.generate(prompts, steps=5))
        np.testing.assert_array_equal(outs["gspmd"], outs["elk_stream"])

    def test_generate_token_count_edge_steps(self, mesh11, rng):
        """generate must return exactly S0 + steps tokens, including the
        steps=0 (no continuation) and steps=1 (prefill token only) edges."""
        cfg = get_smoke_config("qwen3_14b")
        params = T.init_params(rng, cfg)
        eng = ServeEngine(cfg, mesh11, params, ServeConfig(
            batch=2, cache_capacity=32))
        prompts = jax.random.randint(rng, (2, 7), 0, cfg.vocab_size)
        for steps in (0, 1, 3):
            out = np.asarray(eng.generate(prompts, steps=steps))
            assert out.shape == (2, 7 + steps)
            np.testing.assert_array_equal(out[:, :7], np.asarray(prompts))

    def test_prefetch_depth_invariance(self, mesh11, rng):
        """The ELK preload number changes scheduling, never results."""
        cfg = get_smoke_config("qwen3_14b")
        params = T.init_params(rng, cfg)
        prompts = jax.random.randint(rng, (2, 6), 0, cfg.vocab_size)
        ref = None
        for p in (1, 2, 4):
            eng = ServeEngine(cfg, mesh11, params, ServeConfig(
                batch=2, cache_capacity=16, mode="elk_stream",
                prefetch_depth=p))
            out = np.asarray(eng.generate(prompts, steps=4))
            if ref is None:
                ref = out
            np.testing.assert_array_equal(ref, out)


    @pytest.fixture(scope="class")
    def four_device_serve(self):
        """Every ``_FOUR_DEVICE_CASES`` entry served in one fresh process
        with four host devices (they exist only in a new process)."""
        import json
        import subprocess
        import sys
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.pathsep.join(
                       [_SRC, os.environ.get("PYTHONPATH", "")]))
        res = subprocess.run(
            [sys.executable, "-c", _FOUR_DEVICE_SERVE,
             json.dumps(_FOUR_DEVICE_CASES)],
            env=env, capture_output=True, text=True, timeout=900)
        assert res.returncode == 0, res.stderr[-4000:]
        return json.loads(res.stdout.strip().splitlines()[-1])

    @pytest.mark.parametrize("case", list(_FOUR_DEVICE_CASES))
    def test_stream_over_four_devices_equals_one(self, case,
                                                 four_device_serve):
        """elk_stream with weights sharded over a (data=4, model=1) mesh —
        the gather-ahead window and the per-shard fused-MLP kernel — serves
        the same greedy tokens as gspmd on one device, lock-step and
        through the continuous batcher.  float32 weights keep re-associated
        sums off argmax ties."""
        out = four_device_serve[case]
        assert out["gspmd x1"]["slots"], out
        assert out["elk_stream x4"] == out["gspmd x1"], case

    @pytest.mark.parametrize("case", ["gated 8", "ungated 8", "ungated 6"])
    def test_fused_mlp_per_shard_matches_ref(self, case, four_device_serve):
        """The fused-MLP kernel under ``shard_map`` on four devices (rows
        split where 4 divides them, gathered where not; fc biases given)
        matches the composed reference."""
        assert four_device_serve["mlp"][case] <= 1e-5, four_device_serve["mlp"]


class TestLaunch:
    def test_local_mesh_never_shrinks(self):
        from repro.launch.mesh import make_local_mesh
        n = len(jax.devices())
        assert make_local_mesh(n, 1).devices.size == n
        with pytest.raises(ValueError, match="devices"):
            make_local_mesh(n + 1, 1)

    def test_compile_cache_defaults_to_checkout(self, monkeypatch):
        from repro.launch.compile_cache import CHECKOUT_CACHE, \
            use_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            assert use_compile_cache() == str(CHECKOUT_CACHE)
            assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        assert str(CHECKOUT_CACHE.parent) == os.path.dirname(_SRC)

    def test_compile_cache_follows_env(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, compiled entries land there
        and the helper sets no directory of its own."""
        import subprocess
        import sys
        code = (
            "import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "d = use_compile_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == d, d\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()"
            "\nprint(d)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
                   PYTHONPATH=os.pathsep.join(
                       [_SRC, os.environ.get("PYTHONPATH", "")]))
        res = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-4000:]
        assert res.stdout.strip() == str(tmp_path / "cc")
        assert any((tmp_path / "cc").iterdir())


class TestCompression:
    def test_bf16_roundtrip_close(self):
        g = {"w": jnp.linspace(-1, 1, 128, dtype=jnp.float32)}
        wire, _ = comp.compress_grads(g, None, "bf16")
        assert wire["w"].dtype == jnp.bfloat16
        assert float(jnp.max(jnp.abs(
            wire["w"].astype(jnp.float32) - g["w"]))) < 1e-2

    def test_int8_error_feedback_telescopes(self):
        """Repeated identical grads: error feedback makes the running mean
        of the decoded stream converge to the true gradient."""
        g = {"w": jnp.array([0.301, -0.007, 0.513, 0.002], jnp.float32)}
        err = comp.init_error_feedback(g, "int8")
        acc = jnp.zeros(4)
        n = 50
        for _ in range(n):
            wire, err = comp.compress_grads(g, err, "int8")
            acc = acc + wire["w"]
        np.testing.assert_allclose(np.asarray(acc / n),
                                   np.asarray(g["w"]), atol=1e-3)


class TestSimAndEmu:
    def test_simulator_agrees_with_scheduler(self):
        """Event simulator total within 2x of the analytic plan estimate
        and never better than Ideal (independent model cross-check)."""
        from repro.chip.config import ipu_pod4_hbm
        from repro.chip.simulator import simulate
        from repro.core.elk import compile_model
        from repro.core.baselines import ideal_plan
        from repro.core.graph import build_graph
        cfg = dataclasses.replace(get_config("llama2_13b"), num_layers=2)
        chip = ipu_pod4_hbm()
        plan = compile_model(cfg, chip, batch=32, seq=2048, phase="decode",
                             design="ELK-Dyn")
        res = simulate(plan, chip)
        assert 0.4 * plan.total_time <= res.total_time <= 2.5 * plan.total_time
        ideal = ideal_plan(build_graph(cfg, batch=32, seq=2048,
                                       phase="decode"), chip)
        # the simulator overlaps transfers the Ideal roofline serializes
        # (per-op hbm_time latencies), so allow it to land slightly under
        assert res.total_time >= ideal.total_time * 0.6

    def test_emulator_validates_plans(self):
        from repro.chip.config import ipu_mk2
        from repro.chip.emulator import check_plan_numerics
        from repro.core.graph import build_graph
        from repro.core.partition import (enumerate_exec_plans,
                                          enumerate_preload_plans)
        cfg = get_config("llama2_13b")
        g = build_graph(cfg, batch=4, seq=128, phase="decode")
        op = next(o for o in g.ops if o.kind == "matmul")
        chip = ipu_mk2()
        plans = enumerate_exec_plans(op, chip)[:4]
        for ep in plans:
            pps = enumerate_preload_plans(op, ep, chip)
            for pp in (pps[0], pps[-1]):
                check_plan_numerics(ep, pp)
