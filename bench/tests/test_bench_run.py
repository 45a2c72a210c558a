"""Whole runs of the harness on the CPU at a tiny size: the check passes on
the program as it is and fails with a token altered where it is produced,
and the command refuses to run without a TPU."""

import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness
from bench_tiny import CASES, CountingClock, cpu_device

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")


def _run(cell, seed=2 ** 33 + 5, seconds=3.0):
    config, mix, limits = CASES[cell]
    return harness.execute(cell, seed, seconds, False,
                           t_start=time.perf_counter(), chips_check=cpu_device,
                           overrides={"config": config(), "mix": mix,
                                      "limits": limits},
                           clock=CountingClock())


@pytest.mark.parametrize("cell", sorted(CASES))
def test_tiny_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks" and r["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(
        harness.load_benchmark(), cell, False)}
    assert set(r["metrics"]) == names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["checks"]["tokens_checked"]["value"] >= 100


@pytest.mark.parametrize("cell", sorted(CASES))
def test_token_altered_where_produced_fails(cell, monkeypatch):
    """Every third decode step hands back each slot's token plus one."""
    from repro.serve.engine import ServeEngine

    step, calls = ServeEngine.step, [0]

    def altered(self, tokens):
        out = step(self, tokens)
        calls[0] += 1
        return (out + 1) % self.cfg.vocab_size if calls[0] % 3 == 0 else out

    monkeypatch.setattr(ServeEngine, "step", altered)
    r = _run(cell)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > 1.0


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_exits_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3_14b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert _no_result(proc), proc.stdout + proc.stderr
    assert "needs a TPU" in proc.stderr


def test_exits_with_only_the_benchmark_files(tmp_path):
    """A checkout that holds BENCHMARK.json and bench/ alone runs nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3_14b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert _no_result(proc), proc.stdout + proc.stderr
    assert json.loads((tmp_path / "BENCHMARK.json").read_text())
