"""``bench/ticks.py``: the batcher's tick records over a window, summed,
and the command run whole on the CPU at a tiny size."""

import json
import sys

import pytest

from bench import harness, model, ticks, traffic
from bench_tiny import CHAT, cpu_device, qwen_tiny
from repro.serve.batcher import TickRecord


def _rec(start, length, readback, prefill=0, decoded=3):
    return TickRecord(start_s=start, end_s=start + length,
                      readback_s=readback, prefill_tokens=prefill,
                      decoded=decoded)


def test_summarize():
    recs = [_rec(0.0, 0.10, 0.09), _rec(0.1, 0.10, 0.08),
            _rec(3.0, 1.50, 1.49, prefill=128), _rec(9.0, 0.10, 0.07)]
    out = ticks.summarize(recs)
    assert out["ticks"] == 4
    assert out["tick_host_ms"] == pytest.approx((10 + 20 + 10 + 30) / 4)
    assert out["tick_median_ms"] == pytest.approx(100.0)
    assert out["readback_share"] == pytest.approx(1.73 / 1.80)
    longest = out["longest_ticks"]
    assert [t["start_s"] for t in longest] == [3.0, 0.0, 0.1, 9.0]
    assert longest[0]["readback_s"] == 1.49
    assert longest[0]["host_s"] == pytest.approx(0.01)
    assert longest[0]["prefill_tokens"] == 128
    assert "tick_host_ms_traced" not in out
    out = ticks.summarize(recs, traced=(2.0, 10.0))
    assert out["tick_host_ms_traced"] == pytest.approx(20.0)
    assert out["tick_host_ms_untraced"] == pytest.approx(15.0)


def test_command_on_the_cpu(monkeypatch, capsys):
    """Two seeds of a tiny chat cell, untraced: one line for the spans'
    cost and one per seed, every window tick kept."""
    monkeypatch.setattr(harness, "require_chips", cpu_device)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(model, "load_config", lambda name: qwen_tiny())
    monkeypatch.setattr(traffic, "load_mix", lambda name: CHAT)
    monkeypatch.setattr(sys, "argv", [
        "ticks.py", "--workload", "qwen3_14b.chat", "--seconds", "1.0",
        "--seeds", str(2 ** 33 + 5), "7"])
    ticks.main()
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert set(lines[0]["span_cost_us"]) == {"span", "step_span"}
    assert [r["seed"] for r in lines[1:]] == [2 ** 33 + 5, 7]
    for r in lines[1:]:
        assert r["ticks"] > 0 and r["tick_host_ms"] > 0
        assert 0 <= r["readback_share"] <= 1
        assert "scopes" not in r
        for t in r["longest_ticks"]:
            assert 0 <= t["readback_s"] <= t["length_s"]
