"""The trace reduction on a small trace recorded on a TPU v5e (``data/``,
gzipped): two ticks of qwen3_14b at 12 layers and 12 slots, each a
128-token prefill chunk and a decode step, inside ``bench.tick`` spans
with ``bench.chunk`` and ``bench.step`` around the engine's calls."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench import trace_reduce as R
from bench.metrics import (RunData, decode_step_ms, fused_mlp_roofline,
                           idle_share, prefill_chunk_ms)

DATA = Path(__file__).resolve().parent / "data" / "chat_trace.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "chat.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return R.read(path)


def _run(trace):
    return RunData(win=None, trace=trace, chips=1,
                   device_kind="TPU v5 lite")


def test_recorded_trace_is_small():
    assert DATA.stat().st_size < 1 << 20


def test_busy_and_idle(trace):
    assert 0 < trace.busy_s() <= trace.window_s
    share = idle_share.read(_run(trace))
    assert 0 <= share < 100
    assert trace.stretches()


def test_programs_by_name(trace):
    step = decode_step_ms.read(_run(trace))
    chunk = prefill_chunk_ms.read(_run(trace))
    assert len(trace.calls("jit_step")) >= 1 and len(trace.calls("jit_chunk")) >= 1
    assert 1 < chunk < step < 1000        # ms; a 128-token chunk is shorter


def test_kernel_and_roofline(trace):
    ops = trace.kernel_ops("fused_mlp_kernel", inside="jit_step")
    assert len(ops) % 12 == 0 and ops      # one call per layer, 12 layers
    share = fused_mlp_roofline.read(_run(trace))
    assert 50 < share <= 105
    assert trace.kernel_ops("no_such_kernel") == []


def test_breakdown(trace):
    ops, gaps = trace.device_ops(), trace.idle_gaps()
    assert 0 < len(ops) <= 10 and len(gaps) <= 10
    assert all(k not in R.CONTAINERS for k, _ in ops)
    assert sum(v for _, v in ops) <= trace.busy_s() * 1.001
    assert all(k.startswith("bench.") for k, _ in gaps)
    assert sum(v for _, v in gaps) <= trace.window_s - trace.busy_s() + 1e-9


def test_op_kind_and_module_names():
    assert R.op_kind("%fused_mlp_kernel.9 = bf16[8] custom-call()") == \
        "fused_mlp_kernel"
    assert R.op_kind("%dynamic-slice_bitcast_fusion.12.remat2 = x") == \
        "dynamic-slice_bitcast_fusion"
    assert R.module_base("jit_step(1443651)") == "jit_step"
