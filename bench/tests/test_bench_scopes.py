"""The reduction by the program's own names (``bench/scopes.py``): the
ops' name stacks read from the XPlane's event metadata, their classes,
and the split of a program's device time.

Two traces recorded on a TPU v5e (``data/``, gzipped), each two ticks of
qwen3_14b at 12 layers and 12 slots: a 128-token prefill chunk and a
decode step of 11 slots, inside ``bench.tick`` spans with the harness's
``bench.<call>`` spans around the engine's calls.  ``chat_trace`` was
recorded before the model had named scopes; on it every reader of
``bench/metrics`` reads the numbers it read when it was recorded.
``chat_scoped_trace`` holds the scoped program and the batcher's
``serve.*`` spans (recorded through ``bench.harness.build_program`` with
the profiler on around two ``ContinuousBatcher.tick`` calls)."""

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import scopes as S
from bench import trace_reduce as R
from bench.metrics import (RunData, decode_step_ms, fused_mlp_roofline,
                           idle_share, prefill_chunk_ms)

DATA = Path(__file__).resolve().parent / "data"


def _unzip(name, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / name.removesuffix(".gz")
    with gzip.open(DATA / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    return S.read(_unzip("chat_trace.xplane.pb.gz", tmp_path_factory))


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return S.read(_unzip("chat_scoped_trace.xplane.pb.gz", tmp_path_factory))


# -- the wire-format reader on a message built here --------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


def _entry(key, value):                    # one entry of a proto map
    return _field(1, key) + _field(2, value)


def _plane(name, events, stat_names):
    out = _field(2, name) + _field(3, _field(1, 7) + _field(2, "XLA Ops"))
    for i, (text, stats) in enumerate(events, 1):
        out += _field(4, _entry(i, _field(1, i) + _field(2, text) + b"".join(
            _field(5, s) for s in stats)))
    for k, v in stat_names.items():
        out += _field(5, _entry(k, _field(1, k) + _field(2, v)))
    return out


def test_wire_reader_takes_the_name_stack(tmp_path):
    stats = {1: "tf_op", 2: "flops", 3: "jit(step)/head/dot_general:"}
    tpu = _plane("/device:TPU:0", [
        ("%a = f32[2] fusion()", [_field(1, 2) + _field(4, 7),
                                  _field(1, 1) + _field(
                                      5, "jit(step)/while/body/layer/attn"
                                         "/kv_ring/scatter:")]),
        ("%b = f32[2] dot()", [_field(1, 1) + _field(7, 3)]),   # by ref
        ("%c = f32[2] copy()", [_field(1, 2) + _field(4, 1)]),  # no stack
    ], stats)
    host = _plane("/host:CPU", [("%d = f32[2] add()", [
        _field(1, 1) + _field(5, "host/op:")])], stats)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, tpu) + _field(1, host))
    assert S.op_names(path) == {
        "%a = f32[2] fusion()": (
            "jit(step)/while/body/layer/attn/kv_ring/scatter",),
        "%b = f32[2] dot()": ("jit(step)/head/dot_general",)}


def test_op_class():
    assert S.op_class("jit(step)/while/body/closed_call/layer/attn/kv_ring"
                      "/scatter") == "kv_ring"
    assert S.op_class("jit(step)/while/body/closed_call/layer/attn/exp") \
        == "attn"
    assert S.op_class("jit(step)/while/body/closed_call/layer/mlp/jit("
                      "fused_mlp_kernel)/pallas_call") == "mlp"
    assert S.op_class("jit(step)/while/body/closed_call/layer/add") == "layer"
    assert S.op_class("jit(step)/while/body/squeeze") == "layer_loop"
    assert S.op_class("jit(step)/while") == "layer_loop"
    assert S.op_class("jit(step)/kv_ring/reshape") == "kv_ring"
    assert S.op_class("jit(step)/head/bsd,dv->bsv/dot_general") == "head"
    assert S.op_class("jit(step)/argmax") == "other"
    assert S.op_class(None) == "other"
    # the compiler's copy of a ring argument into another layout
    assert S.op_class("cache['k']") == "kv_ring"
    assert S.op_class("cache['slot_pos']") == "kv_ring"
    assert S.op_class("params['embed']") == "other"


# -- the fixture recorded before the scopes -----------------------------------

def test_every_timed_op_has_a_name_stack(unscoped):
    """Only the halves of async copies and slices, and the markers around
    them, carry no ``tf_op``; they hold under 1% of the programs' time."""
    t = unscoped.trace
    bare, timed = 0.0, 0.0
    for d in t.devices:
        for op in d.ops:
            if op.name in R.CONTAINERS or not any(
                    m.start <= op.start < m.end for m in d.modules):
                continue
            timed += op.dur
            if not unscoped.stacks.get(op.text):
                assert op.name.endswith(("-start", "-done", "custom-call")), \
                    op.text
                bare += op.dur
    assert 0 < bare < 0.01 * timed


def test_existing_readers_read_as_before(unscoped):
    """The numbers every reader gave on this fixture when it was recorded."""
    run = RunData(win=None, trace=unscoped.trace, chips=1,
                  device_kind="TPU v5 lite")
    assert decode_step_ms.read(run) == pytest.approx(87.394136, abs=1e-6)
    assert prefill_chunk_ms.read(run) == pytest.approx(40.9423465, abs=1e-6)
    assert fused_mlp_roofline.read(run) == pytest.approx(91.96475459,
                                                         abs=1e-6)
    assert idle_share.read(run) == pytest.approx(3.35265670, abs=1e-6)


def test_unscoped_program_is_loop_and_other(unscoped):
    """Before the scopes, a step split into the layer scan (its own data
    movement and the layer's compute) and the rest; the classes add up to
    the program's time."""
    for program in ("jit_step", "jit_chunk"):
        split = unscoped.split(program)
        assert set(split) == set(S.ORDER)
        assert all(split[k] == 0 for k in S.CLASSES)
        assert split["layer_loop"] > split["other"] > 0
        assert sum(split.values()) == pytest.approx(
            unscoped.trace.program_s(program), rel=1e-3)
    assert unscoped.split("jit_nothing") is None
    assert unscoped.serve == [] and unscoped.tick_host_s() is None


def test_idle_by_span(unscoped):
    """With no ``serve.*`` spans the gaps fall to the harness's spans, as
    the harness's own breakdown has them."""
    idle = unscoped.trace.window_s - unscoped.trace.busy_s()
    rows = unscoped.idle_gaps()
    assert rows and all(k.startswith("bench.") for k, _ in rows)
    assert sum(v for _, v in rows) <= idle + 1e-9
    assert rows == unscoped.trace.idle_gaps()


@pytest.mark.parametrize("name", ["chat_trace.xplane.pb.gz",
                                  "chat_scoped_trace.xplane.pb.gz"])
def test_one_pass_reads_as_trace_reduce(name, tmp_path_factory):
    """``read``'s own pass over the trace gives what ``trace_reduce.read``
    gives, and every ``serve.*`` span besides."""
    path = _unzip(name, tmp_path_factory)
    ours, theirs = S.read(path).trace, R.read(path)
    assert ours == theirs


# -- the fixture recorded with the scopes and the batcher's spans ------------

def test_recorded_scoped_trace_is_small():
    assert (DATA / "chat_scoped_trace.xplane.pb.gz").stat().st_size < 1 << 20


@pytest.mark.parametrize("program", ["jit_step", "jit_chunk"])
def test_classes_add_up_to_the_program(scoped, program):
    split = scoped.split(program)
    total = scoped.trace.program_s(program)
    assert sum(split.values()) == pytest.approx(total, rel=0.01)
    assert split["other"] < 0.05 * total
    for k in ("attn", "mlp", "kv_ring", "head", "layer_loop"):
        assert split[k] > 0.01 * total, k


def test_decode_step_by_scope(scoped):
    """The step moves the ring and the weights about the layer scan more
    than it computes: the classes as recorded, in ms."""
    ms = {k: 1e3 * v for k, v in scoped.split("jit_step").items()}
    assert ms["kv_ring"] == pytest.approx(31.71, abs=0.05)
    assert ms["layer_loop"] == pytest.approx(38.81, abs=0.05)
    assert ms["mlp"] == pytest.approx(8.53, abs=0.05)
    assert ms["attn"] == pytest.approx(6.24, abs=0.05)
    assert ms["head"] == pytest.approx(2.06, abs=0.05)
    rows = S.summary(scoped, top=3)["step_stacks_ms"]
    assert [r[:2] for r in rows] == [
        ["jit(step)/while/body/squeeze", "layer_loop"],
        ["jit(step)/kv_ring/reshape", "kv_ring"],
        ["jit(step)/while", "layer_loop"]]


def test_serve_spans_of_two_ticks(scoped):
    ticks = [s for s in scoped.serve if s.name == "serve.tick"]
    assert len(ticks) == 2
    for t in ticks:
        inside = sorted((s for s in scoped.serve
                         if s is not t and t.start <= s.start < t.end),
                        key=lambda s: s.start)
        assert [s.name for s in inside] == [
            "serve.prefill", "serve.decode", "serve.sync", "serve.finish"]
        assert all(s.end <= t.end for s in inside)
    host = scoped.tick_host_s()
    waits = scoped.serve_s()["serve.sync"]
    assert 0 < host < 0.01
    assert host == pytest.approx(sum(t.dur for t in ticks) / 2 - waits / 2)


def test_idle_by_either_family(scoped):
    gaps = dict(scoped.idle_gaps())
    assert max(gaps, key=gaps.get) == "serve.sync"
    old = dict(scoped.trace.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(sum(old.values()))
    # the harness's own breakdown still names bench.* spans alone
    assert all(k.startswith("bench.") for k, _ in scoped.trace.idle_gaps())


def test_command_prints_the_split(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(DATA / "chat_scoped_trace.xplane.pb.gz") as src:
        path.write_bytes(src.read())
    proc = subprocess.run(
        [sys.executable, "bench/scopes.py", str(path)], cwd=DATA.parents[2],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.splitlines()[-1])
    step = out["split_ms"]["jit_step"]
    assert sum(step.values()) == pytest.approx(out["program_ms"]["jit_step"],
                                               rel=0.01)
    assert out["serve_s"]["serve.tick"] > out["serve_s"]["serve.sync"] > 0
    assert sum(r[2] for r in out["step_stacks_ms"]) <= sum(step.values())
