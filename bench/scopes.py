#!/usr/bin/env python3
"""A profiler trace read by the program's own names: where a compiled
program's device time goes, by the model's named scopes, and what the
serving loop's host did while the chip waited.

    python3 bench/scopes.py <trace.xplane.pb, or a directory holding one>

prints one JSON object: the mean device ms of one ``jit_step`` and one
``jit_chunk`` call by class, the name stacks that take most of a
``jit_step``, the host seconds in each ``serve.*`` span, the mean host ms
of a tick less its readbacks, and the idle device time by the innermost
span of either family (``serve.*`` or ``bench.*``) open where each gap
begins.

``bench/trace_reduce.py`` reads what JAX's ``ProfileData`` gives: events
with a name, a start and a length; ``read`` here makes the same one pass,
keeping the ``serve.*`` host spans beside the ``bench.*`` ones.  An XLA
op's event is named by its HLO text; its JAX name stack (the ``tf_op`` stat, such as
``jit(step)/while/body/closed_call/layer/attn/kv_ring/scatter:``) sits in
the plane's event metadata, which ``ProfileData`` does not expose.  A
small reader of the protobuf wire format takes it from there: XSpace
.planes = 1; XPlane .name = 2, .lines = 3 (skipped by length),
.event_metadata = 4, .stat_metadata = 5 (maps: key 1, value 2);
XEventMetadata .name = 2, .stats = 5; XStatMetadata .id = 1, .name = 2;
XStat .metadata_id = 1, .str_value = 5, .ref_value = 7.

Each op inside a call of a program goes to the innermost of the model's
scopes on its name stack (``CLASSES``); an op with none that runs in the
layer scan's body or carry (``while`` on the stack) is ``layer_loop``:
the scan slicing each layer's weights and ring out of the stacks and
carrying them.  Anything else (embedding, sampling, an op without a name
stack) is ``other``.  Ops that hold other ops (``while``) are left out,
so the classes add up to the time of the ops that ran in the call.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import sys
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce as R  # noqa: E402

CLASSES = ("attn", "mlp", "kv_ring", "head", "layer")
ORDER = CLASSES + ("layer_loop", "other")
# the ring's leaves: a copy the compiler makes of one of them where the
# program is entered (to another layout) is named by the argument's path,
# such as ``cache['k']``, and is ring traffic like any other
RING_LEAVES = ("['k']", "['v']", "['k_scale']", "['v_scale']",
               "['slot_pos']")


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field, value)`` of one message: an int, or the bytes of a
    length-delimited field (a view, so skipping one costs nothing)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _map_value(entry):
    for f, v in _fields(entry):
        if f == 2:
            return v
    return b""


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_names(path) -> dict[str, tuple[str, ...]]:
    """HLO text -> the name stacks (``tf_op``, its ``:<type>`` tail cut)
    of the ops of that text on the trace's TPU planes.  One text can name
    ops of several programs, so each maps to every stack seen."""
    buf = memoryview(Path(path).read_bytes())
    out: dict[str, set] = collections.defaultdict(set)
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 4:
                events.append(_map_value(v))
            elif pf == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if not name.startswith("/device:TPU:"):
            continue
        tf_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        for ev in events:
            text, stack = None, None
            for ef, v in _fields(ev):
                if ef == 2:
                    text = _text(v)
                elif ef == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) not in tf_ids:
                        continue
                    stack = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
            if text is not None and stack:
                out[text].add(stack.rsplit(":", 1)[0] if ":" in stack
                              else stack)
    return {k: tuple(sorted(v)) for k, v in out.items()}


def op_class(stack: str | None) -> str:
    """The class of an op by its name stack: its innermost scope of
    ``CLASSES``, else ``layer_loop`` inside the scan, else ``kv_ring``
    for the compiler's copy of a ring argument, else ``other``."""
    parts = (stack or "").split("/")
    for p in reversed(parts):
        if p in CLASSES:
            return p
    if "while" in parts:
        return "layer_loop"
    return "kv_ring" if len(parts) == 1 and parts[0].endswith(
        RING_LEAVES) else "other"


def _stack_in(stacks: tuple[str, ...], program: str) -> str | None:
    """Of an HLO text's name stacks, the one of ``program``
    (``jit_step`` -> ``jit(step)/...``)."""
    head = "jit(" + program[len("jit_"):] + ")"
    for s in stacks:
        if s.split("/", 1)[0] == head:
            return s
    return stacks[0] if stacks else None


@dataclasses.dataclass
class Scoped:
    """A reduced trace with the ops' name stacks and the program's spans."""
    trace: R.Trace
    stacks: dict[str, tuple[str, ...]]
    serve: list[R.Span]           # serve.* host spans inside the window

    def _timed_stacks(self, program: str):
        """``(name stack, device seconds)`` of every op, containers left
        out, that starts inside a call of ``program`` in the window."""
        lo, hi = self.trace.window
        for d in self.trace.devices:
            mods = sorted((m.start, m.end) for m in d.modules
                          if R.module_base(m.name) == program
                          and lo <= m.start < hi)
            ops = sorted((o for o in d.ops if o.name not in R.CONTAINERS),
                         key=lambda o: o.start)
            j = 0
            for start, end in mods:
                while j < len(ops) and ops[j].start < start:
                    j += 1
                while j < len(ops) and ops[j].start < end:
                    op = ops[j]
                    yield _stack_in(self.stacks.get(op.text, ()),
                                    program), op.dur
                    j += 1

    def split(self, program: str = "jit_step") -> dict[str, float] | None:
        """Mean device seconds of one call of ``program``, by class."""
        n = len(self.trace.calls(program))
        if not n:
            return None
        agg = dict.fromkeys(ORDER, 0.0)
        for stack, dur in self._timed_stacks(program):
            agg[op_class(stack)] += dur
        return {k: v / n for k, v in agg.items()}

    def serve_s(self) -> dict[str, float]:
        """Host seconds inside each ``serve.*`` span, most first."""
        agg: collections.Counter = collections.Counter()
        for s in self.serve:
            agg[s.name] += s.dur
        return dict(agg.most_common())

    def tick_host_s(self) -> float | None:
        """Mean length of a ``serve.tick`` span less the ``serve.sync``
        spans inside it: the host's own time in a tick."""
        ticks = [s for s in self.serve if s.name == "serve.tick"]
        syncs = [s for s in self.serve if s.name == "serve.sync"]
        if not ticks:
            return None
        waits = sum(s.dur for s in syncs
                    if any(t.start <= s.start < t.end for t in ticks))
        return (sum(t.dur for t in ticks) - waits) / len(ticks)

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle device seconds by the innermost span, of either family,
        open where each gap begins."""
        both = R.Trace(self.trace.devices, self.trace.host + self.serve)
        return both.idle_gaps(top)


def summary(sc: Scoped, top: int = 12) -> dict:
    """What the command prints: the split of each program in ms, the
    ``top`` name stacks of one ``jit_step`` with their classes, the
    program times, the ``serve.*`` spans and the idle gaps."""
    stacks: collections.Counter = collections.Counter()
    for stack, dur in sc._timed_stacks("jit_step"):
        stacks[stack] += dur
    n = max(len(sc.trace.calls("jit_step")), 1)
    host = sc.tick_host_s()
    return {"split_ms": {p: None if (s := sc.split(p)) is None else
                         {k: 1e3 * v for k, v in s.items()}
                         for p in ("jit_step", "jit_chunk")},
            "step_stacks_ms": [[st, op_class(st), 1e3 * v / n]
                               for st, v in stacks.most_common(top)],
            "program_ms": {p: None if (s := sc.trace.program_s(p)) is None
                           else 1e3 * s for p in ("jit_step", "jit_chunk")},
            "serve_s": sc.serve_s(),
            "tick_host_ms": None if host is None else 1e3 * host,
            "idle_gaps": sc.idle_gaps()}


def read(path) -> Scoped:
    """One ``.xplane.pb`` (a file, or a directory holding one) as
    ``trace_reduce.read`` reduces it, in the same one ``ProfileData``
    pass, with the ``serve.*`` spans inside its window and its ops' name
    stacks."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = R.find_xplane(path)
    devices, host, serve = [], [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            devices.append(R.Device(
                modules=[R._span(e) for e in lines.get("XLA Modules", [])],
                ops=[R._span(e, text=True) for e in lines.get("XLA Ops",
                                                              [])]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("bench.", "serve.")):
                        (host if e.name[0] == "b" else serve).append(
                            R._span(e))
    if not devices or not host:
        raise ValueError(f"{path}: no TPU device plane or no bench.* host "
                         "spans in the trace")
    trace = R.Trace(devices, host)
    lo, hi = trace.window
    return Scoped(trace, op_names(path),
                  [s for s in serve if lo <= s.start and s.end <= hi])


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    print(json.dumps(summary(read(sys.argv[1]))))
