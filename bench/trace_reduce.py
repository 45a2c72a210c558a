"""From a profiler trace of the window to per-layer numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's own reader.  On a TPU the trace holds, per device plane
(``/device:TPU:<n>``), one line of compiled programs (``XLA Modules``,
named ``jit_<function>(<fingerprint>)``) and one line of the operations
inside them (``XLA Ops``, named by their HLO text, ``%<name> = <shape>
<op>(<operands>)``).  Host planes hold the harness's own spans, every one
named ``bench.<what>``.  All times share one clock.

* busy: the union of the operations' intervals on a device, in the traced
  window, averaged over the devices;
* idle share: 1 - busy over the stretches in which the batcher had work,
  which is the window less the spans marked ``bench.idle``;
* time per program: the mean device time of one call of a module;
* kernel time: the device time of the operations whose HLO name starts
  with the kernel's name, optionally only inside one program;
* breakdown: the operations that took most time, by kind, and the
  longest idle gaps, by the innermost harness span that was open when the
  gap began.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import re
from pathlib import Path

CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float                  # seconds on the trace's clock
    end: float
    text: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


def op_kind(text: str) -> str:
    """``%fused_mlp_kernel.9 = bf16[...] custom-call(...)`` ->
    ``fused_mlp_kernel``; numbered copies of one fusion share a kind."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\.remat\w*|\.clone\w*)+$", "", name)


def module_base(name: str) -> str:
    """``jit_step(1443...)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def union(spans) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((s.start, s.end) for s in spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(intervals, holes) -> list[tuple[float, float]]:
    """``intervals`` less the (sorted, disjoint) ``holes``."""
    out = []
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur or hs >= e:
                continue
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Device:
    modules: list[Span]
    ops: list[Span]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: list[Span]              # the harness's bench.* spans

    @property
    def window(self) -> tuple[float, float]:
        """From the first harness span to the end of the last."""
        return (min(s.start for s in self.host),
                max(s.end for s in self.host))

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return hi - lo

    def _busy(self, dev: Device) -> list[tuple[float, float]]:
        return clip(union(dev.ops or dev.modules), *self.window)

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        return sum(total(self._busy(d)) for d in self.devices) / len(
            self.devices)

    def stretches(self) -> list[tuple[float, float]]:
        """The window less the harness's ``bench.idle`` spans."""
        idle = union(s for s in self.host if s.name == "bench.idle")
        return subtract([self.window], idle)

    def idle_share(self) -> float | None:
        work = self.stretches()
        if total(work) <= 0:
            return None
        shares = []
        for d in self.devices:
            busy = sum(total(clip(self._busy(d), s, e)) for s, e in work)
            shares.append(1.0 - busy / total(work))
        return sum(shares) / len(shares)

    def calls(self, base: str) -> list[Span]:
        lo, hi = self.window
        return [m for d in self.devices for m in d.modules
                if module_base(m.name) == base and lo <= m.start < hi]

    def program_s(self, base: str) -> float | None:
        """Mean device seconds of one call of the program ``base``."""
        calls = self.calls(base)
        return sum(c.dur for c in calls) / len(calls) if calls else None

    def kernel_ops(self, kind: str, inside: str | None = None) -> list[Span]:
        """The ops of ``kind`` in the window, only within calls of the
        program ``inside`` where given."""
        lo, hi = self.window
        out = []
        for d in self.devices:
            mods = [m for m in d.modules
                    if inside is None or module_base(m.name) == inside]
            for op in d.ops:
                if op_kind(op.text) != kind or not lo <= op.start < hi:
                    continue
                if any(m.start <= op.start < m.end for m in mods):
                    out.append(op)
        return out

    def device_ops(self, top: int = 10) -> list[list]:
        """Device seconds by op kind, containers left out, most first."""
        lo, hi = self.window
        agg: collections.Counter = collections.Counter()
        for d in self.devices:
            for op in d.ops:
                kind = op_kind(op.text)
                if kind in CONTAINERS:
                    continue
                agg[kind] += total(clip([(op.start, op.end)], lo, hi))
        n = len(self.devices)
        return [[k, v / n] for k, v in agg.most_common(top)]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle device seconds inside the stretches of work, by the
        innermost harness span open where each gap begins."""
        agg: collections.Counter = collections.Counter()
        spans = sorted((h for h in self.host if h.name != "bench.idle"),
                       key=lambda h: h.start)
        for d in self.devices:
            gaps = subtract(self.stretches(), self._busy(d))
            i, open_ = 0, []
            for gs, ge in gaps:             # one sweep: gaps and spans in order
                while i < len(spans) and spans[i].start <= gs:
                    open_.append(spans[i])
                    i += 1
                open_ = [h for h in open_ if h.end > gs]
                name = min(open_, key=lambda h: h.dur).name \
                    if open_ else "bench.none"
                agg[name] += ge - gs
        n = len(self.devices)
        return [[k, v / n] for k, v in agg.most_common(top)]


def find_xplane(trace_dir) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def read(path) -> Trace:
    """Parse one ``.xplane.pb`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            devices.append(Device(
                modules=[_span(e) for e in lines.get("XLA Modules", [])],
                ops=[_span(e, text=True) for e in lines.get("XLA Ops", [])]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_span(e) for e in line.events
                            if e.name.startswith("bench."))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if not host:
        raise ValueError(f"{path}: no bench.* host spans in the trace")
    return Trace(devices, host)


def _span(e, text: bool = False) -> Span:
    start = e.start_ns * 1e-9
    return Span(name=e.name if not text else op_kind(e.name), start=start,
                end=start + e.duration_ns * 1e-9,
                text=e.name if text else "")
