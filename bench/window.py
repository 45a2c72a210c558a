"""What the measured window saw, and the end-to-end arithmetic over it.

The harness records, on the host clock and relative to the window's
start: each request's scheduled arrival and admission, the time of every
token (the end of the tick that produced it), and each tick.  Everything
here is plain arithmetic over those records, so the tests can check it
without a chip.

* A time to first token is counted for every request scheduled to arrive
  inside the window, from its scheduled arrival, however late its first
  token comes.  One that gets none before the run gives up has failed.
* A gap between tokens is counted when both tokens fall inside the window.
* A rate is the tokens whose time falls inside the window over the
  window's length.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def percentile(values, q: float) -> float:
    """``q``-th percentile, linear between order statistics (numpy's
    default); NaN for no values."""
    return float(np.percentile(values, q)) if len(values) else float("nan")


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    decoded: int                  # slots that produced a decode token
    prefill_tokens: int
    flops: float                  # useful model FLOPs of the tick


@dataclasses.dataclass
class Window:
    seconds: float
    slots: int
    arrival: dict = dataclasses.field(default_factory=dict)     # rid -> s
    lateness: list = dataclasses.field(default_factory=list)    # s
    admitted: dict = dataclasses.field(default_factory=dict)    # rid -> s
    stamps: dict = dataclasses.field(default_factory=dict)      # rid -> [s]
    ticks: list = dataclasses.field(default_factory=list)
    compiles: int = 0             # compiles inside the window
    gc_pauses: list = dataclasses.field(default_factory=list)  # (s, s, gen)

    def stamp(self, rid: int, t: float, n: int = 1) -> None:
        self.stamps.setdefault(rid, []).extend([t] * n)

    def _inside(self, t: float) -> bool:
        return 0.0 <= t <= self.seconds

    def in_window_arrivals(self) -> list[int]:
        return [r for r, t in self.arrival.items() if t < self.seconds]

    def ttfts(self) -> list[float]:
        out = []
        for rid in self.in_window_arrivals():
            times = self.stamps.get(rid)
            if times:
                out.append(times[0] - self.arrival[rid])
        return out

    def failed(self) -> int:
        """Window arrivals that never got a first token."""
        return sum(1 for r in self.in_window_arrivals()
                   if not self.stamps.get(r))

    def gaps(self) -> list[float]:
        out = []
        for times in self.stamps.values():
            for a, b in zip(times, times[1:]):
                if self._inside(a) and self._inside(b):
                    out.append(b - a)
        return out

    def tokens_inside(self) -> int:
        return sum(1 for times in self.stamps.values()
                   for t in times if self._inside(t))

    def served(self) -> int:
        """Requests that got a token inside the window."""
        return sum(1 for times in self.stamps.values()
                   if any(self._inside(t) for t in times))

    def queue_waits(self) -> list[float]:
        return [self.admitted[r] - self.arrival[r]
                for r in self.in_window_arrivals() if r in self.admitted]

    def window_ticks(self) -> list[Tick]:
        return [t for t in self.ticks if self._inside(t.end)]

    def occupancy(self) -> float:
        """Mean share of slots that decoded, over the window's ticks."""
        ticks = self.window_ticks()
        if not ticks:
            return float("nan")
        return float(np.mean([t.decoded for t in ticks])) / self.slots

    def model_flops(self) -> float:
        return sum(t.flops for t in self.window_ticks())

    def _between_ticks(self) -> float:
        """Window time outside any tick: the harness's own loop."""
        ticks = self.window_ticks()
        return sum(b.start - a.end for a, b in zip(ticks, ticks[1:]))

    def end_to_end(self) -> dict:
        """Every end-to-end quantity this window can give, by metric name."""
        ttft, gaps = self.ttfts(), self.gaps()
        return {
            "ttft_p50_s": percentile(ttft, 50),
            "itl_p95_ms": percentile(gaps, 95) * 1e3,
            "gen_tok_s": self.tokens_inside() / self.seconds,
        }

    def samples(self) -> dict:
        """Sample counts behind each number, printed beside them."""
        late = self.lateness or [0.0]
        longest = sorted(self.window_ticks(), key=lambda t: t.end - t.start)
        lengths = [t.end - t.start for t in longest]
        typical = percentile(lengths, 50)
        slow = [x - typical for x in lengths if x > 2 * typical]
        return {"ttft_requests": len(self.ttfts()),
                "itl_gaps": len(self.gaps()),
                "tokens": self.tokens_inside(),
                "ticks": len(self.window_ticks()),
                "served_requests": self.served(),
                "generator_late_max_s": max(late),
                "generator_late_mean_s": float(np.mean(late)),
                "compiles_in_window": self.compiles,
                "tick_median_s": typical,
                "slow_ticks": len(slow),          # over twice the median
                "slow_ticks_excess_s": sum(slow),
                "between_ticks_s": self._between_ticks(),
                "longest_ticks": [[t.start, t.end - t.start, t.prefill_tokens,
                                   t.decoded] for t in longest[:-4:-1]],
                "gc_pauses": len(self.gc_pauses),
                "gc_total_s": sum(p[1] for p in self.gc_pauses),
                "gc_longest": [list(p) for p in sorted(
                    self.gc_pauses, key=lambda p: p[1])[:-4:-1]]}
