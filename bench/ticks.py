#!/usr/bin/env python3
"""The batcher's own record of each tick over a cell's window, in one
process.

    python3 bench/ticks.py --workload qwen3_14b.chat --seconds 51 \\
        --seeds 101 102 --traced 1

For each seed: the seed's weights, the cell's slots filled and its
traffic served for ``--seconds`` as a benchmark run serves them, and
``ContinuousBatcher.last_tick`` kept after every tick of the window.
Prints one JSON line per seed: the window's ticks, ``tick_host_ms`` (the
mean of a tick's length less its readback waits), the share of tick time
spent in readbacks, and the longest ticks split into readback and host
time with the work they did.  The last ``--traced`` seeds run with the
profiler on over the part of the window a ``--trace 1`` run records
(from ``harness.TRACE_OFFSET_S``, ``harness.TRACE_SPAN_S`` long); their
line adds ``tick_host_ms`` inside and outside that part and
``bench/scopes.py``'s summary of the trace.  The first line gives what
an inactive ``serve.*`` span costs on this host.  No output check runs:
not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
LONGEST = 5


def span_cost_us(n: int = 100_000) -> dict:
    """Mean microseconds of entering and leaving an inactive span."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    out = {}
    for name, make in (("span", lambda i: TraceAnnotation("serve.sync")),
                       ("step_span", lambda i: StepTraceAnnotation(
                           "serve.tick", step_num=i))):
        t = time.perf_counter()
        for i in range(n):
            with make(i):
                pass
        out[name] = (time.perf_counter() - t) / n * 1e6
    return out


def summarize(ticks, traced: tuple | None = None) -> dict:
    """``ticks``: the ``TickRecord``s that began inside the window."""
    import numpy as np

    def host_ms(ts):
        return float(np.mean([t.host_s for t in ts])) * 1e3 if ts else None

    length = sum(t.end_s - t.start_s for t in ticks)
    out = {"ticks": len(ticks), "tick_host_ms": host_ms(ticks),
           "tick_median_ms": float(np.median(
               [t.end_s - t.start_s for t in ticks])) * 1e3,
           "readback_share": sum(t.readback_s for t in ticks) / length,
           "longest_ticks": [
               {"start_s": t.start_s, "length_s": t.end_s - t.start_s,
                "readback_s": t.readback_s, "host_s": t.host_s,
                "prefill_tokens": t.prefill_tokens, "decoded": t.decoded}
               for t in sorted(ticks, key=lambda t: t.start_s - t.end_s)[
                   :LONGEST]]}
    if traced is not None:
        lo, hi = traced
        out["tick_host_ms_traced"] = host_ms(
            [t for t in ticks if lo <= t.start_s < hi])
        out["tick_host_ms_untraced"] = host_ms(
            [t for t in ticks if not lo <= t.start_s < hi])
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--traced", type=int, default=0,
                    help="the last seeds that run with the profiler on")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, model, scopes, traffic as T, window as Wn
    from repro.serve.batcher import ContinuousBatcher

    class Runner(harness.Runner):
        """Keeps the batcher's record of every tick once the window opens."""
        records: list | None = None

        def open_window(self) -> None:
            super().open_window()
            self.records = []

        def tick(self) -> None:
            super().tick()
            if self.records is not None:
                self.records.append(self.b.last_tick)

    cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[
        args.workload]
    harness.require_chips(cell["chips"])
    harness.use_compile_cache()
    hf = model.load_config(cell["config"])
    mix = T.load_mix(cell["traffic"])
    print(json.dumps({"span_cost_us": span_cost_us()}), flush=True)
    eng = harness.build_program(hf, args.seeds[0], args.traced > 0, {})
    first_traced = len(args.seeds) - args.traced
    for k, seed in enumerate(args.seeds):
        if k:
            model.reseed(eng, seed, hf)
        plan = T.generate(mix, seed=seed, seconds=args.seconds,
                          slots=eng.scfg.slots, vocab=eng.cfg.vocab_size)
        runner = Runner(ContinuousBatcher(eng), hf,
                        Wn.Window(seconds=args.seconds,
                                  slots=eng.scfg.slots))
        harness.fill(eng, runner, plan.prefilled)
        tmp = (Path(tempfile.mkdtemp(prefix="ticks_trace_"))
               if k >= first_traced else None)
        traced = None
        try:
            harness.serve_window(runner, plan, args.seconds, tmp)
            if tmp is not None:
                lo = min(harness.TRACE_OFFSET_S,
                         max(args.seconds - harness.TRACE_SPAN_S, 0.0))
                traced = (lo, lo + harness.TRACE_SPAN_S)
                scoped = scopes.summary(scopes.read(tmp))
        finally:
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)
        ticks = [t for t in runner.records if t.start_s < args.seconds]
        row = {"seed": seed, **summarize(ticks, traced)}
        if traced is not None:
            row["scopes"] = scoped
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
