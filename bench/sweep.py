#!/usr/bin/env python3
"""Find a cell's knee once: serve its mix at one fixed arrival rate and
print how the queue behaved.  One process per rate (a fresh chip each
time), so a sweep is a loop in the shell:

    for r in 0.45 0.6 0.75 0.9; do
        python3 bench/sweep.py --workload qwen3_14b.chat --seed 7 \\
            --seconds 40 --rate $r
    done

Not part of a benchmark run: the knee it finds is written into the mix's
file as a fixed rate.  No grace after a window: a request without a first
token when the window closes counts as waiting.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, model, traffic as T
    from bench.window import percentile

    harness.GRACE_S = 0.0
    cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[
        args.workload]
    harness.require_chips(cell["chips"])
    harness.use_compile_cache()
    hf = model.load_config(cell["config"])
    split: dict = {}
    eng = harness.build_program(hf, args.seed, False, split)
    print("set-up", json.dumps(split), flush=True)
    rate = args.rate
    mix = T.load_mix(cell["traffic"])
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
    out = harness.measure(eng, hf, mix, args.seed, args.seconds,
                          split=split, t_start=time.perf_counter())
    win, b = out["win"], out["runner"].b
    ttft = win.ttfts()
    arrived = win.in_window_arrivals()
    print(json.dumps({
        "rate_per_s": rate, "arrived": len(arrived),
        "first_token": len(ttft), "waiting_at_close": win.failed(),
        "queue_at_close": len(b.queue),
        "ttft_p50_s": percentile(ttft, 50),
        "ttft_p90_s": percentile(ttft, 90),
        "queue_wait_p90_s": percentile(win.queue_waits(), 90),
        "itl_p95_ms": percentile(win.gaps(), 95) * 1e3,
        "gen_tok_s": win.tokens_inside() / win.seconds,
        "occupancy": win.occupancy(),
        "ticks": len(win.window_ticks()),
        "tick_ms_mean": 1e3 * win.seconds / max(len(win.window_ticks()), 1),
        "fill_s": split.get("fill_s")}), flush=True)


if __name__ == "__main__":
    main()
