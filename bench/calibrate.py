#!/usr/bin/env python3
"""Readings that a cell's output limits are set from, in one process.

    python3 bench/calibrate.py --workload qwen3_14b.chat --seconds 51 \\
        --seeds 101 102 ... --control 3

For each seed: the seed's weights, one window of the cell's own traffic
at its own load, the run's sample of finished requests, and the numbers
that ``harness.check`` compares for the program (the lower readings),
with where its widest gap lies.  For the first ``--control`` seeds also
each control of the plain reference (``CONTROLS`` in the cell's
reference, or those ``--controls`` names), put in the program's place and
judged by the same ``harness.check`` against the cell's limits (the upper
readings).  Prints one JSON line per seed.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def widest(seqs, kept) -> dict | None:
    """Where the program's widest gap lies (the request, the served token,
    the gap, and the program's logit error there beside its median), and
    each request's length, served tokens and median logit error for the
    program and, where it was read, the bfloat16 witness."""
    import numpy as np

    from bench.harness import token_errors

    rows, reqs = [], []
    for j, ((tokens, first, at), (gap, r)) in enumerate(
            zip(seqs, kept.get("reference", []))):
        if at is None:
            return None
        err = token_errors(at, r)
        rows += [(float(g), j, i, int(first), float(e))
                 for i, (g, e) in enumerate(zip(gap, err))]
        req = [int(len(tokens)), int(len(gap)), float(np.median(err))]
        if "bf16" in kept:
            req.append(float(np.median(token_errors(kept["bf16"][j][1], r))))
        reqs.append(req)
    if not rows:
        return None
    g, j, i, first, e = max(rows)
    return {"gap": g, "request": j, "token": first + i, "err_there": e,
            "err_median": float(np.median([x[-1] for x in rows])),
            "flipped": sum(1 for x in rows if x[0] > 0), "requests": reqs}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also read the controls")
    ap.add_argument("--controls", nargs="*", default=None,
                    help="which of the reference's CONTROLS (default: all)")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import families, harness, model, traffic as T

    cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[
        args.workload]
    harness.require_chips(cell["chips"])
    harness.use_compile_cache()
    hf = model.load_config(cell["config"])
    mix = T.load_mix(cell["traffic"])
    limits = harness.load_limits(args.workload)
    ref = families.reference(hf)
    split: dict = {}
    eng = harness.build_program(hf, args.seeds[0], False, split)
    for k, seed in enumerate(args.seeds):
        if k:
            model.reseed(eng, seed, hf)
        out = harness.measure(eng, hf, mix, seed, args.seconds, split=split,
                              t_start=time.perf_counter())
        seqs = harness.sample(list(out["runner"].b.completed),
                              out["runner"], seed, out["probe"])
        del out
        # the reference runs beside nothing of the program's: free the
        # weights and the slots (the next seed's window rebuilds both)
        eng.params = eng.slot_cache = None
        gc.collect()
        t = time.perf_counter()
        controls = (ref.CONTROLS if args.controls is None
                    else tuple(args.controls)) if k < args.control else ()
        kept: dict = {}
        res = harness.check(hf, seed, seqs, limits, controls, kept)
        row = {"seed": seed, "requests": len(seqs),
               "lengths": [int(len(t)) for t, _, _ in seqs],
               "reference_s": time.perf_counter() - t,
               "widest": widest(seqs, kept)}
        for who, (ok, checks) in res.items():
            if who == "bf16":       # a witness, judged by no limit
                continue
            row[who] = {"correct": ok, **{n: c["value"]
                                          for n, c in checks.items()}}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
