#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root
of the checkout; each cell's configuration, traffic mix, limits and
metric readers are files under ``bench/`` found by name.  One process,
one chip: it exits non-zero, printing no result, where JAX finds no TPU,
fewer chips than the cell asks for, or a device with no published peaks.
The last line of standard output is one JSON object; the numbers the
check compared, each beside its limit, are also the last lines of
standard error.
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


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    result = harness.execute(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
