#!/usr/bin/env python3
"""The rows each held expert is given, and where a step's device time goes
by the expert layer's scopes, over a cell's window, in one process.

    python3 bench/experts.py --workload kimi_k2_instruct.long_decode \\
        --seconds 51 --seeds 101 102 --traced 1

For a configuration whose experts are sigmoid-routed over a held share,
for each seed: the seed's weights, the cell's slots filled and its
traffic served for ``--seconds`` as a benchmark run serves them.  The
first seeds decode with ``ServeEngine.step(count_experts=True)`` and keep
each step's routed rows per held expert and expert layer
(``last_expert_rows``) on the device, as the harness's ``Probe`` keeps
``last_logits``; their line gives the decode steps, the rows per held
expert per decode step (the mean over steps and expert layers, and the
max and mean over the experts of each one's mean), and those per layer.
The last ``--traced`` seeds serve with the plain step and the profiler
on over the part of the window a ``--trace 1`` run records; their line
gives the mean device ms of one ``jit_step`` and one ``jit_chunk`` by
the innermost of ``SCOPES`` on each op's name stack (``bench/scopes.py``'s
classes with the expert layer's beside them).  No output check runs: not
part of a benchmark run.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SCOPES = ("router", "dispatch", "combine", "moe", "shared_expert", "attn",
          "kv_ring", "mlp", "head", "layer")


def scope_split(scoped, program: str) -> dict | None:
    """Mean device ms of one call of ``program`` by the innermost of
    ``SCOPES`` (``layer_loop`` for the rest of the layer scan, ``other``
    outside it)."""
    n = len(scoped.trace.calls(program))
    if not n:
        return None
    agg: collections.Counter = collections.Counter()
    for stack, dur in scoped._timed_stacks(program):
        parts = (stack or "").split("/")
        cls = next((p for p in reversed(parts) if p in SCOPES), None)
        agg[cls or ("layer_loop" if "while" in parts else "other")] += dur
    return {k: 1e3 * v / n for k, v in agg.most_common()}


def expert_rows(rows) -> dict:
    """``rows``: (steps, expert layers, held) routed rows of each decode
    step."""
    import numpy as np

    per = rows.mean(0)                          # (layers, held)
    return {"decode_steps": int(rows.shape[0]),
            "rows_per_expert_step": float(per.mean()),
            "max_over_experts": float(per.mean(0).max()),
            "mean_over_experts": float(per.mean(0).mean()),
            "per_layer": [[float(x) for x in (r.mean(), r.max(), r.min())]
                          for r in per],
            "steps_with_no_row_share": float(np.mean(rows.sum((1, 2)) == 0))}


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
    import jax
    import numpy as np

    from bench import harness, model, scopes, traffic as T, window as Wn
    from repro.serve.batcher import ContinuousBatcher

    cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[
        args.workload]
    harness.require_chips(cell["chips"])
    harness.use_compile_cache()
    hf = model.load_config(cell["config"])
    mix = T.load_mix(cell["traffic"])
    eng = harness.build_program(hf, args.seeds[0], args.traced > 0, {})
    if args.traced < len(args.seeds):
        # compiled here, not at the counted window's first step
        tokens = jax.numpy.zeros((eng.scfg.slots,), jax.numpy.int32)
        eng._counted_step.lower(eng.params, tokens,
                                eng.slot_cache).compile()
    step = eng.step
    first_traced = len(args.seeds) - args.traced
    for k, seed in enumerate(args.seeds):
        if k:
            model.reseed(eng, seed, hf)
        plan = T.generate(mix, seed=seed, seconds=args.seconds,
                          slots=eng.scfg.slots, vocab=eng.cfg.vocab_size)
        runner = harness.Runner(ContinuousBatcher(eng), hf,
                                Wn.Window(seconds=args.seconds,
                                          slots=eng.scfg.slots))
        harness.fill(eng, runner, plan.prefilled)
        kept: list = []

        def counted(tokens):
            out = step(tokens, count_experts=True)
            kept.append(eng.last_expert_rows)
            return out
        tmp = (Path(tempfile.mkdtemp(prefix="experts_trace_"))
               if k >= first_traced else None)
        if tmp is None:
            eng.step = counted
        try:
            harness.serve_window(runner, plan, args.seconds, tmp)
            row = {"seed": seed}
            if kept:
                row.update(expert_rows(np.stack(jax.device_get(kept))))
            if tmp is not None:
                scoped = scopes.read(tmp)
                row["split_ms"] = {p: scope_split(scoped, p)
                                   for p in ("jit_step", "jit_chunk")}
                row["program_ms"] = {
                    p: None if (s := scoped.trace.program_s(p)) is None
                    else 1e3 * s for p in ("jit_step", "jit_chunk")}
        finally:
            eng.step = step
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
