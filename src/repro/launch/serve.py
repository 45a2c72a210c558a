"""Serving launcher: batched generation with the ELK streaming engine.

Lock-step batch:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --mode elk_stream --batch 4 --steps 16

Continuous batching over a mixed-length request trace (tok/s + request
latency percentiles, optionally against the static-batching baseline):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
      --trace 16 --compare-static
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import ARCH_IDS, canonical, get_config, get_smoke_config
from repro.distributed.sharding import init_sharded
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import transformer as tfm
from repro.serve.engine import ServeConfig, ServeEngine, elk_serve_config


def _run_trace(eng: ServeEngine, args, vocab: int) -> dict:
    from repro.serve.batcher import (ContinuousBatcher, make_trace,
                                     run_static_trace, summarize)

    trace = make_trace(args.trace, vocab_size=vocab,
                       arrival_spacing_s=args.arrival_spacing,
                       seed=args.trace_seed, burst=args.burst,
                       sys_prompt_len=args.sys_prompt_len,
                       sys_prompt_frac=args.sys_prompt_frac)
    # warm the compile caches so the numbers are steady-state serving
    warm = make_trace(min(4, args.trace), vocab_size=vocab,
                      seed=args.trace_seed + 1)
    ContinuousBatcher(eng).run(warm)

    prefix_store = None
    if args.prefix_cache_mb > 0:
        from repro.serve.prefix import PrefixStore
        prefix_store = PrefixStore(args.prefix_cache_mb << 20)
    oversub = args.oversub if args.oversub > 0 else None
    bat = ContinuousBatcher(eng, oversub=oversub, prefix_store=prefix_store)
    t0 = time.perf_counter()
    completions = bat.run(trace)
    stats = {"continuous": summarize(completions,
                                     time.perf_counter() - t0)}
    stats["continuous"]["oversub"] = round(bat.oversub, 3)
    stats["continuous"]["spill_events"] = len(bat.spill_events)
    stats["continuous"]["planned_spill_s"] = round(bat.planned_spill_s, 6)
    if bat.prefix is not None:
        stats["continuous"]["prefix_hits"] = bat.prefix_hits
        stats["continuous"]["prefix_tokens_saved"] = bat.prefix_tokens_saved
    order = [c.rid for c in completions]
    print(f"continuous: {stats['continuous']}  finish order: {order}")

    if args.compare_static:
        run_static_trace(eng, warm)
        t0 = time.perf_counter()
        static = run_static_trace(eng, trace)
        stats["static"] = summarize(static, time.perf_counter() - t0)
        print(f"static:     {stats['static']}")

    if args.fleet > 0:
        stats["fleet"] = _run_fleet(eng, args, vocab)
        print(f"fleet:      {stats['fleet']}")
    return stats


def _run_fleet(eng: ServeEngine, args, vocab: int) -> dict:
    """Serve the same trace on an ``--fleet N`` pod fleet over the virtual
    clock (deterministic scheduling deltas, not wall time): N mixed
    replicas by default, or ``--disagg`` 1 prefill + N-1 decode pods with
    router-priced migrations; ``--ttft-slo-ms`` arms shedding."""
    import dataclasses

    from repro.serve.batcher import make_trace
    from repro.serve.engine import PREFILL_SAT
    from repro.serve.fleet import FleetPod, FleetRouter, PodCosts

    def pod(role):
        # mirror elk_serve_config's role sizing on the launcher's scfg
        chunk = eng.scfg.prefill_chunk
        if role == "prefill":
            chunk = min(PREFILL_SAT, eng.scfg.cache_capacity)
        elif role == "decode":
            chunk = min(16, eng.scfg.cache_capacity)
        scfg = dataclasses.replace(eng.scfg, prefill_chunk=chunk)
        return FleetPod(ServeEngine(eng.cfg, eng.mesh, eng.params, scfg),
                        role, costs=PodCosts.from_serve_config(scfg))

    roles = (["prefill"] + ["decode"] * (args.fleet - 1)
             if args.disagg and args.fleet > 1
             else ["mixed"] * args.fleet)
    router = FleetRouter([pod(r) for r in roles],
                         ttft_slo_s=args.ttft_slo_ms * 1e-3)
    router.run(make_trace(args.trace, vocab_size=vocab,
                          arrival_spacing_s=args.arrival_spacing,
                          seed=args.trace_seed, burst=args.burst,
                          sys_prompt_len=args.sys_prompt_len,
                          sys_prompt_frac=args.sys_prompt_frac))
    out = router.summary()
    out["disagg"] = bool(args.disagg and args.fleet > 1)
    out["ttft_slo_ms"] = args.ttft_slo_ms
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {ARCH_IDS} (dashed aliases ok)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="elk_stream",
                    choices=["gspmd", "elk_stream"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--cache", type=int, default=256)
    ap.add_argument("--kv-dtype", default="bfloat16",
                    choices=["bfloat16", "int8"])
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="0 = ask the ELK scheduler (core.integration)")
    ap.add_argument("--pipeline-pod", type=int, default=0, metavar="GROUPS",
                    help="plan the pod across GROUPS chip islands with the "
                         "joint hybrid search (cuts x tensor width x "
                         "replicas x microbatch, DESIGN.md §9; never worse "
                         "than pure pipeline stages) and size admission "
                         "from the steady-state interval (0 = flat pod)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--trace", type=int, default=0, metavar="N",
                    help="serve N mixed-length requests with continuous "
                         "batching instead of one lock-step batch")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--arrival-spacing", type=float, default=0.0,
                    help="seconds between request arrivals in --trace mode")
    ap.add_argument("--burst", type=int, default=1,
                    help="bursty arrivals: requests land in groups of this "
                         "size sharing one arrival time")
    ap.add_argument("--sys-prompt-len", type=int, default=0,
                    help="length of a shared system prompt prepended to "
                         "--sys-prompt-frac of the trace (prefix reuse)")
    ap.add_argument("--sys-prompt-frac", type=float, default=0.0)
    ap.add_argument("--oversub", type=float, default=0.0,
                    help="admission multiplier K over the physical slots "
                         "(0 = take K from the plan; needs a finite "
                         "backing tier, e.g. --stacked-gb)")
    ap.add_argument("--prefix-cache-mb", type=int, default=0,
                    help="prefix-KV store budget in MB (0 = plan-sized, "
                         "off unless the pod funds it)")
    ap.add_argument("--stacked-gb", type=float, default=0.0,
                    help="plan against an SRAM-only pod with this much "
                         "stacked DRAM (all-finite hierarchy: enables KV "
                         "offload + oversubscription, DESIGN.md §11)")
    ap.add_argument("--compare-static", action="store_true",
                    help="also run the static-batching baseline on the "
                         "same trace")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="also serve the trace on an N-pod fleet behind "
                         "the SLO-aware router, ticked on the virtual "
                         "clock (DESIGN.md §12)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregate the --fleet pods: 1 prefill pod + "
                         "N-1 decode pods with router-priced KV "
                         "migrations (default: N mixed replicas)")
    ap.add_argument("--ttft-slo-ms", type=float, default=0.0,
                    help="shed fleet requests whose predicted TTFT "
                         "exceeds this target (0 = admit everything)")
    ap.add_argument("--json-out", default="",
                    help="write --trace stats to this JSON file")
    args = ap.parse_args()

    use_compile_cache()
    arch = canonical(args.arch)
    cfg = get_smoke_config(arch) if args.smoke else get_config(arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())

    if args.prefetch_depth <= 0 and args.mode == "elk_stream":
        # plan against the config actually served: a smoke engine must not
        # run a prefetch depth chosen for the full-size model
        pod = None
        if args.pipeline_pod > 0:
            from repro.chip.config import tpu_v5e_pod_hier
            pod = tpu_v5e_pod_hier(groups=args.pipeline_pod)
        elif args.stacked_gb > 0:
            from repro.chip.config import GB, ipu_mk2
            pod = ipu_mk2().with_stacked_dram(int(args.stacked_gb * GB))
        scfg = elk_serve_config(cfg, batch=args.batch,
                                cache_capacity=args.cache,
                                kv_dtype=args.kv_dtype,
                                pipeline=args.pipeline_pod > 0, pod=pod,
                                mesh=mesh)
        msg = (f"ELK scheduler: mode={scfg.mode} "
               f"prefetch_depth={scfg.prefetch_depth} "
               f"prefill_chunk={scfg.prefill_chunk}")
        if scfg.steady_interval_s:
            msg += (f" steady_interval="
                    f"{scfg.steady_interval_s * 1e3:.3f}ms")
        if scfg.oversub > 1.0:
            msg += (f" oversub K={scfg.oversub:.2f} "
                    f"slot_spill={scfg.slot_spill_s * 1e6:.1f}us "
                    f"prefix_cache={scfg.prefix_cache_bytes >> 20}MB")
        print(msg)
    else:
        scfg = ServeConfig(
            batch=args.batch, cache_capacity=args.cache, mode=args.mode,
            prefetch_depth=max(args.prefetch_depth, 1),
            kv_dtype=args.kv_dtype)

    eng = ServeEngine(cfg, mesh, init_sharded(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg), mesh,
        fsdp=scfg.mode == "elk_stream"), scfg)

    if args.trace > 0:
        stats = _run_trace(eng, args, cfg.vocab_size)
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(stats, f, indent=1)
            print(f"wrote {args.json_out}")
        return

    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)
    t0 = time.perf_counter()
    out = eng.generate(prompts, steps=args.steps)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"generated {args.steps} tokens x {args.batch} requests in "
          f"{dt:.2f}s ({args.steps*args.batch/dt:.1f} tok/s); "
          f"sample: {out[0, -args.steps:].tolist()}")


if __name__ == "__main__":
    main()
