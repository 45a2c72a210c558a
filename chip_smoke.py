#!/usr/bin/env python3
"""Serve qwen3_14b at its published widths on a TPU through the slot engine,
and check the answers on the chip.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # elk_stream over four chips vs one

One chip: random weights from a seed, depth cut to ``LAYERS`` and nothing
else cut; ``REQUESTS`` requests of 64 to 1024 prompt tokens served through
``ContinuousBatcher``; the fused-MLP kernel against its reference on the
model's own activations; slot-path logits (``prefill_chunk``/``step``)
against lock-step ``prefill``/``decode``; the Pallas kernel present in the
compiled slot decode step.  ``--chips 4`` runs only ``elk_stream`` on a
(data=4, model=1) mesh, weights sharded 1/4 per chip, against the same
model on one of the four devices.

Everything runs in this one process (a chip belongs to the process that
touched it first).  Without a TPU it exits non-zero and prints no result.
Timings printed here are smoke timings, not benchmark numbers.  The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LAYERS = 12            # depth cut; every width stays as published
SLOTS = 8              # decode slots (rows of the running batch)
CAPACITY = 4096        # KV ring per slot, tokens
PROMPT_LENS = (64, 320, 704, 1024)
NEW_TOKENS = 32
REQUESTS = 16
PROBE_STEPS = 4        # teacher-forced decode steps compared on logits
SEED = 0
# Tolerances, as max |got - ref| over max |ref|.  bf16 keeps 8 mantissa
# bits (relative step 2^-8 = 0.0039): the kernel keeps its intermediate in
# fp32 where the reference rounds it to bf16 (a few steps); two serving
# paths re-associate bf16 sums through LAYERS layers.  On a TPU v5e the
# logits differed by 6.8e-3 (slot path vs lock-step) and 1.1e-2 (four
# chips vs one), so LOGIT_TOL sits a little under twice the larger.  A
# greedy token may differ only where the reference's top two logits are
# within TIE_TOL of max |ref| (four bf16 steps): a near tie.
MLP_TOL = 2e-2
LOGIT_TOL = 2e-2
TIE_TOL = 4 * 2.0 ** -8


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    return dev


def model_config():
    from repro.configs import get_config

    return dataclasses.replace(get_config("qwen3_14b"), num_layers=LAYERS)


def serve_config(cfg, mesh):
    """ServeConfig from the ELK plan for ``mesh``, as ``launch/serve.py``
    builds it."""
    from repro.serve.engine import elk_serve_config

    return elk_serve_config(cfg, batch=SLOTS, cache_capacity=CAPACITY,
                            num_chips=mesh.size, mesh=mesh)


def build_engine(cfg, mesh):
    """The engine for ``serve_config``, its weights built on the device
    straight into the engine's shardings."""
    import jax

    from repro.distributed.sharding import init_sharded
    from repro.models import transformer as tfm
    from repro.serve.engine import ServeEngine

    scfg = serve_config(cfg, mesh)
    params = init_sharded(lambda: tfm.init_params(
        jax.random.PRNGKey(SEED), cfg), mesh, fsdp=scfg.mode == "elk_stream")
    eng = ServeEngine(cfg, mesh, params, scfg)
    del params
    jax.block_until_ready(eng.params)
    return eng


def make_requests(cfg):
    from repro.serve.batcher import make_trace

    return make_trace(REQUESTS, vocab_size=cfg.vocab_size,
                      prompt_lens=PROMPT_LENS, max_new=(NEW_TOKENS,),
                      seed=SEED)


def serve(eng, trace, label):
    """Serve the trace twice: the first pass compiles every shape it meets
    (set-up), the second is the served run.  Returns tokens by rid."""
    import numpy as np

    from repro.serve.batcher import ContinuousBatcher

    t0 = time.perf_counter()
    ContinuousBatcher(eng).run(trace)
    t1 = time.perf_counter()
    done = ContinuousBatcher(eng).run(trace)
    t2 = time.perf_counter()
    by_rid = {c.rid: c for c in done}
    full = [r.rid for r in trace if r.rid in by_rid
            and len(by_rid[r.rid].tokens) == len(r.prompt) + r.max_new_tokens]
    gen = sum(r.max_new_tokens for r in trace)
    print(f"[{label}] served {len(done)}/{len(trace)} requests, "
          f"{len(full)} with their full {NEW_TOKENS} new tokens "
          f"({gen} generated); smoke timings, not benchmark numbers: "
          f"compile+first pass {t1 - t0} s, served pass {t2 - t1} s",
          flush=True)
    ok = len(full) == len(trace)
    return ok, {rid: np.asarray(c.tokens) for rid, c in by_rid.items()}


def probe_group(trace):
    """The requests of one prompt length: lock-step needs equal lengths."""
    import numpy as np

    length = PROMPT_LENS[1]
    group = [r for r in trace if len(r.prompt) == length]
    return [r.rid for r in group], np.stack([r.prompt for r in group])


def slot_probe(eng, prompts, forced, budget=None):
    """Last-position logits of the slot path: each prompt prefilled in
    power-of-two chunks within ``budget`` (default: the engine's admission
    budget), spliced into slot r, then ``PROBE_STEPS`` decode steps fed the
    ``forced`` tokens.  Returns float32 (rows, PROBE_STEPS + 1, V)."""
    import jax.numpy as jnp
    import numpy as np

    rows, length = prompts.shape
    budget = min(budget or eng.scfg.prefill_chunk, eng.scfg.cache_capacity)
    out = []
    for r in range(rows):
        rc, off = eng.new_request_cache(), 0
        while off < length:
            t = 1 << (min(length - off, budget).bit_length() - 1)
            _, rc = eng.prefill_chunk(
                rc, jnp.asarray(prompts[r:r + 1, off:off + t], jnp.int32))
            off += t
        out.append([np.asarray(eng.last_logits[0], np.float32)])
        eng.insert_slot(r, rc)
    for s in range(PROBE_STEPS):
        tokens = np.zeros((eng.scfg.slots,), np.int32)
        tokens[:rows] = forced[:, s]
        eng.step(jnp.asarray(tokens))
        logits = np.asarray(eng.last_logits, np.float32)
        for r in range(rows):
            out[r].append(logits[r])
    for r in range(rows):
        eng.evict_slot(r)
    return np.stack([np.stack(o) for o in out])


def lockstep_probe(eng, prompts, forced):
    """The same positions through lock-step ``prefill``/``decode`` — the
    jitted steps ``ServeEngine.generate`` runs."""
    import jax.numpy as jnp
    import numpy as np

    logits, cache = eng.prefill(jnp.asarray(prompts, jnp.int32))
    out = [np.asarray(logits[:, -1], np.float32)]
    for s in range(PROBE_STEPS):
        logits, cache = eng.decode(jnp.asarray(forced[:, s], jnp.int32),
                                   cache)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out, axis=1)


def compare_logits(label, got, ref, tol):
    """max |got - ref| / max |ref| within ``tol``; an argmax may differ
    only where the reference's own top two are within ``TIE_TOL`` of
    max |ref| (a near tie)."""
    import numpy as np

    scale = float(np.max(np.abs(ref)))
    rel = float(np.max(np.abs(got - ref))) / scale
    a_got, a_ref = got.argmax(-1), ref.argmax(-1)
    agree = int((a_got == a_ref).sum())
    margin = (np.take_along_axis(ref, a_ref[..., None], -1)
              - np.take_along_axis(ref, a_got[..., None], -1))[..., 0]
    widest = float(margin.max()) / scale
    ties_ok = widest <= TIE_TOL
    ok = rel <= tol and ties_ok
    print(f"[{label}] last-position logits: max|d|/max|ref| = {rel} "
          f"(tolerance {tol}); greedy token agrees at {agree}/{a_ref.size} "
          f"positions; widest reference margin at a disagreement "
          f"{widest} of max|ref| (near tie up to {TIE_TOL}) -> "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    return ok


def token_agreement(label, got, ref):
    """Share of generated tokens equal to the reference, per request up to
    the first divergence and overall (greedy streams diverge after one
    flipped near tie, so this is reported, not gated)."""
    import numpy as np

    same = total = 0
    prefix = []
    for rid, toks in ref.items():
        a, b = got[rid][-NEW_TOKENS:], toks[-NEW_TOKENS:]
        same += int((a == b).sum())
        total += NEW_TOKENS
        diff = np.nonzero(a != b)[0]
        prefix.append(int(diff[0]) if diff.size else NEW_TOKENS)
    print(f"[{label}] greedy tokens: {same}/{total} equal; tokens before "
          f"the first divergence per request: {prefix}", flush=True)


def check_mlp(eng, trace):
    """Fused-MLP kernel against ``fused_mlp_ref`` with layer 0's weights on
    activations the model makes: the trace's tokens embedded and RMS-normed
    by layer 0's ``ln2``, as that MLP sees them.  M = SLOTS (decode) and
    M = 128 (prefill chunk)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_mlp.ops import fused_mlp
    from repro.kernels.fused_mlp.ref import fused_mlp_ref
    from repro.models.layers import rms_norm

    blk = jax.tree.map(lambda a: a[0], eng.params["blocks"][0])
    w = blk["mlp"]
    tokens = jnp.asarray(np.concatenate([r.prompt for r in trace])[:128])
    x = rms_norm(jnp.take(eng.params["embed"], tokens, axis=0), blk["ln2"],
                 eng.cfg.norm_eps)
    kernel = jax.jit(lambda x, w: fused_mlp(
        x, w["w_up"], w["w_down"], w_gate=w["w_gate"], act=eng.cfg.mlp_act))
    ref = jax.jit(lambda x, w: fused_mlp_ref(
        x, w["w_up"], w["w_down"], w_gate=w["w_gate"], act=eng.cfg.mlp_act))
    ok = True
    for m in (SLOTS, 128):
        got = np.asarray(kernel(x[:m], w), np.float32)
        want = np.asarray(ref(x[:m], w), np.float32)
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        good = bool(np.isfinite(got).all()) and rel <= MLP_TOL
        ok &= good
        print(f"[mlp] fused-MLP kernel vs fused_mlp_ref, M={m}, "
              f"d={x.shape[1]}, ff={w['w_up'].shape[1]}: max|d|/max|ref| = "
              f"{rel} (tolerance {MLP_TOL}) -> {'PASS' if good else 'FAIL'}",
              flush=True)
    return ok


def kernel_in_step(eng):
    """The compiled slot decode step calls the Pallas kernel."""
    import jax.numpy as jnp

    tokens = jnp.zeros((eng.scfg.slots,), jnp.int32)
    text = eng._step_slots.lower(eng.params, tokens,
                                 eng.slot_cache).compile().as_text()
    ok = "tpu_custom_call" in text
    print(f"[step] compiled slot decode step contains tpu_custom_call: {ok}",
          flush=True)
    return ok


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def describe(cfg, eng):
    import jax

    from repro.configs import get_config

    full = get_config("qwen3_14b")
    nbytes = sum(a.nbytes for a in jax.tree.leaves(eng.params))
    print(f"model: {cfg.name} at published widths: d_model {cfg.d_model}, "
          f"{cfg.num_heads} q / {cfg.num_kv_heads} kv heads, head_dim "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"qk_norm {cfg.qk_norm}, GLU {cfg.gated_mlp}/{cfg.mlp_act}; "
          f"depth cut {full.num_layers} -> {cfg.num_layers} layers; "
          f"{nbytes} weight bytes; mode {eng.scfg.mode}, {eng.scfg.slots} "
          f"slots x {eng.scfg.cache_capacity}-token KV ring, prefill chunk "
          f"{eng.scfg.prefill_chunk}"
          + (f", prefetch depth {eng.scfg.prefetch_depth}"
             if eng.scfg.mode == "elk_stream" else ""), flush=True)


def one_chip(dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_local_mesh

    cfg = model_config()
    mesh = make_local_mesh()            # exactly one device, on any host
    t0 = time.perf_counter()
    eng = build_engine(cfg, mesh)
    print(f"[init] weights built on the device in "
          f"{time.perf_counter() - t0} s (smoke timing, compile included)",
          flush=True)
    describe(cfg, eng)
    trace = make_requests(cfg)
    checks = {"mlp": check_mlp(eng, trace)}

    # lock-step first, before the slot cache exists: its caches are gone
    # before the slot path allocates its own
    rids, prompts = probe_group(trace)
    ref_tokens = {}
    t0 = time.perf_counter()
    for length in PROMPT_LENS:
        group = [r for r in trace if len(r.prompt) == length]
        toks = jax.device_get(eng.generate(
            jnp.asarray(np.stack([r.prompt for r in group])), NEW_TOKENS))
        ref_tokens.update({r.rid: toks[i] for i, r in enumerate(group)})
    forced = np.stack([ref_tokens[r][-NEW_TOKENS:] for r in rids])
    ref_logits = lockstep_probe(eng, prompts, forced)
    print(f"[lockstep] generate over {len(ref_tokens)} requests and the "
          f"logits probe in {time.perf_counter() - t0} s (smoke timing, "
          f"compile included)", flush=True)

    got = slot_probe(eng, prompts, forced)
    checks["logits"] = compare_logits("slot vs lockstep", got, ref_logits,
                                      LOGIT_TOL)
    checks["served"], tokens = serve(eng, trace, "serve")
    token_agreement("slot vs lockstep", tokens, ref_tokens)
    checks["kernel"] = kernel_in_step(eng)
    peak, limit = peak_bytes(mesh.devices.flat[0])
    checks["memory"] = peak is not None and peak < 16e9
    print(f"[memory] peak_bytes_in_use {peak} of bytes_limit {limit} "
          f"(must stay under 16e9)", flush=True)
    return checks, mesh.size


def four_chips(dev):
    import jax
    import numpy as np

    from repro.launch.mesh import make_local_mesh

    cfg = model_config()
    trace = make_requests(cfg)
    rids, prompts = probe_group(trace)
    one, four = make_local_mesh(), make_local_mesh(4, 1)
    # both probes prefill in the same chunks, so the logits differ only by
    # the serving path (gather window over four chips vs resident weights)
    chunk = min(serve_config(cfg, m).prefill_chunk for m in (one, four))

    def run(mesh, label, forced=None):
        eng = build_engine(cfg, mesh)
        print(f"[{label}]", end=" ")
        describe(cfg, eng)
        ok, tokens = serve(eng, trace, label)
        if forced is None:
            forced = np.stack([tokens[r][-NEW_TOKENS:] for r in rids])
        logits = slot_probe(eng, prompts, forced, chunk)
        peak = [peak_bytes(d)[0] for d in mesh.devices.flat]
        print(f"[{label}] peak_bytes_in_use per device {peak}", flush=True)
        return ok, tokens, forced, logits

    ok1, ref_tokens, forced, ref_logits = run(one, "1 chip")
    gc.collect()
    jax.clear_caches()
    ok4, tokens, _, logits = run(four, "4 chips", forced)
    checks = {"served 1 chip": ok1, "served 4 chips": ok4}
    checks["logits"] = compare_logits("4 chips vs 1 chip", logits,
                                      ref_logits, LOGIT_TOL)
    token_agreement("4 chips vs 1 chip", tokens, ref_tokens)
    return checks, four.size


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the elk_stream four-chip phase and the "
                         "one-chip run it is compared with")
    args = ap.parse_args()

    dev = require_tpu()
    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"device: {dev.device_kind}, {len(jax.devices())} visible; "
          f"compile cache {cache_dir}", flush=True)
    # count: the chips the checked phase ran on, not the chips visible
    checks, count = four_chips(dev) if args.chips == 4 else one_chip(dev)
    entries = (sum(1 for _ in Path(cache_dir).iterdir())
               if Path(cache_dir).is_dir() else 0)
    print(f"compile cache: {entries} entries in {cache_dir}", flush=True)
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        sys.exit(f"chip_smoke: failed checks: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()
