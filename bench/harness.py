"""One run of one cell: set-up, the measured window, the check.

``execute`` does the whole run and returns the result line as a dict;
``bench/run.py`` is the command around it.  The order inside a run:

1. set-up: refuse anything but a TPU (``require_chips``), turn on the
   compile cache in the checkout, build the model on the device from the
   seed, warm up every shape the window uses by serving one request
   through every chunk length, and fill the slots as the mix says;
2. the window: an open loop (or a full backlog) drives the program's
   ``ContinuousBatcher.tick`` over ``ServeEngine`` for ``seconds``; every
   token is stamped at the end of the tick that made it;
3. with ``trace``, the profiler records part of the window and
   ``bench/trace_reduce.py`` reduces it; the per-layer metrics are read by
   the readers in ``bench/metrics``;
4. the peak of device memory is read, the program is freed, and the plain
   reference checks a sample of the finished requests: the tokens they
   were served, and the program's logits at a few vocabulary entries for
   each of those tokens, which a ``Probe`` keeps as the window makes them.
"""

from __future__ import annotations

import functools
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import families

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
GRACE_S = 60.0                       # wait for late first tokens
SAMPLE_TOKENS = 4096                 # served tokens the check aims at
SAMPLE_REQUESTS = 48
PROBE_ENTRIES = 128                  # vocabulary entries the probe keeps
TRACE_OFFSET_S = 2.0                 # traced part of the window
TRACE_SPAN_S = 8.0


def log(*a) -> None:
    print(*a, flush=True)


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def require_chips(chips: int):
    """The first device, where JAX sees a TPU with enough chips and peaks
    for its kind; otherwise exit non-zero before anything is printed."""
    import jax

    from bench.peaks import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devs[0].platform!r} "
                 f"({devs[0].device_kind})")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips; {len(devs)} visible")
    try:
        peaks(devs[0].device_kind)
    except ValueError as e:
        sys.exit(f"bench: {e}")
    return devs[0]


def use_compile_cache() -> str:
    """The program's persistent cache (the checkout's ``.jax_cache/``, or
    ``JAX_COMPILATION_CACHE_DIR``), kept for every program however quick
    to compile, so that a run after the first compiles nothing."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def _pow2_chunk(remaining: int, budget: int) -> int:
    """Largest power of two <= min(remaining, budget): the chunk lengths
    the batcher uses, so set-up compiles nothing the window would not."""
    return 1 << (min(remaining, budget).bit_length() - 1)


def _annotate(eng) -> None:
    """Wrap the engine's slot calls in ``bench.<call>`` host spans."""
    from jax.profiler import TraceAnnotation

    for name in ("prefill_chunk", "step", "insert_slot", "evict_slot",
                 "new_request_cache", "refill_slot"):
        fn = getattr(eng, name)

        def wrapped(*a, _fn=fn, _span=f"bench.{name}", **k):
            with TraceAnnotation(_span):
                return _fn(*a, **k)
        setattr(eng, name, wrapped)


def probe_entries(seed: int, vocab: int) -> np.ndarray:
    """The vocabulary entries whose logits the check compares, from the
    seed."""
    rng = np.random.default_rng([int(seed), 2])
    return np.sort(rng.choice(vocab, min(PROBE_ENTRIES, vocab),
                              replace=False)).astype(np.int32)


class Probe:
    """Keeps the program's logits at ``idx`` for every token it serves.

    While installed, each decode ``step`` and each ``prefill_chunk`` that
    ends a prompt is followed by one small gather of the engine's
    ``last_logits`` at ``idx``, kept on the device, with the request and
    output index that each row belongs to (read from the batcher as the
    call is made).  ``at(rid, k0, n)`` gives the rows of output tokens
    ``k0 .. k0 + n - 1`` of a request once the window is over."""

    def __init__(self, eng, batcher, idx):
        import jax
        import jax.numpy as jnp

        self.eng, self.b = eng, batcher
        self.idx = jnp.asarray(idx)
        self.take = jax.jit(lambda lg, i: jnp.take(lg, i, axis=-1).astype(
            jnp.float32))
        self.rows: list = []
        self.where: dict[tuple[int, int], tuple[int, int]] = {}
        self.host = None
        self.saved = None

    def warm(self) -> None:
        """Compile the gather for both shapes the window gives it."""
        import jax.numpy as jnp

        eng = self.eng
        if eng.last_logits is not None:
            self.take(eng.last_logits, self.idx)
        eng.prefill_chunk(eng.new_request_cache(), jnp.zeros((1, 1),
                                                             jnp.int32))
        self.take(eng.last_logits, self.idx).block_until_ready()

    def install(self) -> None:
        eng, b = self.eng, self.b
        self.saved = {n: eng.__dict__.get(n) for n in ("step",
                                                      "prefill_chunk")}
        step, chunk = eng.step, eng.prefill_chunk

        def stepped(tokens):
            out = step(tokens)
            row = len(self.rows)
            self.rows.append(self.take(eng.last_logits, self.idx))
            for slot, st in b.active.items():
                self.where[(st.req.rid, len(st.generated))] = (row, slot)
            return out

        def chunked(cache, tokens):
            out = chunk(cache, tokens)
            ps = b.prefilling
            if ps is not None and ps.off + tokens.shape[1] == len(
                    ps.req.prompt):
                self.where[(ps.req.rid, 0)] = (len(self.rows), 0)
                self.rows.append(self.take(eng.last_logits, self.idx))
            return out
        eng.step, eng.prefill_chunk = stepped, chunked

    def remove(self) -> None:
        for n, fn in self.saved.items():
            if fn is None:
                self.eng.__dict__.pop(n, None)
            else:
                setattr(self.eng, n, fn)

    def at(self, rid: int, k0: int, n: int):
        """(n, entries) float32, or None where a token has no row."""
        import jax

        if self.host is None:
            self.host = jax.device_get(self.rows)
            self.rows = []
        try:
            return np.stack([self.host[r][s] for r, s in (
                self.where[(rid, k)] for k in range(k0, k0 + n))])
        except KeyError:
            return None


class Runner:
    """Drives the batcher and keeps the window's records."""

    def __init__(self, batcher, hf: dict, win, clock=time.perf_counter):
        self.b, self.hf, self.win, self.clock = batcher, hf, win, clock
        self.known: dict[int, int] = {}       # rid -> tokens generated
        self.prompt_len: dict[int, int] = {}
        self.first_served: dict[int, int] = {}  # rid -> first served index
        self.n_done = 0
        self.t0 = clock()

    @functools.cached_property
    def chunk_flops(self):
        """The useful FLOPs of a chunk, as the family adapter counts them."""
        return families.adapter(self.hf).chunk_flops

    def now(self) -> float:
        return self.clock() - self.t0

    def sleep(self, seconds: float) -> None:
        if self.clock is time.perf_counter:
            time.sleep(seconds)

    def open_window(self) -> None:
        """Start the window's clock.  What set-up recorded (the ticks and
        tokens that filled the slots) moves to before the window's start,
        so that no window metric counts it."""
        t0 = self.clock()
        shift = t0 - self.t0
        for times in self.win.stamps.values():
            times[:] = [t - shift for t in times]
        for tick in self.win.ticks:
            tick.start -= shift
            tick.end -= shift
        self.t0 = t0
        self.b.t0 = t0

    def adopt(self, planned, state) -> None:
        """A prefilled slot: its context and ``done`` tokens are already in
        ``state``; the last done token is the next one fed."""
        from repro.serve.batcher import Request

        req = Request(rid=planned.rid, prompt=planned.prompt,
                      max_new_tokens=planned.max_new)
        self.prompt_len[planned.rid] = len(planned.prompt)
        self.known[planned.rid] = len(planned.done)
        self.first_served[planned.rid] = len(planned.prompt) + len(
            planned.done)
        self.b.adopt(req, state, [int(t) for t in planned.done], 0.0,
                     admitted_s=0.0)

    def submit(self, planned, scheduled: bool) -> None:
        from repro.serve.batcher import Request

        now = self.now()
        self.prompt_len[planned.rid] = len(planned.prompt)
        self.first_served[planned.rid] = len(planned.prompt)
        if scheduled:
            self.win.arrival[planned.rid] = planned.arrival_s
            self.win.lateness.append(now - planned.arrival_s)
        self.b.submit(Request(rid=planned.rid, prompt=planned.prompt,
                              max_new_tokens=planned.max_new,
                              arrival_s=planned.arrival_s))

    def tick(self) -> None:
        """One scheduler tick, and the records it leaves."""
        from bench.window import Tick

        b, hf = self.b, self.hf
        queued = {r.rid for r in b.queue}
        pf = b.prefilling
        pf_at = (pf.req.rid, pf.off) if pf is not None else None
        start = self.now()
        b.tick()
        end = self.now()
        for rid in queued - {r.rid for r in b.queue}:
            self.win.admitted[rid] = start
        flops, decoded = 0.0, 0
        t = b.tick_prefill_tokens
        if t:
            if pf_at is not None:
                rid, off = pf_at
            else:
                rid, off = next(iter(queued - {r.rid for r in b.queue})), 0
            flops += self.chunk_flops(
                hf, off, t, head=off + t == self.prompt_len[rid])
        made = {st.req.rid: len(st.generated) for st in b.active.values()}
        for c in b.completed[self.n_done:]:
            made[c.rid] = len(c.tokens) - c.prompt_len
        self.n_done = len(b.completed)
        for rid, n in made.items():
            old = self.known.get(rid, 0)
            if n <= old:
                continue
            self.win.stamp(rid, end, n - old)
            for j in range(max(old, 1), n):     # token 0 comes from prefill
                decoded += 1
                flops += self.chunk_flops(
                    hf, self.prompt_len[rid] + j - 1, 1, head=True)
            self.known[rid] = n
        self.win.ticks.append(Tick(start, end, decoded, t, flops))


def warm_up(eng, vocab: int) -> None:
    """Serve one request whose prompt of ``2 * budget - 1`` tokens takes
    one chunk of every power of two up to the budget, then two decode
    steps and an eviction: every program and host path the window runs,
    compiled or loaded from the cache."""
    from repro.serve.batcher import ContinuousBatcher, Request

    budget = eng.scfg.prefill_chunk
    b = ContinuousBatcher(eng)
    b.submit(Request(rid=-1, prompt=np.arange(2 * budget - 1, dtype=np.int32)
                     % vocab, max_new_tokens=3))
    while b.busy:
        b.tick()


def fill(eng, runner, planned: list) -> None:
    """Prefill each planned slot's context and done tokens in chunks, and
    hand it to the batcher, one tick after each (a tick puts it in a slot,
    so no more than one request cache waits beside the slots)."""
    import jax.numpy as jnp

    budget = eng.scfg.prefill_chunk
    for p in planned:
        ctx = np.concatenate([p.prompt, p.done[:-1]]).astype(np.int32)
        rc, off = eng.new_request_cache(), 0
        while off < len(ctx):
            t = _pow2_chunk(len(ctx) - off, budget)
            _, rc = eng.prefill_chunk(rc, jnp.asarray(ctx[None, off:off + t]))
            off += t
        runner.adopt(p, rc)
        del rc
        runner.tick()


def serve_window(runner, traffic, seconds: float, trace_dir=None) -> None:
    """The measured window: arrivals on their schedule (or the whole
    backlog at once), one tick whenever the batcher has work.  After the
    window an open loop keeps going until every arrival of the window has
    its first token, or ``GRACE_S`` have passed."""
    import jax

    from jax.profiler import TraceAnnotation

    b, win = runner.b, runner.win
    sched = sorted(traffic.arrivals, key=lambda r: (r.arrival_s, r.rid))
    t_on = min(TRACE_OFFSET_S, max(seconds - TRACE_SPAN_S, 0.0))
    t_off = t_on + TRACE_SPAN_S
    tracing = False
    i = 0
    runner.open_window()
    while True:
        now = runner.now()
        if trace_dir is not None and not tracing and t_on <= now < t_off:
            jax.profiler.start_trace(str(trace_dir))
            tracing = True
        if tracing and now >= t_off:
            jax.profiler.stop_trace()
            tracing, trace_dir = False, None
        if now >= seconds:
            waiting = traffic.open_loop and win.failed() > 0
            if not waiting or now >= seconds + GRACE_S:
                break
        while i < len(sched) and (not traffic.open_loop
                                  or sched[i].arrival_s <= now):
            runner.submit(sched[i], traffic.open_loop)
            i += 1
        if b.busy:
            with TraceAnnotation("bench.tick"):
                runner.tick()
        else:
            nxt = sched[i].arrival_s if i < len(sched) else math.inf
            wait = max(0.0, min(nxt, seconds if now < seconds else nxt)
                       - runner.now())
            with TraceAnnotation("bench.idle"):
                runner.sleep(min(wait, 0.05))
    if tracing:
        jax.profiler.stop_trace()


def sample(completions, runner, seed: int, probe=None) -> list[tuple]:
    """Finished requests to check: the longest, then others in an order
    drawn from the seed, until ``SAMPLE_TOKENS`` served tokens or
    ``SAMPLE_REQUESTS`` requests.  Each is ``(tokens, first, at)``: the
    whole sequence, the index of its first served token, and the
    program's logits at the probe's entries for each served token (None
    without a probe)."""
    done = [c for c in completions
            if c.rid in runner.first_served
            and len(c.tokens) > runner.first_served[c.rid]]
    if not done:
        return []
    longest = max(done, key=lambda c: (len(c.tokens), c.rid))
    rest = [c for c in done if c is not longest]
    order = np.random.default_rng(int(seed) + 1).permutation(len(rest))
    picked, served = [], 0
    for c in [longest] + [rest[k] for k in order]:
        if served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_REQUESTS:
            break
        first = runner.first_served[c.rid]
        n = len(c.tokens) - first
        at = None if probe is None else probe.at(
            c.rid, first - runner.prompt_len[c.rid], n)
        picked.append((np.asarray(c.tokens, np.int32), first, at))
        served += n
    return picked


def load_limits(cell: str) -> dict:
    path = BENCH / "limits" / f"{cell}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no limits for {cell}: {path}")
    return json.loads(path.read_text())


def token_errors(at, ref_at) -> np.ndarray:
    """Each token's logit error relative to the reference, over the
    probe's entries."""
    return np.sqrt(np.sum((at - ref_at) ** 2, -1) / np.sum(ref_at ** 2, -1))


def judge(gaps, ats, ref_ats, limits: dict) -> tuple[bool, dict]:
    """The numbers compared, each beside its limit, for whatever stands in
    the program's place: ``gaps`` (per sequence) of the tokens it served
    below the reference's best, and its logits ``ats`` at the probe's
    entries beside the reference's ``ref_ats``.

    * ``logit_gap``: the widest of the gaps;
    * ``logit_err``: the median over every checked token of the error of
      its logits relative to the reference's, ``|a - r| / |r|`` over the
      entries (a median, since bfloat16 computes the odd request a few
      times worse than the rest: see ``PERF.md``);
    * ``tokens_checked``: served tokens compared (at least ``min_tokens``).
    """
    served = int(sum(len(g) for g in gaps))
    widest = max(float(np.max(g)) for g in gaps) if served else None
    err = None
    if served and all(a is not None for a in ats):
        err = float(np.median(np.concatenate([token_errors(a, r) for a, r in
                                              zip(ats, ref_ats)])))
    checks = {"logit_gap": {"value": widest, "limit": limits["logit_gap"]},
              "logit_err": {"value": err, "limit": limits["logit_err"]},
              "tokens_checked": {"value": served,
                                 "limit": limits["min_tokens"]}}
    ok = (widest is not None and widest <= limits["logit_gap"]
          and err is not None and err <= limits["logit_err"]
          and served >= limits["min_tokens"])
    return ok, checks


def check(hf: dict, seed: int, seqs, limits: dict, controls=(),
          keep=None) -> dict:
    """The plain reference over each sampled sequence, judged for the
    program and for each control of ``controls`` put in its place.
    Returns ``{"program" or control: (ok, checks)}``; ``keep`` (a dict)
    receives the reference's readings, its own under ``"reference"``."""
    ref = families.reference(hf)
    if not seqs:
        return {k: judge([], [], [], limits)
                for k in ("program",) + tuple(controls)}
    vocab = families.adapter(hf).model_config(hf).vocab_size
    idx = probe_entries(seed, vocab)
    out = ref.served_gaps(hf, seed, [(t, f) for t, f, _ in seqs], idx,
                          controls=controls)
    if keep is not None:
        keep.update(out)
    ref_ats = [a for _, a in out["reference"]]
    res = {"program": judge([g for g, _ in out["reference"]],
                            [a for _, _, a in seqs], ref_ats, limits)}
    for m in controls:
        res[m] = judge([g for g, _ in out[m]], [a for _, a in out[m]],
                       ref_ats, limits)
    return res


def build_program(hf: dict, seed: int, trace: bool, split: dict):
    """The engine with the seed's weights, every shape warmed up."""
    import jax

    from bench import model
    from repro.launch.mesh import make_local_mesh

    cfg = model.model_config(hf)
    eng = model.build_engine(seed, hf, cfg, make_local_mesh(), split)
    if trace:
        _annotate(eng)
    t = time.perf_counter()
    warm_up(eng, cfg.vocab_size)
    jax.block_until_ready(eng.slot_cache)
    split["warm_up_s"] = time.perf_counter() - t
    return eng


def measure(eng, hf: dict, mix: dict, seed: int, seconds: float, *,
            trace: bool = False, split: dict, t_start: float,
            clock=time.perf_counter):
    """Fill the slots, serve the window, reduce the trace.  Returns the
    window's records, the trace, the runner and the set-up time.  The
    window runs on ``clock`` (tests give one that counts calls)."""
    import jax

    from bench import traffic as T, window as Wn
    from repro.serve.batcher import ContinuousBatcher

    slots = eng.scfg.slots
    plan = T.generate(mix, seed=seed, seconds=seconds, slots=slots,
                      vocab=eng.cfg.vocab_size)
    win = Wn.Window(seconds=seconds, slots=slots)
    runner = Runner(ContinuousBatcher(eng, clock=clock), hf, win, clock)
    probe = Probe(eng, runner.b, probe_entries(seed, eng.cfg.vocab_size))
    t = time.perf_counter()
    probe.warm()
    probe.install()
    try:
        fill(eng, runner, plan.prefilled)
        jax.block_until_ready(eng.slot_cache)
        split["fill_s"] = time.perf_counter() - t
        setup_s = time.perf_counter() - t_start
        out = _window(runner, plan, seconds, trace)
    finally:
        probe.remove()
    out.update(runner=runner, probe=probe, setup_s=setup_s)
    return out


def _window(runner, plan, seconds: float, trace: bool) -> dict:
    """Serve the window, counting compiles inside it; with ``trace``, the
    profiler's record of part of it, reduced."""
    import jax

    compiles = []

    def on_event(event, *a, **k):
        if event.startswith("/jax/core/compile/"):
            compiles.append(event)

    pauses, began = runner.win.gc_pauses, []

    def on_gc(phase, info):
        """Python's collections inside the window: start, length, gen."""
        if phase == "start":
            began[:] = [runner.now()]
        elif began:
            pauses.append((began[0], runner.now() - began[0],
                           info["generation"]))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    gc.callbacks.append(on_gc)
    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
    reduced = None
    try:
        try:
            serve_window(runner, plan, seconds, tmp)
        finally:
            gc.callbacks.remove(on_gc)
            jax.monitoring.unregister_event_duration_listener(on_event)
        runner.win.compiles = len(compiles)
        if trace:
            from bench import trace_reduce

            reduced = trace_reduce.read(tmp)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    return {"win": runner.win, "trace": reduced}


def memory(eng) -> dict:
    """Device memory after the window, as JAX reports it, and the
    temporaries that the compiled decode step reserves beside what is in
    use (its compiled memory analysis, found in the compile cache)."""
    import jax.numpy as jnp

    stats = eng.mesh.devices.flat[0].memory_stats() or {}
    out = {k: stats[k] for k in ("peak_bytes_in_use", "bytes_limit",
                                 "bytes_reserved", "peak_bytes_reserved")
           if k in stats}
    step = getattr(eng, "_step_slots", None)
    if step is not None and eng.slot_cache is not None:
        tokens = jnp.zeros((eng.scfg.slots,), jnp.int32)
        analysis = step.lower(eng.params, tokens,
                              eng.slot_cache).compile().memory_analysis()
        if analysis is not None:
            out["step_temp_bytes"] = int(analysis.temp_size_in_bytes)
    return out


def run_program(hf: dict, mix: dict, seed: int, seconds: float,
                trace: bool, t_start: float, split: dict, **kw) -> dict:
    """Set-up and window.  Returns what the check and the metrics need;
    the engine and its device state are gone when it returns."""
    eng = build_program(hf, seed, trace, split)
    out = measure(eng, hf, mix, seed, seconds, trace=trace, split=split,
                  t_start=t_start, **kw)
    t = time.perf_counter()
    out["memory"] = memory(eng)
    out["memory"]["read_s"] = time.perf_counter() - t
    runner, probe = out.pop("runner"), out.pop("probe")
    out["seqs"] = sample(list(runner.b.completed), runner, seed, probe)
    del runner, probe, eng
    gc.collect()
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, chips_check=require_chips, overrides=None,
            clock=time.perf_counter) -> dict:
    """One run of ``workload``; returns the result line as a dict.
    ``overrides`` may replace the cell's ``config``, ``mix`` or ``limits``
    (the tests run a tiny configuration on the CPU)."""
    from bench import model, traffic as T

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        sys.exit(f"bench: no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    metrics = cell_metrics(bench, workload, trace)
    dev = chips_check(cell["chips"])
    cache = use_compile_cache()
    overrides = overrides or {}
    hf = overrides.get("config") or model.load_config(cell["config"])
    mix = overrides.get("mix") or T.load_mix(cell["traffic"])
    limits = overrides.get("limits") or load_limits(workload)
    split: dict = {"start_s": time.perf_counter() - t_start}
    out = run_program(hf, mix, seed, seconds, trace, t_start, split,
                      clock=clock)
    win = out["win"]
    log(f"device {dev.platform} {dev.device_kind}; compile cache {cache}")
    log("set-up split (s): " + json.dumps(split))
    log("window samples: " + json.dumps(win.samples()))
    log("memory (B; read_s in s): " + json.dumps(out["memory"]))

    from bench import metrics as M

    values = {}
    if trace:
        run = M.RunData(win=win, trace=out["trace"], chips=cell["chips"],
                        device_kind=dev.device_kind)
        for m in metrics:
            v = M.read(m["name"], run)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = win.end_to_end()
        e2e["setup_s"] = out["setup_s"]
        for m in metrics:
            values[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t = time.perf_counter()
    correct, checks = check(hf, seed, out["seqs"], limits)["program"]
    log(f"reference check: {len(out['seqs'])} requests in "
        f"{time.perf_counter() - t} s")
    mem = out["memory"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": mem.get("peak_bytes_in_use"),
              "memory_step_temp_bytes": mem.get("step_temp_bytes")}
    result = {"correct": bool(correct),
              "attempted": win.served() + win.failed(),
              "failed": win.failed(), "metrics": values, "device": device}
    if trace:
        tr = out["trace"]
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.device_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["checks"] = checks
    return result
