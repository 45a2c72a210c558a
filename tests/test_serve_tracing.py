"""What the serving path records about itself: the batcher's per-tick
record, its ``serve.*`` spans in a profiler trace, and the
names and scopes of the engine's compiled slot programs."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serve.batcher import ContinuousBatcher, Request, TickRecord
from repro.serve.engine import ServeConfig, ServeEngine

SCOPES = ("layer", "attn", "mlp", "kv_ring", "head")


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("qwen3_14b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    from repro.launch.mesh import make_local_mesh
    return ServeEngine(cfg, make_local_mesh(), params,
                       ServeConfig(batch=2, cache_capacity=64,
                                   prefill_chunk=8))


def _requests(vocab):
    rng = np.random.default_rng(0)
    lens, news = (5, 12, 3, 9, 17), (4, 1, 6, 0, 3)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(zip(lens,
                                                                     news))]


class _Watch:
    """Wraps the engine's slot calls to note what each tick asked of it."""

    def __init__(self, eng, batcher):
        self.chunk, self.active, self.stepped = 0, 0, False
        step, chunk = eng.step, eng.prefill_chunk

        def stepped(tokens):
            self.active, self.stepped = len(batcher.active), True
            return step(tokens)

        def chunked(cache, tokens):
            self.chunk = tokens.shape[1]
            return chunk(cache, tokens)
        eng.step, eng.prefill_chunk = stepped, chunked

    def reset(self):
        self.chunk, self.active, self.stepped = 0, 0, False


def test_tick_record(engine):
    b = ContinuousBatcher(engine)
    assert b.last_tick == TickRecord() and b.tick_decoded is False
    watch = _Watch(engine, b)
    reqs = _requests(engine.cfg.vocab_size)
    for r in reqs:
        b.submit(r)
    records = []
    try:
        while b.busy:
            watch.reset()
            b.tick()
            t = b.last_tick
            records.append(t)
            assert 0 <= t.readback_s <= t.end_s - t.start_s
            assert t.host_s == pytest.approx(t.end_s - t.start_s
                                             - t.readback_s)
            assert t.decoded == watch.active      # len(active) at decode
            # the counters the fleet prices a tick from read as before
            assert b.tick_prefill_tokens == watch.chunk
            assert b.tick_decoded is watch.stepped
    finally:
        engine.__dict__.pop("step")
        engine.__dict__.pop("prefill_chunk")
    assert len(records) == b.ticks
    assert all(a.end_s <= z.start_s for a, z in zip(records, records[1:]))
    assert sum(t.prefill_tokens for t in records) == sum(
        len(r.prompt) for r in reqs if r.max_new_tokens > 0)
    assert sum(t.decoded for t in records) > 0
    with pytest.raises(AttributeError):
        b.tick_decoded = True


def test_virtual_clock_ticks_take_no_time(engine):
    """On a clock that only its owner moves, a tick and its readbacks
    last nothing, whatever the host did meanwhile."""
    from repro.serve.fleet import VirtualClock

    b = ContinuousBatcher(engine, VirtualClock(5.0))
    b.submit(_requests(engine.cfg.vocab_size)[0])
    while b.busy:
        b.tick()
        assert b.last_tick.start_s == b.last_tick.end_s == 0.0
        assert b.last_tick.readback_s == 0.0


def _host_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    return [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith("serve.")]


def test_spans_in_a_profiler_trace(engine, tmp_path):
    b = ContinuousBatcher(engine)
    reqs = _requests(engine.cfg.vocab_size)
    cache = engine.new_request_cache()
    tok, cache = engine.prefill_chunk(cache,
                                      jnp.asarray(reqs[0].prompt[None]))
    state = jax.tree.map(np.array, cache)
    b.adopt(reqs[0], state, [int(tok[0])], 0.0)     # refilled by a tick
    for r in reqs[1:]:
        b.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        while b.busy:
            b.tick()
    spans = _host_spans(tmp_path)
    ticks = [e for e in spans if e.name == "serve.tick"]
    assert [dict(e.stats)["step_num"] for e in ticks] == list(range(b.ticks))
    children = [e for e in spans if e.name != "serve.tick"]
    assert {e.name for e in children} == {
        "serve.refill", "serve.admit", "serve.prefill", "serve.decode",
        "serve.sync", "serve.finish"}
    for e in children:              # every phase inside one tick's span
        assert any(t.start_ns <= e.start_ns and e.end_ns <= t.end_ns
                   for t in ticks), e.name
    # one sync per decode step, one more per prompt's last chunk
    n_sync = sum(e.name == "serve.sync" for e in children)
    n_decode = sum(e.name == "serve.decode" for e in children)
    assert n_sync == n_decode + sum(r.max_new_tokens > 0 for r in reqs[1:])


def test_slot_programs_are_named_and_scoped(engine):
    """``jit_step`` and ``jit_chunk`` are the names a trace finds the
    programs by; their ops carry the model's scopes in their metadata."""
    engine._ensure_slots()
    tokens = jnp.zeros((engine.scfg.slots,), jnp.int32)
    step = engine._step_slots.lower(engine.params, tokens,
                                    engine.slot_cache).compile().as_text()
    engine.prefill_chunk(engine.new_request_cache(),
                         jnp.zeros((1, 4), jnp.int32))
    chunk = engine._chunk_jits[4].lower(
        engine.params, jnp.zeros((1, 4), jnp.int32),
        engine.new_request_cache()).compile().as_text()
    for text, name in ((step, "jit_step"), (chunk, "jit_chunk")):
        assert re.match(rf"HloModule {name}\b", text)
        op_names = re.findall(r'op_name="([^"]*)"', text)
        for scope in SCOPES:
            assert any(f"/{scope}/" in n for n in op_names), (name, scope)
    # the ring write inside attention is kv_ring, within attn and layer
    assert any("/layer/attn/kv_ring/" in n
               for n in re.findall(r'op_name="([^"]*)"', step))
