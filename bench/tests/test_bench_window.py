"""End-to-end arithmetic: percentiles over all requests or gaps, rates
over the whole window."""

import math

from bench.window import Tick, Window


def _win():
    w = Window(seconds=10.0, slots=4)
    w.arrival = {1: 0.5, 2: 3.0, 3: 9.5, 4: 12.0}
    w.stamp(1, 1.0)
    w.stamp(1, 1.1)
    w.stamp(1, 1.3)
    w.stamp(2, 4.0)
    w.stamp(2, 4.5)
    w.stamp(3, 11.0)          # first token after the window closed
    w.stamp(5, -0.2)          # a prefilled slot: set-up, then the window
    w.stamp(5, 0.3)
    w.stamp(5, 0.6)
    w.admitted = {1: 0.7, 2: 3.2, 3: 10.5}
    w.ticks = [Tick(0.0, 0.3, 2, 0, 5.0), Tick(0.3, 0.6, 4, 16, 7.0),
               Tick(9.9, 10.4, 1, 0, 100.0)]
    return w


def test_ttft_counts_every_window_arrival_even_late():
    w = _win()
    assert sorted(w.ttfts()) == [0.5, 1.0, 1.5]   # rid 4 arrives after
    assert w.failed() == 0
    w.arrival[6] = 5.0
    assert w.failed() == 1


def test_gaps_inside_the_window_only():
    w = _win()
    got = sorted(round(g, 6) for g in w.gaps())
    assert got == [0.1, 0.2, 0.3, 0.5]


def test_rate_over_the_whole_window():
    w = _win()
    assert w.tokens_inside() == 7
    assert w.end_to_end()["gen_tok_s"] == 0.7


def test_percentiles_and_occupancy():
    w = _win()
    e = w.end_to_end()
    assert math.isclose(e["ttft_p50_s"], 1.0)
    assert math.isclose(e["itl_p95_ms"], 470.0)
    assert [round(q, 9) for q in sorted(w.queue_waits())] == [0.2, 0.2, 1.0]
    assert math.isclose(w.occupancy(), 3 / 4)      # ticks ending inside
    assert w.model_flops() == 12.0
    assert w.samples()["ttft_requests"] == 3


def test_fill_before_the_window_counts_in_no_metric():
    """Ticks and tokens of filling the slots, recorded before the window
    opens, fall before its start once it opens."""
    from types import SimpleNamespace

    from bench.harness import Runner

    now = [0.0]
    w = Window(seconds=10.0, slots=2)
    r = Runner(SimpleNamespace(t0=0.0), {}, w, clock=lambda: now[0])
    now[0] = 2.0
    w.stamp(7, r.now())
    w.ticks.append(Tick(1.5, r.now(), 1, 0, 3.0))
    now[0] = 5.0
    r.open_window()
    now[0] = 5.5
    w.stamp(7, r.now())
    w.ticks.append(Tick(0.1, r.now(), 1, 0, 3.0))
    assert w.stamps[7] == [-3.0, 0.5]
    assert w.tokens_inside() == 1
    assert w.gaps() == []
    assert len(w.window_ticks()) == 1
    assert w.model_flops() == 3.0
