"""Scheduler: 90th percentile of the time from a request's scheduled
arrival to its admission (the tick at which it left the batcher's queue),
over the window's arrivals.  Host clock."""

from bench.window import percentile


def read(run):
    waits = run.win.queue_waits()
    return percentile(waits, 90) if waits else None
