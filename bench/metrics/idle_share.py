"""Device: share of the stretches in which the batcher had work (the traced
window less the harness's ``bench.idle`` spans) with no operation running
on the chip, in percent, from the trace."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share
