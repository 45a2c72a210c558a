"""Scheduler: mean share of the slots that decoded a token in a tick, over
the window's ticks, in percent.  Counted by the harness's loop."""


def read(run):
    occ = run.win.occupancy()
    return None if occ != occ else 100.0 * occ
