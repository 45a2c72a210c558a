"""Whole step: useful model FLOPs of the window's ticks (``bench/counts``:
each decoded token and each prompt token through every layer, attention
over its real context only, the output head once per produced token) over
the window's length times the chips' published bf16 peak, in percent."""

from bench.peaks import peaks


def read(run):
    flops = run.win.model_flops()
    if flops <= 0:
        return None
    peak = peaks(run.device_kind)["bf16_flops"] * run.chips
    return 100.0 * flops / (run.win.seconds * peak)
