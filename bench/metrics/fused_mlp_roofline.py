"""Kernels: the fused-MLP Pallas kernel's share of its roofline inside the
slot decode step, in percent.  For every call in the trace (ops named
``fused_mlp_kernel`` within ``jit_step``), the least time the chip could
take, from the call's operand shapes (``bench/counts.fused_mlp``) at the
published peaks, summed, over the summed device time of those calls.

An operand whose layout puts it in memory space 1 (``S(1)``, on-chip
VMEM) was staged there by an earlier operation, which is charged its
bytes; the kernel's own bound counts only what it moves from HBM."""

import re

from bench import counts
from bench.peaks import peaks

# "bf16[8,2560]{1,0:T(8,128)(2,1)S(1)}": dims, then the layout's tail
ARRAY = re.compile(r"\w+\[(\d+(?:,\d+)*)\](\{[^}]*\})?")


def _arrays(text: str):
    return [(tuple(int(n) for n in m.group(1).split(",")),
             "S(1)" in (m.group(2) or "")) for m in ARRAY.finditer(text)]


def call_cost(text: str):
    """(flops, bytes) of one call from its HLO text: the result (M, d_out),
    then operands x (M, d), w_up (d, ff), w_gate (d, ff) when gated and
    w_down (ff, d_out)."""
    head, rest = text.split(" custom-call(", 1)
    args = rest.split("), custom_call_target", 1)[0]
    result = _arrays(head)[0]
    mats = [a for a in _arrays(args) if len(a[0]) == 2]
    gated = len(mats) == 4
    names = ["x", "w_up"] + (["w_gate"] if gated else []) + ["w_down"]
    staged = {n for n, (_, vmem) in zip(names, mats) if vmem}
    if result[1]:
        staged.add("out")
    (m, d), (_, ff) = mats[0][0], mats[1][0]
    return counts.fused_mlp(m, d, ff, gated=gated, staged=frozenset(staged))


def read(run):
    if run.trace is None:
        return None
    ops = run.trace.kernel_ops("fused_mlp_kernel", inside="jit_step")
    if not ops:
        return None
    p = peaks(run.device_kind)
    bound = sum(counts.roofline_s(*call_cost(op.text), p["bf16_flops"],
                                  p["hbm_bytes_per_s"]) for op in ops)
    return 100.0 * bound / sum(op.dur for op in ops)
