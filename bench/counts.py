"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's own arithmetic: a kernel's roofline share
divides them by measured time.  Only the work the answer needs counts: a
weight is read once per call.  The whole step's useful FLOPs, which
``mfu`` reads, depend on the architecture and are each family adapter's
``chunk_flops`` (``bench/families``).
"""

from __future__ import annotations

BF16 = 2


def fused_mlp(m: int, d: int, ff: int, *, gated: bool = True,
              staged: frozenset = frozenset(),
              itemsize: int = BF16) -> tuple[float, float]:
    """(flops, HBM bytes) of one fused-MLP call on ``m`` rows:
    ``act(x @ w_gate) * (x @ w_up) @ w_down``.  Bytes: each weight once,
    the input rows once and the output rows once, less the operands in
    ``staged`` (of ``x``, ``w_up``, ``w_gate``, ``w_down``, ``out``): those
    the compiler placed in on-chip memory, where an earlier operation
    moved them."""
    sizes = {"x": m * d, "w_up": d * ff, "w_down": ff * d, "out": m * d}
    if gated:
        sizes["w_gate"] = d * ff
    flops = 2.0 * m * d * ff * (2 if gated else 1) + 2.0 * m * ff * d
    nbytes = itemsize * sum(n for k, n in sizes.items() if k not in staged)
    return flops, float(nbytes)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bytes_per_s: float) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
