"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's own arithmetic: a kernel's roofline share and
the whole step's model FLOP utilization divide them by measured time.
Only the work the answer needs counts: a weight is read once per call,
attention covers each token's real context (never the ring's empty or
masked slots), and the output head runs once per produced token.
"""

from __future__ import annotations

from bench import weights as W

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


def layer_matmul_flops(hf: dict) -> float:
    """Matmul FLOPs of one decoder layer for one token (projections, MLP)."""
    z = W.dims(hf)
    d, h, kv, hd, ff = z["d"], z["h"], z["kv"], z["hd"], z["ff"]
    params = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return 2.0 * params


def attention_flops(hf: dict, context: int) -> float:
    """Score and value FLOPs of one token attending to ``context`` keys in
    one layer, the context cut to the sliding window where there is one."""
    z = W.dims(hf)
    if W.window(hf):
        context = min(context, W.window(hf))
    return 2.0 * 2.0 * z["h"] * z["hd"] * context


def head_flops(hf: dict) -> float:
    z = W.dims(hf)
    return 2.0 * z["d"] * z["v"]


def chunk_flops(hf: dict, start: int, tokens: int, *, head: bool) -> float:
    """Useful FLOPs of the tokens at 0-based positions ``[start, start +
    tokens)`` through every layer (position ``p`` attends to ``p + 1``
    keys), plus the output head once where ``head``: for a decode token,
    or for the last token of a prompt."""
    per_layer = sum(layer_matmul_flops(hf) + attention_flops(hf, p + 1)
                    for p in range(start, start + tokens))
    return (W.dims(hf)["layers"] * per_layer
            + (head_flops(hf) if head else 0.0))
