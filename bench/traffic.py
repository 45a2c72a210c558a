"""One seeded generator for every traffic mix.

A mix is a data file, ``bench/traffic/<mix>.json``:

* ``prompt``, ``output``: length distributions (``lognormal`` with a
  ``median`` and ``sigma``), clipped to ``[min, max]``;
* ``max_total``: optional cap on prompt + output, which the generator
  keeps by shortening the prompt;
* ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` for an open loop,
  or ``{"kind": "backlog", "requests": n}`` for a queue that is full from
  the start;
* ``prefilled_slots``: how many slots hold a request when the window
  opens (``"all"`` or a number).  Each is drawn from the same lengths and
  is a uniform share of the way through its output; that share of the
  output is part of what set-up prefills.

Every seed gets the same work in the same order: lengths, gaps and shares
are quantiles of their distributions, laid out in one stratified order
(``ORDER_SEED``) in which any stretch of the schedule holds a near-equal
mix.  The seed draws the token ids, uniform over the vocabulary (and,
elsewhere, the weights), so two runs differ only in what the chip computes
and not in how much.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

BENCH = Path(__file__).resolve().parent
BLOCK = 8           # requests per block of the stratified order
ORDER_SEED = 0      # the order of lengths, gaps and shares, for every seed


@dataclasses.dataclass
class Planned:
    """One request of a run.  ``done`` output tokens (for a prefilled slot)
    are already part of the context when the window opens."""
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int                  # output tokens in all
    arrival_s: float = 0.0        # from the window's start
    done: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))


@dataclasses.dataclass
class Traffic:
    prefilled: list[Planned]
    arrivals: list[Planned]       # in arrival order
    open_loop: bool               # arrivals on a schedule (TTFT is judged)


def load_mix(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {path}")
    return json.loads(path.read_text())


def _inv_normal(p: np.ndarray) -> np.ndarray:
    """Standard normal quantile at each of ``p``."""
    return np.array([NormalDist().inv_cdf(float(x)) for x in np.ravel(p)])


def stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` probabilities ``(i + 0.5) / n``, in a seeded order that
    spreads every block of ``BLOCK`` consecutive entries over all of
    (0, 1): the sorted points fall into ``BLOCK`` strata, and block ``b``
    takes one point of each stratum (the ``b``-th of a shuffle), in a
    shuffled order."""
    strata = [rng.permutation(idx) for idx in np.array_split(
        np.arange(n), BLOCK)]
    order = []
    for b in range(math.ceil(n / BLOCK)):
        block = [s[b] for s in strata if b < len(s)]
        order.extend(rng.permutation(block))
    return (np.asarray(order, float) + 0.5) / n


def lengths(spec: dict, p: np.ndarray) -> np.ndarray:
    """Lengths at probabilities ``p`` of the spec's clipped distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * np.exp(spec["sigma"] * _inv_normal(p))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def _requests(mix, n, order, rng, vocab, rid0):
    prompt = lengths(mix["prompt"], stratified(n, order))
    out = lengths(mix["output"], stratified(n, order))
    cap = mix.get("max_total")
    if cap:
        prompt = np.minimum(prompt, cap - out)
    return [Planned(rid=rid0 + i, max_new=int(out[i]),
                    prompt=rng.integers(0, vocab, int(prompt[i]),
                                        dtype=np.int32))
            for i in range(n)]


def generate(mix: dict, *, seed: int, seconds: float, slots: int,
             vocab: int) -> Traffic:
    """The run's requests: the prefilled slots, then the window's arrivals
    (enough of them to cover ``seconds`` and a grace after it)."""
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(int(seed))
    n_pre = slots if mix.get("prefilled_slots") == "all" else \
        min(int(mix.get("prefilled_slots", 0)), slots)
    pre = _requests(mix, n_pre, order, rng, vocab, 0)
    for r, share in zip(pre, stratified(n_pre, order)):
        # at least one output token is done: it is the next one fed
        k = min(max(1, int(share * r.max_new)), r.max_new - 1)
        r.done = rng.integers(0, vocab, k, dtype=np.int32)
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        rate = float(arr["rate_per_s"])
        n = math.ceil(rate * (seconds + 60.0))
        reqs = _requests(mix, n, order, rng, vocab, n_pre)
        gaps = -np.log1p(-stratified(n, order)) / rate
        for r, t in zip(reqs, np.cumsum(gaps)):
            r.arrival_s = float(t)
        return Traffic(pre, reqs, open_loop=True)
    if arr["kind"] == "backlog":
        reqs = _requests(mix, int(arr["requests"]), order, rng, vocab,
                        n_pre)
        return Traffic(pre, reqs, open_loop=False)
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")
