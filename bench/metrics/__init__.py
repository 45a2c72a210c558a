"""Per-layer metric readers, found by name.

A per-layer metric ``<base>[.<suffix>]`` is read by ``bench/metrics/
<base>.py``'s ``read(run)``; the suffix only says which end-to-end metric
the number moves, so one reader serves every suffix.  A reader that finds
nothing to read returns ``None``, and the harness leaves the metric out.
"""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class RunData:
    """What a reader may read: the window's records, the reduced trace
    (``None`` without one), the chips and their kind."""
    win: object
    trace: object
    chips: int
    device_kind: str


def read(name: str, run: RunData):
    base = name.split(".", 1)[0]
    value = importlib.import_module(f"bench.metrics.{base}").read(run)
    return None if value is None else float(value)
