"""Model families: what the benchmark knows of one architecture.

A configuration file names its family (``"family": "dense_gqa"``), and that
one name selects both halves of the comparison, each found by name:

* ``bench/families/<family>.py``, the program adapter: the only code that
  speaks the program's language for this architecture;
* ``bench/references/<family>.py``, the plain reference, which imports
  nothing of the program and nothing of the adapter.

No list of families is kept anywhere: a new family is new files and
nothing else.  The generic code (``bench/model.py``, ``bench/harness.py``,
``bench/counts.py``) reads no published config key and calls an adapter
only through these three functions, each given the configuration file's
dict ``hf``:

* ``model_config(hf)``: the program's ``ModelConfig`` with every published
  size of ``hf``; it raises ``ValueError`` for what it cannot map;
* ``program_params(base, hf, cfg)``: the program's parameter tree for the
  seed key ``base``, traceable (one compiled call on the device, the key
  an argument), drawn with ``bench.weights.draw`` so that the reference
  can draw each tensor again bit for bit; it compares its tree with
  ``jax.eval_shape`` of the program's own ``init_params`` and raises
  ``ValueError`` where the program's layout has changed;
* ``chunk_flops(hf, start, tokens, *, head)``: the useful FLOPs of the
  tokens at 0-based positions ``[start, start + tokens)`` of one sequence
  through every layer, plus the output head once where ``head`` (a
  decode token, or a prompt's last token).  Only the work the answer
  needs counts: each weight once per token, attention over each token's
  real context (never the ring's empty or masked slots, and only the
  experts a token is routed to).  ``mfu`` reads these.

The reference gives ``served_gaps(hf, seed, seqs, idx, *, controls=())``
as ``bench/references/dense_gqa.py`` documents it.

A ``model_config`` change that brings a new architecture adds, and edits
nothing:

1. ``bench/configs/<config>.json``: the published config, ``"family"``,
   ``"reduced"`` (each cut with its published value and why),
   ``"serving"`` (``slots``, ``ring_tokens``, ``prefill_chunk``: what
   ``elk_serve_config`` picks for one chip) and ``"assumed"``;
2. ``bench/families/<family>.py``, the adapter above;
3. ``bench/references/<family>.py``, the reference, with its controls;
4. the family's own draws, where ``bench/weights.py`` lacks them, in a
   module that the adapter and the reference both import;
5. ``bench/traffic/<mix>.json`` for a new mix, and
   ``bench/limits/<cell>.json`` for each cell, set from the readings of the
   program and of the control on the chip;
6. a test of the family at a CPU size under ``bench/tests`` (as
   ``test_bench_families.py`` runs its toy MoE family);
7. in ``BENCHMARK.json``: an entry in ``configs``, its cells in
   ``workloads``, and per-layer metrics whose ``workloads`` name them.
"""

from __future__ import annotations

import importlib


def adapter(hf: dict):
    """The program adapter of ``hf``'s family."""
    return importlib.import_module(f"bench.families.{hf['family']}")


def reference(hf: dict):
    """The plain reference of ``hf``'s family."""
    return importlib.import_module(f"bench.references.{hf['family']}")
