"""The system under test, built from a configuration file.

What differs from one architecture to the next is the configuration's
family adapter (``bench/families``, found by its ``"family"`` key): it
maps the published config keys to a ``repro`` ``ModelConfig`` and lays
the seeded draws out as the program's parameter tree.  What is left here
is the same for every family: the engine is the program's own
``ServeEngine`` with the serving knobs the program's planner picks
(``elk_serve_config``) for a one-chip mesh, and its weights are made on
the device in one compiled call from the seed.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax

from bench import families
from bench import weights as W

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    """``bench/configs/<name>.json``: published keys, cuts and serving sizes."""
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def model_config(hf: dict):
    """The program's ``ModelConfig`` for ``hf``, from its family adapter."""
    return families.adapter(hf).model_config(hf)


def program_params(base: jax.Array, hf: dict, cfg):
    """The program's parameter tree for the key ``base``, from ``hf``'s
    family adapter.  Traceable, so that it runs on the device."""
    return families.adapter(hf).program_params(base, hf, cfg)


def build_engine(seed: int, hf: dict, cfg, mesh, split: dict | None = None):
    """``ServeEngine`` with seeded weights made on the device in one
    compiled call, straight into the engine's shardings (as the program's
    ``init_sharded`` does, but with the key an argument: one program for
    every seed, found in the compile cache)."""
    from repro.distributed.sharding import param_shardings
    from repro.serve.engine import ServeEngine, elk_serve_config

    split = {} if split is None else split
    t = time.perf_counter()
    serving = hf["serving"]
    scfg = elk_serve_config(cfg, batch=serving["slots"],
                            cache_capacity=serving["ring_tokens"],
                            num_chips=mesh.size, mesh=mesh)
    split["plan_s"] = time.perf_counter() - t
    if scfg.prefill_chunk != serving["prefill_chunk"] or \
            scfg.cache_capacity != serving["ring_tokens"]:
        raise ValueError(f"the planner chose chunk {scfg.prefill_chunk} and "
                         f"ring {scfg.cache_capacity}; the configuration "
                         f"states {serving}")
    t = time.perf_counter()
    base = W.seed_key(seed)
    shapes = jax.eval_shape(lambda k: program_params(k, hf, cfg), base)
    make = jax.jit(lambda k: program_params(k, hf, cfg),
                   out_shardings=param_shardings(
                       shapes, mesh, scfg.mode == "elk_stream")
                   ).lower(base).compile()
    split["weights_program_s"] = time.perf_counter() - t
    t = time.perf_counter()
    params = jax.block_until_ready(make(base))
    split["weights_draw_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = ServeEngine(cfg, mesh, params, scfg)
    del params
    jax.block_until_ready(eng.params)
    split["engine_s"] = time.perf_counter() - t
    return eng


def reseed(eng, seed: int, hf: dict) -> None:
    """Give a built engine the weights of another seed, in place (the old
    ones are freed first: two sets may not fit beside the slots)."""
    import gc

    eng.params = None
    gc.collect()
    eng.params = jax.jit(lambda k: program_params(k, hf, eng.cfg),
                         out_shardings=eng.p_sh)(W.seed_key(seed))
