"""The system under test, built from a configuration file.

This is the one place where the benchmark speaks the program's language:
the published config keys become a ``repro`` ``ModelConfig``, the seeded
weights of ``bench/weights.py`` become the program's parameter tree, and
the engine is the program's own ``ServeEngine`` with the serving knobs the
program's planner picks (``elk_serve_config``) for a one-chip mesh.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import weights as W

BENCH = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    """``bench/configs/<name>.json``: published keys, cuts and serving sizes."""
    path = BENCH / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration file {path}")
    return json.loads(path.read_text())


def model_config(hf: dict):
    """The program's ``ModelConfig`` with every published size of ``hf``."""
    from repro.models.config import ModelConfig

    if hf.get("architectures") not in (["Qwen3ForCausalLM"],
                                       ["MistralForCausalLM"],
                                       ["LlamaForCausalLM"]):
        raise ValueError(f"no program mapping for {hf.get('architectures')}")
    if hf["hidden_act"] != "silu" or hf.get("tie_word_embeddings"):
        raise ValueError("only untied SwiGLU decoders are mapped")
    z = W.dims(hf)
    window = W.window(hf)
    return ModelConfig(
        name=hf["model_type"], family="dense", num_layers=z["layers"],
        d_model=z["d"], num_heads=z["h"], num_kv_heads=z["kv"],
        d_ff=z["ff"], vocab_size=z["v"], head_dim=z["hd"],
        qk_norm=W.qk_norm(hf), gated_mlp=True, mlp_act="silu",
        norm_eps=float(hf["rms_norm_eps"]),
        rope_theta=float(hf["rope_theta"]),
        sliding_window=window, swa_layers="all" if window else "none",
        param_dtype="bfloat16")


def program_params(base: jax.Array, hf: dict, cfg):
    """The program's parameter tree holding ``bench/weights.py``'s draws
    for the key ``base``.  Traceable, so that it runs on the device."""
    from repro.models import transformer as tfm

    outer = W.outer_weights(base, hf)
    layers = jax.vmap(lambda i: W.layer_weights(base, i, hf))(
        jnp.arange(cfg.num_layers))

    def attn(w):
        a = {"w_q": w["q"], "w_k": w["k"], "w_v": w["v"], "w_o": w["o"]}
        if "q_norm" in w:
            a["q_norm"], a["k_norm"] = w["q_norm"], w["k_norm"]
        return a

    params = {
        "embed": outer["embed"], "final_norm": outer["final_norm"],
        "lm_head": outer["lm_head"], "prefix": [],
        "blocks": [{"ln1": layers["attn_norm"], "ln2": layers["mlp_norm"],
                    "attn": attn(layers),
                    "mlp": {"w_gate": layers["gate"], "w_up": layers["up"],
                            "w_down": layers["down"]}}],
    }
    want = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(params) or got != \
            jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise ValueError("the program's parameter layout changed; "
                         "bench/model.py no longer matches it")
    return params


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
