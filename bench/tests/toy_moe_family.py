"""Program adapter of a toy mixture-of-experts family (tests only).

A decoder whose every layer is GQA attention and the program's MoE FFN
(``repro.models.moe``: top-k routing with softmax gates over the chosen
experts, dropless in the slot step and the prefill chunk).  The test
registers it as ``bench.families.toy_moe``, beside
``toy_moe_reference.py`` as ``bench.references.toy_moe``, to show that a
family enters the harness by files alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W

import toy_moe_weights as TW


def model_config(hf: dict):
    from repro.models.config import ModelConfig

    z = TW.dims(hf)
    return ModelConfig(
        name=hf["model_type"], family="moe", num_layers=z["layers"],
        d_model=z["d"], num_heads=z["h"], num_kv_heads=z["kv"],
        d_ff=z["ff"], vocab_size=z["v"], head_dim=z["hd"],
        gated_mlp=True, mlp_act="silu", norm_eps=float(hf["rms_norm_eps"]),
        rope_theta=float(hf["rope_theta"]), moe_experts=z["e"],
        moe_top_k=z["k"], moe_d_ff=z["ff"], param_dtype="bfloat16")


def program_params(base, hf: dict, cfg):
    from repro.models import transformer as tfm

    outer = W.outer_weights(base, hf)
    w = jax.vmap(lambda i: TW.layer_weights(base, i, hf))(
        jnp.arange(cfg.num_layers))
    params = {
        "embed": outer["embed"], "final_norm": outer["final_norm"],
        "lm_head": outer["lm_head"], "prefix": [],
        "blocks": [{"ln1": w["attn_norm"], "ln2": w["mlp_norm"],
                    "attn": {"w_q": w["q"], "w_k": w["k"], "w_v": w["v"],
                             "w_o": w["o"]},
                    "moe": {"router": w["router"], "w_gate": w["gate"],
                            "w_up": w["up"], "w_down": w["down"]}}],
    }
    want = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != jax.tree.map(
            lambda a: (a.shape, a.dtype), params):
        raise ValueError("the program's parameter layout changed; "
                         "toy_moe_family.py no longer matches it")
    return params


def chunk_flops(hf: dict, start: int, tokens: int, *, head: bool) -> float:
    """Projections, the router and the ``k`` experts a token is routed
    to, attention over its context, and the head once where ``head``."""
    z = TW.dims(hf)
    d, h, kv, hd = z["d"], z["h"], z["kv"], z["hd"]
    mat = 2.0 * (2 * d * h * hd + 2 * d * kv * hd + d * z["e"]
                 + z["k"] * 3 * d * z["ff"])
    attn = sum(4.0 * h * hd * (p + 1) for p in range(start, start + tokens))
    return (z["layers"] * (tokens * mat + attn)
            + (2.0 * d * z["v"] if head else 0.0))
