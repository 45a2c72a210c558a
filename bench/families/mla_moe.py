"""Latent attention with sigmoid-routed experts (DeepSeek-V3 layer
equations: Kimi-K2), at one chip's share of an expert-parallel
deployment.

The published config keys become a ``repro`` ``ModelConfig`` with MLA
sizes, yarn rope and a held share of the router's experts; the seeded
draws of ``bench/weights_mla_moe.py`` become the program's parameter
tree; and the useful FLOPs of a chunk are counted from the published
widths.  The contract is in ``bench/families/__init__.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights_mla_moe as WM


def model_config(hf: dict):
    """The program's ``ModelConfig`` with every published size of ``hf``."""
    from repro.models.config import ModelConfig, Yarn

    if hf.get("model_type") not in ("kimi_k2", "deepseek_v3"):
        raise ValueError(f"no program mapping for {hf.get('model_type')}")
    rs = hf["rope_scaling"]
    if (hf["hidden_act"] != "silu" or hf["tie_word_embeddings"]
            or hf["scoring_func"] != "sigmoid"
            or hf["topk_method"] != "noaux_tc" or hf["n_group"] != 1
            or not hf["norm_topk_prob"] or hf["moe_layer_freq"] != 1
            or rs["type"] != "yarn"):
        raise ValueError("only untied SwiGLU layers with one group of "
                         "sigmoid-routed, renormalised experts and yarn "
                         "rope are mapped")
    z = WM.dims(hf)
    return ModelConfig(
        name=hf["model_type"], family="moe", num_layers=z["layers"],
        d_model=z["d"], num_heads=z["h"], num_kv_heads=z["h"],
        d_ff=z["ff"], vocab_size=z["v"], gated_mlp=True, mlp_act="silu",
        norm_eps=float(hf["rms_norm_eps"]),
        rope_theta=float(hf["rope_theta"]),
        moe_experts=z["e"], moe_top_k=z["k"], moe_d_ff=z["mff"],
        moe_shared_d_ff=z["sff"], moe_first_dense=z["dense"],
        moe_router="sigmoid",
        moe_routed_scale=float(hf["routed_scaling_factor"]),
        moe_experts_held=z["held"], moe_held_offset=z["offset"],
        q_lora_rank=z["rq"], kv_lora_rank=z["rkv"],
        qk_nope_head_dim=z["nope"], qk_rope_head_dim=z["rope"],
        v_head_dim=z["dv"],
        rope_yarn=Yarn(
            factor=float(rs["factor"]),
            original_max=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        param_dtype="bfloat16")


def _attn(w: dict, hf: dict) -> dict:
    """The source's q_b and kv_b (in, out) as the program's per-head
    tensors: w_qb (rq, H, nope + rope); w_uk (H, nope, latent) and
    w_uv (H, v, latent), the two halves of each head's kv_b rows."""
    z = WM.dims(hf)
    h, nope = z["h"], z["nope"]
    kvb = w["kv_b"].reshape(z["rkv"], h, nope + z["dv"])
    return {"w_qa": w["q_a"], "q_norm": w["q_a_norm"],
            "w_qb": w["q_b"].reshape(z["rq"], h, nope + z["rope"]),
            "w_kva": w["kv_a"], "kv_norm": w["kv_a_norm"],
            "w_uk": kvb[..., :nope].transpose(1, 2, 0),
            "w_uv": kvb[..., nope:].transpose(1, 2, 0), "w_o": w["o"]}


def _dense_layer(base, i, hf: dict) -> dict:
    a, m = WM.attn_weights(base, i, hf), WM.dense_weights(base, i, hf)
    return {"ln1": a["attn_norm"], "ln2": a["mlp_norm"], "attn": _attn(a, hf),
            "mlp": {"w_gate": m["gate"], "w_up": m["up"],
                    "w_down": m["down"]}}


def _moe_layer(base, i, hf: dict) -> dict:
    a, m = WM.attn_weights(base, i, hf), WM.moe_weights(base, i, hf)
    s = m["shared"]
    return {"ln1": a["attn_norm"], "ln2": a["mlp_norm"], "attn": _attn(a, hf),
            "moe": {"router": m["router"], "router_bias": m["router_bias"],
                    "w_gate": m["gate"], "w_up": m["up"],
                    "w_down": m["down"]},
            "shared_mlp": {"w_gate": s["gate"], "w_up": s["up"],
                           "w_down": s["down"]}}


def program_params(base: jax.Array, hf: dict, cfg):
    """The program's parameter tree holding ``bench/weights_mla_moe.py``'s
    draws for the key ``base``: the dense first layers unrolled, the
    expert layers stacked.  Traceable, so that it runs on the device."""
    from repro.models import transformer as tfm

    z = WM.dims(hf)
    outer = WM.outer_weights(base, hf)
    params = {
        "embed": outer["embed"], "final_norm": outer["final_norm"],
        "lm_head": outer["lm_head"],
        "prefix": [_dense_layer(base, i, hf) for i in range(z["dense"])],
        "blocks": [jax.vmap(lambda i: _moe_layer(base, i, hf))(
            jnp.arange(z["dense"], z["layers"]))],
    }
    want = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    if jax.tree.structure(want) != jax.tree.structure(params) or \
            jax.tree.map(lambda a: (a.shape, a.dtype), want) != \
            jax.tree.map(lambda a: (a.shape, a.dtype), params):
        raise ValueError("the program's parameter layout changed; "
                         "bench/families/mla_moe.py no longer matches it")
    return params


def attn_matmul_flops(hf: dict) -> float:
    """One token's latent-attention projections, absorbed as the program
    computes them: q_a, q_b, kv_a, each head's key up-projection folded
    into its query, each head's value up-projection, o."""
    z = WM.dims(hf)
    d, h, rq, rkv = z["d"], z["h"], z["rq"], z["rkv"]
    return 2.0 * (d * rq + rq * h * (z["nope"] + z["rope"])
                  + d * (rkv + z["rope"]) + h * z["nope"] * rkv
                  + h * rkv * z["dv"] + h * z["dv"] * d)


def attention_flops(hf: dict, context: int) -> float:
    """Scores (latent plus rope key) and values (latent) of one token
    over ``context`` keys, every head, in one layer."""
    z = WM.dims(hf)
    return 2.0 * z["h"] * context * (2 * z["rkv"] + z["rope"])


def ffn_flops(hf: dict, layer: int) -> float:
    """One token's dense SwiGLU, or its router over every expert, the
    expert applications expected here (``k * held / E``: a token's k
    picks fall on the held share at that rate) and the shared expert."""
    z = WM.dims(hf)
    d = z["d"]
    if layer < z["dense"]:
        return 2.0 * 3 * d * z["ff"]
    expected = z["k"] * z["held"] / z["e"]
    return 2.0 * (d * z["e"] + expected * 3 * d * z["mff"]
                  + 3 * d * z["sff"])


def chunk_flops(hf: dict, start: int, tokens: int, *, head: bool) -> float:
    """Useful FLOPs of the tokens at 0-based positions ``[start, start +
    tokens)`` through every layer: the absorbed attention over each
    token's real context (position ``p`` attends to ``p + 1`` keys),
    ``k * held / E`` expert applications a token (8 * 12 / 384 here) and
    no more, plus the output head once where ``head``."""
    z = WM.dims(hf)
    ctx = sum(attention_flops(hf, p + 1) for p in range(start, start + tokens))
    layer = tokens * attn_matmul_flops(hf) + ctx
    ffn = sum(ffn_flops(hf, i) for i in range(z["layers"]))
    return (z["layers"] * layer + tokens * ffn
            + (2.0 * z["d"] * z["v"] if head else 0.0))
