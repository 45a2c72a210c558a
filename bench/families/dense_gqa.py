"""Dense GQA decoders (Llama, Mistral, Qwen3): the first family adapter.

The published config keys become a ``repro`` ``ModelConfig``, the seeded
draws of ``bench/weights.py`` become the program's parameter tree, and the
useful FLOPs of a chunk are counted from the published widths.  The
contract is in ``bench/families/__init__.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W


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
                         "bench/families/dense_gqa.py no longer matches it")
    return params


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
