"""Random weights of the toy MoE family, drawn from a run's seed.

Imported by the family's adapter and by its reference alike, as
``bench/weights.py`` serves the dense family: every tensor comes from
``bench.weights.draw`` under its own name, so both sides see the same
bfloat16 numbers.  The outer weights are ``bench.weights.outer_weights``.
"""

from __future__ import annotations

from bench import weights as W


def dims(hf: dict) -> dict:
    """Sizes of the decoder from its config keys."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return {"d": d, "h": h, "kv": hf["num_key_value_heads"],
            "hd": hf["head_dim"], "e": hf["num_experts"],
            "k": hf["num_experts_per_tok"], "ff": hf["moe_intermediate_size"],
            "v": hf["vocab_size"], "layers": hf["num_hidden_layers"]}


def layer_weights(base, layer, hf: dict) -> dict:
    """One decoder layer, bfloat16: attention as the dense family draws
    it, a router (d, E) and each expert's SwiGLU, stacked as (E, in, out)."""
    z = dims(hf)
    d, h, kv, hd, e, ff = z["d"], z["h"], z["kv"], z["hd"], z["e"], z["ff"]
    out = (2 * z["layers"]) ** -0.5      # residual-branch scale
    return {
        "attn_norm": W.draw(base, layer, "attn_norm", (d,), W.NORM_DELTA),
        "mlp_norm": W.draw(base, layer, "mlp_norm", (d,), W.NORM_DELTA),
        "q": W.draw(base, layer, "q", (d, h * hd), d ** -0.5),
        "k": W.draw(base, layer, "k", (d, kv * hd), d ** -0.5),
        "v": W.draw(base, layer, "v", (d, kv * hd), d ** -0.5),
        "o": W.draw(base, layer, "o", (h * hd, d), out * (h * hd) ** -0.5),
        "router": W.draw(base, layer, "router", (d, e), d ** -0.5),
        "gate": W.draw(base, layer, "gate", (e, d, ff), d ** -0.5),
        "up": W.draw(base, layer, "up", (e, d, ff), d ** -0.5),
        "down": W.draw(base, layer, "down", (e, ff, d), out * ff ** -0.5),
    }
