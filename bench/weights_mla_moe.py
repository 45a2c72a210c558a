"""Random weights of the latent-attention MoE family (DeepSeek-V3 layer
equations: Kimi-K2), drawn from a run's seed.

Imported by the family's adapter (``bench/families/mla_moe.py``) and by
its reference (``bench/references/mla_moe.py``) alike, as
``bench/weights.py`` serves the dense family: every tensor comes from
``draw`` (``bench.weights``' keys and scales, its roundings made
explicit) under its own name, matrices as (in, out) in the source's
layout, so both sides see the same bfloat16 numbers.

Each routed expert is drawn under its own number, ``gate.<e>`` and so
on, so a share of experts ``[offset, offset + held)`` is the same
tensors whichever share a chip holds.  The router's selection bias is
drawn nonzero (``BIAS_SCALE``, about the spacing of the sigmoid scores
near the eighth best of 384), so that it changes which experts are
picked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench import weights as W

BIAS_SCALE = 0.02


def draw(base, layer, name: str, shape, scale) -> jax.Array:
    """``bench.weights.draw``'s tensor with each rounding to bfloat16
    made explicit: a float32 normal from the tensor's key, rounded, times
    the rounded scale, rounded again.  Written as ``bfloat16`` products,
    the compiler may keep a fused product in float32 (it may drop a pair
    of conversions), so one draw could round differently in the
    program's weights program and in the reference's; these roundings it
    keeps, so both see the same numbers."""
    def bf16(x):
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    n = bf16(jax.random.normal(W._key(base, layer, name), shape,
                               jnp.float32))
    return bf16(n * bf16(jnp.float32(scale))).astype(W.DTYPE)


def outer_weights(base, hf: dict) -> dict:
    """``bench.weights.outer_weights``' tensors, drawn by ``draw``."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    return {"embed": draw(base, -1, "embed", (v, d), d ** -0.5),
            "final_norm": draw(base, -1, "final_norm", (d,), W.NORM_DELTA),
            "lm_head": draw(base, -1, "lm_head", (d, v), d ** -0.5)}


def dims(hf: dict) -> dict:
    """Sizes from the configuration's keys: ``n_routed_experts`` is the
    count held here and ``deployment`` says which of the router's
    ``experts_routed`` they are."""
    share = hf["deployment"]
    return {"d": hf["hidden_size"], "h": hf["num_attention_heads"],
            "rq": hf["q_lora_rank"], "rkv": hf["kv_lora_rank"],
            "nope": hf["qk_nope_head_dim"], "rope": hf["qk_rope_head_dim"],
            "dv": hf["v_head_dim"], "ff": hf["intermediate_size"],
            "mff": hf["moe_intermediate_size"],
            "sff": hf["n_shared_experts"] * hf["moe_intermediate_size"],
            "e": share["experts_routed"], "held": hf["n_routed_experts"],
            "offset": share["experts_offset"],
            "k": hf["num_experts_per_tok"], "v": hf["vocab_size"],
            "layers": hf["num_hidden_layers"],
            "dense": hf["first_k_dense_replace"]}


def held_experts(hf: dict) -> range:
    z = dims(hf)
    return range(z["offset"], z["offset"] + z["held"])


def attn_weights(base, layer, hf: dict) -> dict:
    """Norms and latent attention of one layer: q_a (d, rq), its norm,
    q_b (rq, H * (nope + rope)); kv_a (d, latent + rope), the latent's
    norm, kv_b (latent, H * (nope + v)); o (H * v, d)."""
    z = dims(hf)
    d, h, rq, rkv = z["d"], z["h"], z["rq"], z["rkv"]
    qk, kvb = z["nope"] + z["rope"], z["nope"] + z["dv"]
    out = (2 * z["layers"]) ** -0.5      # residual-branch scale
    return {
        "attn_norm": draw(base, layer, "attn_norm", (d,), W.NORM_DELTA),
        "mlp_norm": draw(base, layer, "mlp_norm", (d,), W.NORM_DELTA),
        "q_a": draw(base, layer, "q_a", (d, rq), d ** -0.5),
        "q_a_norm": draw(base, layer, "q_a_norm", (rq,), W.NORM_DELTA),
        "q_b": draw(base, layer, "q_b", (rq, h * qk), rq ** -0.5),
        "kv_a": draw(base, layer, "kv_a", (d, rkv + z["rope"]), d ** -0.5),
        "kv_a_norm": draw(base, layer, "kv_a_norm", (rkv,), W.NORM_DELTA),
        "kv_b": draw(base, layer, "kv_b", (rkv, h * kvb), rkv ** -0.5),
        "o": draw(base, layer, "o", (h * z["dv"], d),
                  out * (h * z["dv"]) ** -0.5),
    }


def _mlp(base, layer, name: str, d: int, ff: int, out: float) -> dict:
    return {"gate": draw(base, layer, f"{name}gate", (d, ff), d ** -0.5),
            "up": draw(base, layer, f"{name}up", (d, ff), d ** -0.5),
            "down": draw(base, layer, f"{name}down", (ff, d),
                         out * ff ** -0.5)}


def dense_weights(base, layer, hf: dict) -> dict:
    """A dense layer's SwiGLU of width ``intermediate_size``."""
    z = dims(hf)
    return _mlp(base, layer, "", z["d"], z["ff"], (2 * z["layers"]) ** -0.5)


def moe_weights(base, layer, hf: dict, experts=None) -> dict:
    """An expert layer: the router (d, E) over every expert and its
    selection bias (E,), the ``experts`` (default: those held here) as
    stacks (held, in, out), and the shared expert."""
    z = dims(hf)
    d, mff = z["d"], z["mff"]
    out = (2 * z["layers"]) ** -0.5
    experts = held_experts(hf) if experts is None else experts
    per = [_mlp(base, layer, f"expert{e}.", d, mff, out) for e in experts]
    return {
        "router": draw(base, layer, "router", (d, z["e"]), d ** -0.5),
        "router_bias": draw(base, layer, "router_bias", (z["e"],),
                            BIAS_SCALE).astype(jnp.float32),
        **{k: jnp.stack([p[k] for p in per]) for k in ("gate", "up", "down")},
        "shared": _mlp(base, layer, "shared.", d, z["sff"], out),
    }
