"""Random weights drawn from a run's seed: the generic draw, and the
draws of a dense GQA decoder.

Both sides of the comparison draw from here: the program's parameters
(``bench/families/dense_gqa.py`` lays them out the way the program wants
them) and the plain reference (``bench/references/dense_gqa.py``), which
draws each layer again on its own.  Every tensor has its own key, folded
from the seed, the layer and the tensor's name, and is drawn directly in
bfloat16 (the dtype the model is served in) by ``draw``, so a layer drawn
alone is bit for bit the layer inside the stacked parameters.  A family
with other tensors draws them with ``draw`` in a module of its own, which
its adapter and its reference both import.

Norm weights are ``1 + delta`` with a small random ``delta``: the program
stores ``delta`` (its norms scale by ``1 + w``), the reference multiplies by
``1 + delta``.  Both are exact, so the two sides see the same numbers.

The matrices that write into the residual stream (``o``, ``down``) are
scaled down by ``1 / sqrt(2 * layers)``, as GPT-2 and Megatron initialize
them.  Without it a stack of random layers is chaotic: on the chip, at
h2o-danube's 24 layers, bfloat16 rounding grew into logit errors as large
as int8's, and no comparison could tell the two apart.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16
NORM_DELTA = 0.1          # scale of the random part of every norm weight


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    word = np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _key(base: jax.Array, layer, name: str) -> jax.Array:
    """Key of one tensor: layer -1 holds the embedding, head and final norm."""
    k = jax.random.fold_in(base, layer + 1)
    return jax.random.fold_in(k, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def dims(hf: dict) -> dict:
    """Sizes of the decoder from its published config keys."""
    d = hf["hidden_size"]
    h = hf["num_attention_heads"]
    return {"d": d, "h": h, "kv": hf["num_key_value_heads"],
            "hd": hf.get("head_dim") or d // h,
            "ff": hf["intermediate_size"], "v": hf["vocab_size"],
            "layers": hf["num_hidden_layers"]}


def window(hf: dict) -> int:
    """Sliding-window width in tokens, 0 for full attention (Qwen3 states
    a window but switches it off with ``use_sliding_window``)."""
    if hf["model_type"] == "qwen3" and not hf.get("use_sliding_window"):
        return 0
    return hf.get("sliding_window") or 0


def qk_norm(hf: dict) -> bool:
    """Qwen3's attention normalizes q and k per head; Llama/Mistral's not."""
    return hf["model_type"] in ("qwen3",)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, DTYPE) * jnp.asarray(scale, DTYPE)


def draw(base: jax.Array, layer, name: str, shape, scale) -> jax.Array:
    """The tensor ``name`` of ``layer`` (-1 outside the layers): a normal
    draw in bfloat16 from its own key, times ``scale``."""
    return _normal(_key(base, layer, name), shape, scale)


def layer_weights(base: jax.Array, layer, hf: dict) -> dict:
    """One decoder layer, bfloat16, matrices as (in, out)."""
    z = dims(hf)
    d, h, kv, hd, ff = z["d"], z["h"], z["kv"], z["hd"], z["ff"]
    out = (2 * z["layers"]) ** -0.5      # residual-branch scale
    w = {
        "attn_norm": draw(base, layer, "attn_norm", (d,), NORM_DELTA),
        "mlp_norm": draw(base, layer, "mlp_norm", (d,), NORM_DELTA),
        "q": draw(base, layer, "q", (d, h * hd), d ** -0.5),
        "k": draw(base, layer, "k", (d, kv * hd), d ** -0.5),
        "v": draw(base, layer, "v", (d, kv * hd), d ** -0.5),
        "o": draw(base, layer, "o", (h * hd, d), out * (h * hd) ** -0.5),
        "gate": draw(base, layer, "gate", (d, ff), d ** -0.5),
        "up": draw(base, layer, "up", (d, ff), d ** -0.5),
        "down": draw(base, layer, "down", (ff, d), out * ff ** -0.5),
    }
    if qk_norm(hf):
        w["q_norm"] = draw(base, layer, "q_norm", (hd,), NORM_DELTA)
        w["k_norm"] = draw(base, layer, "k_norm", (hd,), NORM_DELTA)
    return w


def outer_weights(base: jax.Array, hf: dict) -> dict:
    """Token embedding (V, d), final norm delta (d,), output head (d, V):
    the same for every family, from ``hidden_size`` and ``vocab_size``."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    return {
        "embed": draw(base, -1, "embed", (v, d), d ** -0.5),
        "final_norm": draw(base, -1, "final_norm", (d,), NORM_DELTA),
        "lm_head": draw(base, -1, "lm_head", (d, v), d ** -0.5),
    }
