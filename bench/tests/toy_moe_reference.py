"""Plain float32 forward of the toy MoE family (tests only).

Written from the maths, with nothing of the program: pre-norm residual
layers, RMS norm, rotary embeddings on the two halves of each head,
grouped-query causal attention, and a mixture of SwiGLU experts: the
router's top ``k`` logits chosen, their softmax the gates, every expert
computed on every token and the chosen ones summed by their gates.
The weights are ``toy_moe_weights.py``'s draws widened to float32, and
every product runs at ``highest`` precision.  It has no controls.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights as W
from bench.references.dense_gqa import _gap, _rms, _rope

import toy_moe_weights as TW

BUCKET = 128
CONTROLS = ()


def _layer(h, w, hf):
    z = TW.dims(hf)
    s, nh, nkv, hd = h.shape[0], z["h"], z["kv"], z["hd"]
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    pos = jnp.arange(s)
    x = _rms(h, 1.0 + w["attn_norm"], eps)
    q = _rope((x @ w["q"]).reshape(s, nh, hd), pos, theta)
    k = _rope((x @ w["k"]).reshape(s, nkv, hd), pos, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat((x @ w["v"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    scores = jnp.where(pos[None, None, :] <= pos[None, :, None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    h = h + att.reshape(s, nh * hd) @ w["o"]

    x = _rms(h, 1.0 + w["mlp_norm"], eps)
    top, chosen = lax.top_k(x @ w["router"], z["k"])          # (S, k)
    gates = jax.nn.softmax(top, -1)
    every = jnp.einsum("sef,efd->sed", jax.nn.silu(
        jnp.einsum("sd,edf->sef", x, w["gate"]))
        * jnp.einsum("sd,edf->sef", x, w["up"]), w["down"])  # (S, E, d)
    picked = jnp.take_along_axis(every, chosen[:, :, None], 1)  # (S, k, d)
    return h + jnp.einsum("sk,skd->sd", gates, picked)


@functools.lru_cache(maxsize=4)
def _logits(hf_json: str):
    """Jitted: the seed's key and a padded sequence to every position's
    logits."""
    hf = json.loads(hf_json)
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))

    def forward(base, tokens):
        outer = f32(W.outer_weights(base, hf))
        h = outer["embed"][tokens]
        for i in range(hf["num_hidden_layers"]):
            h = _layer(h, f32(TW.layer_weights(base, i, hf)), hf)
        x = _rms(h, 1.0 + outer["final_norm"], float(hf["rms_norm_eps"]))
        return x @ outer["lm_head"]
    return jax.jit(forward)


def served_gaps(hf: dict, seed: int, seqs, idx, *, controls=()):
    """As ``bench/references/dense_gqa.served_gaps``, without controls."""
    if controls:
        raise ValueError(f"the toy MoE family has no controls: {controls}")
    base = W.seed_key(seed)
    forward = _logits(json.dumps(hf, sort_keys=True))
    out = {"reference": []}
    with jax.default_matmul_precision("highest"):
        for tokens, first in seqs:
            tokens = np.asarray(tokens, np.int32)
            n = -(-len(tokens) // BUCKET) * BUCKET
            logits = forward(base, jnp.asarray(np.pad(tokens,
                                                      (0, n - len(tokens)))))
            rows = np.arange(first - 1, len(tokens) - 1)
            ref = logits[rows]
            out["reference"].append((
                np.asarray(_gap(ref, jnp.asarray(tokens[rows + 1]))),
                np.asarray(ref[:, jnp.asarray(idx)])))
    return out
