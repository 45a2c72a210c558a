"""Mixture-of-experts FFN (llama4-maverick, kimi-k2).

Two layers: ``moe_ffn`` (softmax over the top k, every expert held) and
``held_moe_ffn`` (kimi-k2's sigmoid router over every expert, computing
only the experts this chip holds).

GShard-style capacity-factor einsum dispatch: shardable under GSPMD with the
expert dimension on the ``model``/``expert`` mesh axis, no ragged ops, and a
fixed compute shape (required for the multi-pod dry-run).  Tokens over
capacity are dropped (their combine weight is zero) — standard
capacity-factor semantics.

The ELK connection (paper §7 "Apply ELK to MoE"): expert weights are
late-bound preloads — the scheduler models the expert fetch as a preload op
whose earliest issue time is the router op (``Op.preload_dep`` in
``core/graph.py``).  At runtime the EP all_to_all below is the
"data-distribution phase" of the expert tensors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _act, linear


def capacity(tokens: int, experts: int, top_k: int, factor: float) -> int:
    cap = int(tokens * top_k * factor / experts) + 1
    # never below top_k (tiny smoke shapes must route) and never beyond
    # tokens*top_k (the dropless bound — more slots can't be used)
    return min(max(cap, top_k), tokens * top_k)


def router_weights(logits: jax.Array, top_k: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Top-k routing with softmax-renormalized gates.

    logits: (T, E) -> gates (T, k) fp32, idx (T, k) int32."""
    lf = logits.astype(jnp.float32)
    gates, idx = jax.lax.top_k(lf, top_k)
    gates = jax.nn.softmax(gates, axis=-1)
    return gates, idx


def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig,
            capacity_factor: float | None = None,
            dropless: bool = False, mesh=None) -> jax.Array:
    """x: (T, d) token-major.  p: router (d,E), w_gate/w_up (E,d,ff),
    w_down (E,ff,d).  ``dropless`` sizes capacity so no assignment is ever
    dropped (decode uses this — T is just the batch there).

    Dispatch is scatter/gather (sort-free ranking + ``.at[].set`` with
    OOB-drop), not the GShard (T,E,C) einsum: at kimi-k2 scale the one-hot
    dispatch tensor is O(T*E*C) ~= tens of TB, while the scatter path is
    O(E*C*d + T*k*d)."""
    t, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    if cfg.experts_held != e:
        raise ValueError("the softmax router computes every expert; "
                         "a held share needs the sigmoid router")
    cf = capacity_factor or cfg.moe_capacity_factor
    c = t * k if dropless else capacity(t, e, k, cf)

    logits = linear(x, p["router"])                          # (T, E)
    gates, idx = router_weights(logits, k)                   # (T,k)

    # slot of each (token, slot-k) assignment inside its expert's buffer:
    # rank among all assignments to the same expert, in token order.
    flat_e = idx.reshape(t * k)                              # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (T*k,E)
    slot = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # (T*k,)

    tok = jnp.arange(t * k) // k
    xe = jnp.zeros((e, c, d), x.dtype)
    # over-capacity slots (>= c) drop via scatter OOB semantics
    xe = xe.at[flat_e, slot].set(x[tok], mode="drop")        # (E,C,d)

    def constrain_ep(a):
        """Expert-parallel placement: E over the model axis (the expert
        dispatch is the paper's §7 data-distribution phase)."""
        if mesh is None or "model" not in getattr(mesh, "shape", {}):
            return a
        if a.shape[0] % mesh.shape["model"]:
            return a
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, PartitionSpec(
                "model", *([None] * (a.ndim - 1)))))

    xe = constrain_ep(xe)
    act = _act(cfg.mlp_act)
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
        h = act(g) * u
    else:
        h = act(jnp.einsum("ecd,edf->ecf", xe, p["w_up"]))
    ye = constrain_ep(jnp.einsum("ecf,efd->ecd", h, p["w_down"]))  # (E,C,d)

    rows = ye.at[flat_e, slot].get(mode="fill", fill_value=0)  # (T*k,d)
    out = jnp.einsum("tk,tkd->td", gates.astype(jnp.float32),
                     rows.reshape(t, k, d).astype(jnp.float32))
    return out.astype(x.dtype)


@jax.named_scope("router")
def sigmoid_route(x: jax.Array, p: dict, cfg: ModelConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """Deepseek-v3's ``noaux_tc`` routing over every expert: float32
    logits, sigmoid scores, the top k of scores plus the selection bias
    (``router_bias``, which picks experts but never weighs them), their
    scores renormalised over the k and scaled by ``moe_routed_scale``.

    x: (T, d) -> weights (T, k) fp32, idx (T, k) int32."""
    logits = jnp.einsum("td,de->te", x, p["router"],
                        preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           cfg.moe_top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.moe_routed_scale, idx


@jax.named_scope("moe")
def held_moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig
                 ) -> tuple[jax.Array, jax.Array]:
    """The routed experts' part of the layer that this chip holds: experts
    ``[moe_held_offset, + moe_experts_held)`` of the ``moe_experts`` the
    router scores (expert parallelism without its exchange: assignments
    to experts held elsewhere add nothing here).

    x: (T, d); p: router (d, E), router_bias (E,), w_gate/w_up (E_held,
    d, ff), w_down (E_held, ff, d).  A token picks distinct experts, so
    each held expert's buffer has T rows, the exact dropless bound: row t
    holds token t where it is routed there and zeros elsewhere.  Returns
    (out (T, d), the rows routed to each held expert (E_held,) int32)."""
    held = cfg.experts_held
    w, idx = sigmoid_route(x, p, cfg)
    local = idx - cfg.moe_held_offset                         # (T, k)
    mine = jax.nn.one_hot(local, held, dtype=jnp.float32)     # (T, k, Eh)
    gates = jnp.einsum("tk,tke->et", w, mine)                 # (Eh, T)
    routed = jnp.sum(mine, axis=1).T > 0                      # (Eh, T)
    with jax.named_scope("dispatch"):
        xe = jnp.where(routed[..., None], x[None], jnp.zeros((), x.dtype))
    act = _act(cfg.mlp_act)
    g = jnp.einsum("etd,edf->etf", xe, p["w_gate"])
    u = jnp.einsum("etd,edf->etf", xe, p["w_up"])
    ye = jnp.einsum("etf,efd->etd", act(g) * u, p["w_down"])
    with jax.named_scope("combine"):
        out = jnp.einsum("et,etd->td", gates, ye.astype(jnp.float32))
    return out.astype(x.dtype), jnp.sum(routed, axis=1, dtype=jnp.int32)


def moe_params(rng, cfg: ModelConfig, dtype) -> dict:
    d, e, held = cfg.d_model, cfg.moe_experts, cfg.experts_held
    ff = cfg.moe_hidden()
    ks = jax.random.split(rng, 4)
    s_in, s_ff = d ** -0.5, ff ** -0.5
    p = {"router": jax.random.normal(ks[0], (d, e), dtype) * s_in}
    if cfg.moe_router == "sigmoid":
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.gated_mlp:
        p["w_gate"] = jax.random.normal(ks[1], (held, d, ff), dtype) * s_in
    p["w_up"] = jax.random.normal(ks[2], (held, d, ff), dtype) * s_in
    p["w_down"] = jax.random.normal(ks[3], (held, ff, d), dtype) * s_ff
    return p


def aux_load_balance_loss(logits: jax.Array, idx: jax.Array,
                          num_experts: int) -> jax.Array:
    """Switch-style load-balance auxiliary loss (mean_prob . mean_assign . E)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T,E)
    assign = jax.nn.one_hot(idx[..., 0], num_experts, dtype=jnp.float32)
    return num_experts * jnp.mean(probs.mean(0) * assign.mean(0))
