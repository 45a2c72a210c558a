"""Sharding rules for the (pod, data, model) production mesh.

Axis roles
----------
* ``pod``   — pure data parallelism across pods (gradients cross the pod
  boundary once per step, optionally compressed: ``distributed/compression``).
* ``data``  — within-pod data parallelism; also the ZeRO axis: optimizer
  state (and, in FSDP mode, parameters) shard over it.
* ``model`` — tensor parallelism: attention/MLP/vocab dims; also the
  expert-parallel axis for MoE and the sequence/KV axis for long-context
  serving (SP) when head counts don't divide.

Rules are path-pattern based over the parameter pytree produced by
``models.transformer.init_params``.  Every rule falls back to replication
when a dimension is not divisible by the axis size — XLA would otherwise
pad-and-reshard behind our back; an explicit fallback keeps the collective
schedule visible to the roofline analysis.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# preferred (regex over '/'-joined path) -> spec builder.  ``d`` below means
# "shard this dim over the model axis".  Dims count from the *end* so the
# stacked-blocks leading dim never shifts patterns.
_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    # name pattern                      spec for the trailing dims
    (r"embed$",                         ("model", None)),     # (V, d)
    (r"lm_head$",                       (None, "model")),     # (d, V)
    (r"(dec|enc)_pos$",                 (None, None)),
    (r"attn/w_[qkv]$",                  (None, "model")),
    (r"attn/w_o$",                      ("model", None)),
    (r"attn/b_[qkv]$",                  ("model",)),
    (r"xattn/w_[qkv]$",                 (None, "model")),
    (r"xattn/w_o$",                     ("model", None)),
    (r"(mlp|shared_mlp)/w_(gate|up)$",  (None, "model")),
    (r"(mlp|shared_mlp)/w_down$",       ("model", None)),
    (r"(mlp|shared_mlp)/b_up$",         ("model",)),
    (r"(mlp|shared_mlp)/b_down$",       (None,)),
    (r"moe/router$",                    (None, None)),
    (r"moe/w_(gate|up)$",               ("model", None, None)),  # (E,d,ff): EP
    (r"moe/w_down$",                    ("model", None, None)),
    # rwkv time-mix / channel-mix
    (r"w_[rkvg]$",                      (None, "model")),
    (r"w_decay$",                       (None, "model")),
    (r"w_o$",                           ("model", None)),
    (r"w_ck$",                          (None, "model")),
    (r"w_cv$",                          ("model", None)),
    # hymba ssm branch
    (r"ssm/w_in$",                      (None, "model")),
    (r"ssm/w_out$",                     ("model", None)),
    (r"ssm/w_dt$",                      (None, "model")),
    (r"ssm/w_bc$",                      (None, None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    return mesh.shape[name]


def param_pspec(path: str, shape: tuple[int, ...], mesh: Mesh,
                fsdp: bool = False, layout: str = "tp") -> P:
    """Spec for one parameter.  Leading stacked-block dims are unsharded
    (or data-sharded in FSDP mode, realizing the ELK preload state).

    ``layout='fsdp2d'``: block weights shard their largest dim over the
    *joint* (data, model) axes — no tensor-parallel activation traffic at
    all (the measured TP-16 activation gathers cost ~30x the compute bound
    for dense <=30B training; EXPERIMENTS.md §Perf iteration 2).  The
    vocab head/embedding keep their model-axis sharding (vocab-parallel
    logits are what bound the loss memory)."""
    if layout == "fsdp2d" and path.split("/")[0] in ("blocks", "prefix") \
            and len(shape) >= 2:
        dims = list(shape)
        lead = 1 if path.split("/")[0] == "blocks" else 0
        body = dims[lead:]
        order = sorted(range(len(body)), key=lambda i: -body[i])
        d_sz = _axis_size(mesh, "data")
        m_sz = _axis_size(mesh, "model")
        spec: list = [None] * len(body)
        best = order[0]
        if body[best] % (d_sz * m_sz) == 0:
            spec[best] = ("data", "model")
        else:
            placed = False
            for i in order:
                if body[i] % d_sz == 0:
                    spec[i] = "data"
                    placed = True
                    break
            for i in order:
                if spec[i] is None and body[i] % m_sz == 0:
                    spec[i] = "model"
                    break
        return P(*([None] * lead), *spec)

    trailing: tuple[Optional[str], ...] = ()
    for pat, spec in _RULES:
        if re.search(pat, path):
            trailing = spec
            break
    # validate divisibility; drop the axis if it doesn't divide
    trailing = tuple(
        (ax if ax and shape[len(shape) - len(trailing) + i]
         % _axis_size(mesh, ax) == 0 else None)
        for i, ax in enumerate(trailing))
    lead_n = len(shape) - len(trailing)
    lead: list[Optional[str]] = [None] * lead_n
    if fsdp and lead_n >= 1 and path.split("/")[0] == "blocks":
        # FSDP/ELK-preload-state: shard the stacked-blocks dim's *largest
        # unsharded trailing dim* over data.  Gathers happen layer-by-layer
        # in the streaming scan (serve/stream.py) or via XLA (train).
        cand = [i for i, ax in enumerate(trailing) if ax is None]
        sizes = shape[lead_n:]
        cand = [i for i in cand
                if sizes[i] % _axis_size(mesh, "data") == 0 and sizes[i] > 1]
        if cand:
            best = max(cand, key=lambda i: sizes[i])
            trailing = tuple("data" if i == best else ax
                             for i, ax in enumerate(trailing))
    return P(*lead, *trailing)


def param_shardings(params: PyTree, mesh: Mesh, fsdp: bool = False,
                    layout: str = "tp") -> PyTree:
    def one(path, leaf):
        spec = param_pspec(_path_str(path), np.shape(leaf), mesh, fsdp,
                           layout)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params)


def init_sharded(init: Callable[[], PyTree], mesh: Mesh, *,
                 fsdp: bool = False, layout: str = "tp") -> PyTree:
    """Run the parameter initializer ``init`` as one compiled program whose
    outputs land straight in their rule shardings: no eager per-layer
    arrays beside the stacked ones, no host copy, no replicated staging
    tree — peak device memory is the sharded weights themselves."""
    shapes = jax.eval_shape(init)
    return jax.jit(init, out_shardings=param_shardings(
        shapes, mesh, fsdp, layout))()


def param_specs(params: PyTree, mesh: Mesh, fsdp: bool = False,
                layout: str = "tp") -> PyTree:
    def one(path, leaf):
        return param_pspec(_path_str(path), np.shape(leaf), mesh, fsdp,
                           layout)
    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes that jointly shard the global batch."""
    return tuple(ax for ax in ("pod", "data") if ax in mesh.shape)


def batch_pspec(mesh: Mesh) -> P:
    return P(batch_axes(mesh))


def batch_shardings(batch: PyTree, mesh: Mesh) -> PyTree:
    """Batch dict sharding: dim 0 = global batch on (pod, data)."""
    bp = batch_axes(mesh)

    def one(leaf):
        nd = np.ndim(leaf)
        return NamedSharding(mesh, P(bp, *([None] * (nd - 1))))
    return jax.tree_util.tree_map(one, batch)


def cache_pspec(key: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Serving cache sharding.  KV tensors (L, B, Hkv, C, hd): batch over
    (pod, data); heads over model when divisible, otherwise the cache
    length C shards over model (sequence parallelism — the GQA small-kv
    fallback)."""
    bp = batch_axes(mesh)
    dp = int(np.prod([mesh.shape[a] for a in bp])) if bp else 1
    m = mesh.shape.get("model", 1)

    def b_ax(B):
        return bp if B % max(dp, 1) == 0 else None

    if key in ("k", "v", "k_scale", "v_scale", "cross_k", "cross_v"):
        L, B, H, C, D = shape
        if H % m == 0:
            return P(None, b_ax(B), "model", None, None)
        if C % m == 0:
            return P(None, b_ax(B), None, "model", None)
        return P(None, b_ax(B), None, None, None)
    if key == "rwkv_state":        # (L, B, H, D, D)
        L, B, H, *_ = shape
        h_ax = "model" if H % m == 0 else None
        return P(None, b_ax(B), h_ax, None, None)
    if key == "ssm_state":         # (L, B, d, N)
        L, B, d, _ = shape
        d_ax = "model" if d % m == 0 else None
        return P(None, b_ax(B), d_ax, None)
    if key == "slot_pos":
        return P(None)
    return P()                     # pos scalar etc.


def cache_shardings(cache: PyTree, mesh: Mesh) -> PyTree:
    def one(path, leaf):
        key = _path_str(path).split("/")[-1]
        # scales tuple nests one level deeper; normalize
        if key in ("0", "1"):
            key = _path_str(path).split("/")[-2]
        return NamedSharding(mesh, cache_pspec(key, np.shape(leaf), mesh))
    return jax.tree_util.tree_map_with_path(one, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def constrain_batch(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Constrain a (B, ...) activation's batch dim onto (pod, data)."""
    nd = x.ndim
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(batch_axes(mesh), *([None] * (nd - 1)))))
