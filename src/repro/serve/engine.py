"""Batched serving engine: prefill + decode with sharded KV caches.

Two execution modes:

* ``gspmd``      — weights resident replicated-over-data / TP-sharded;
  XLA schedules all collectives (the `Basic`-like baseline at pod level).
* ``elk_stream`` — weights resident *sharded over data* (ELK preload
  state, 1/k per device) with the gather-ahead window of
  ``serve/stream.py``; prefetch depth comes from the ELK scheduler via
  ``core/integration.pod_plan``.  This is what lets a model k-times larger
  than one replica's HBM serve from the pod, at the cost of ICI traffic —
  the paper's capacity/IO/communication trade, live.

Two batching disciplines (DESIGN.md §6):

* lock-step      — ``generate``: one static batch, every request advances
  together.
* slot-batched   — ``step``/``prefill_chunk``/``insert_slot``/
  ``evict_slot``: a per-slot cache where every row sits at its own
  position; requests join/leave the running batch at slot granularity.
  ``serve/batcher.py`` drives this as a continuous-batching scheduler.

Every jitted step that threads a cache **donates** it: the compiled step
aliases the cache input to the cache output (no per-token copy, no double
HBM footprint), exactly as ``train/step.py`` donates params/opt state.
Callers must treat a cache passed to the engine as consumed and use the
returned one.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (batch_axes, cache_shardings,
                                        param_shardings)
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.models.frontends import frontend_embeddings

PyTree = Any


@dataclasses.dataclass
class ServeConfig:
    batch: int
    cache_capacity: int
    mode: str = "gspmd"               # gspmd | elk_stream
    prefetch_depth: int = 2           # ELK preload number (elk_stream)
    kv_dtype: str = "bfloat16"        # bfloat16 | int8
    max_slots: int = 0                # continuous batching slots (0 = batch)
    prefill_chunk: int = 32           # max prompt tokens per scheduler tick
    steady_interval_s: float = 0.0    # pipeline-pod steady-state interval
    #                                   (0 = single-chip plan, no pipeline)
    oversub: float = 1.0              # admission multiplier K: virtual slots
    #                                   per physical slot (DESIGN.md §11);
    #                                   >1 only with a finite backing tier
    slot_spill_s: float = 0.0         # planned one-way offload/refill time
    #                                   for one slot's KV ring (spill_time)
    prefix_cache_bytes: int = 0       # prefix-KV store budget in the bytes
    #                                   left after rings (0 = store off)

    @property
    def slots(self) -> int:
        return self.max_slots or self.batch

    @property
    def virtual_slots(self) -> int:
        """Requests the batcher may hold in flight: the physical slots plus
        the spilled rings the backing tier can park (``oversub`` = K)."""
        return max(self.slots, int(round(self.slots * self.oversub)))


def tier_kv_capacity(cfg: ModelConfig, chip, *, batch: int,
                     kv_dtype: str = "bfloat16") -> int:
    """Per-request KV-cache tokens resident in ``chip``'s off-core memory
    tiers after weight placement (DESIGN.md §10), or 0 when unbounded.

    The KV cache lives in the chip's non-SRAM tiers.  A chip with an
    unbounded backing store (any ``hbm_bw > 0`` chip — including every
    default two-tier config) can hold any cache length, so the budget is
    infinite and this returns 0 ("no cap").  On an SRAM-only chip with
    finite staging tiers (e.g. ``ipu_pod4().with_stacked_dram()``), the
    stacked bytes left after the weights that spill out of SRAM bound the
    cache:  ``tokens = (tier_bytes - weight_spill) // (batch * per_token)``
    with ``per_token`` the bytes of one token in the rings ``init_cache``
    builds (``num_layers * 2 * num_kv_heads * head_dim * itemsize`` for
    K/V, ``num_layers * (latent + rope) * itemsize`` for a latent ring).
    """
    if chip is None:
        return 0
    tiers = chip.mem_tiers[1:]
    if not tiers or any(t.unbounded for t in tiers):
        return 0
    left = _tier_bytes_left(cfg, chip)
    per_token = _ring_bytes_per_token(cfg, kv_dtype)
    return int(left // max(batch * per_token, 1))


def _ring_bytes_per_token(cfg: ModelConfig, kv_dtype: str) -> int:
    """Bytes one cached token takes over every layer: the ring leaves that
    ``init_cache`` builds (K/V, or the latent and its rope key), per slot
    of capacity."""
    spec = tfm.CacheSpec(capacity=1, batch=1, kv_dtype=jnp.dtype(kv_dtype))
    shape = jax.eval_shape(lambda: tfm.init_cache(cfg, spec))
    return int(sum(int(np.prod(shape[key].shape)) * shape[key].dtype.itemsize
                   for key in ("k", "v", "ckv", "kpe") if key in shape))


def _tier_bytes_left(cfg: ModelConfig, chip) -> int:
    """Off-core tier bytes left after weight placement.  ``place_tiers``
    stages the weights that spill out of SRAM across the finite tiers
    (staging tiers + backing store); the *aggregate* bytes they occupy are
    placement-invariant, so the KV budget is the summed tier capacity minus
    that spill regardless of which tier each block landed in."""
    budget = sum(t.capacity for t in chip.mem_tiers[1:])
    weight_bytes = cfg.param_count() * jnp.dtype(cfg.param_dtype).itemsize
    spill = max(0, weight_bytes - chip.total_sram)
    return budget - min(spill, budget)


def kv_ring_bytes(cfg: ModelConfig, capacity: int,
                  kv_dtype: str = "bfloat16") -> int:
    """Bytes one request's spillable slot state occupies: the KV ring plus
    ``pos``/``slot_pos`` metadata — the volume one ``offload_slot`` /
    ``refill_slot`` moves across the tier boundary."""
    try:
        spec = tfm.CacheSpec(capacity=capacity, batch=1,
                             kv_dtype=jnp.dtype(kv_dtype), per_slot=True)
        shape = jax.eval_shape(lambda: tfm.init_cache(cfg, spec))
        return int(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(shape)))
    except ValueError:      # enc-dec: no per-slot serving; K/V formula only
        hd = cfg.resolved_head_dim
        return (capacity * cfg.num_layers * 2 * cfg.num_kv_heads * hd
                * jnp.dtype(kv_dtype).itemsize)


_OVERSUB_MAX = 8.0   # spill-pool backstop: at most 8 virtual slots/physical


def tier_kv_oversub(cfg: ModelConfig, chip, *, slots: int,
                    cache_capacity: int,
                    kv_dtype: str = "bfloat16") -> float:
    """Admission multiplier K for the oversubscribed batcher (DESIGN.md
    §11): how many full KV rings the tier bytes left after weight placement
    can hold, per physical slot.  1.0 when any backing tier is unbounded
    (nothing forces a spill — the resident cache can simply grow) or when
    the budget holds no more rings than the resident slots."""
    if chip is None:
        return 1.0
    tiers = chip.mem_tiers[1:]
    if not tiers or any(t.unbounded for t in tiers):
        return 1.0
    ring = kv_ring_bytes(cfg, cache_capacity, kv_dtype)
    rings = _tier_bytes_left(cfg, chip) // max(ring, 1)
    return float(max(1.0, min(rings / max(slots, 1), _OVERSUB_MAX)))


PREFILL_SAT = 128   # prompt tokens one weight pass saturates: the chunk
#                     size past which prefill stops being amortized (the
#                     planner's long-standing chunk clamp, now named)


def elk_serve_config(cfg: ModelConfig, *, batch: int, cache_capacity: int,
                     kv_dtype: str = "bfloat16", num_chips: int = 256,
                     design: str = "ELK-Full", pipeline: bool = False,
                     pod=None, role: str = "mixed",
                     mesh=None) -> ServeConfig:
    """ServeConfig with the serving knobs chosen by the ELK scheduler.

    ``pod_plan`` reads the process-level plan cache (DESIGN.md §2), so this
    is cheap to call per engine/request once any compile for the same
    (model, shape, design) has happened in this process.

    * ``prefetch_depth`` — the paper's preload number p, per layer-block.
    * ``prefill_chunk``  — admission budget for chunked prefill: how many
      prompt tokens one scheduler tick may process.

      Single-chip plans size it to the gather-ahead window (16 tokens of
      chunk compute per preloaded block keeps the chunk hidden behind the
      window's ICI traffic).  With ``pipeline=True`` the pod is planned
      with the **hybrid** search (joint cut x width x replicas x
      microbatch, DESIGN.md §9 — never worse than pure pipeline stages)
      and admission is sized from the **steady-state interval** instead:
      the whole running batch decodes once per ``batch_interval``, so one
      interval hides up to ``microbatch * microbatches`` prompt tokens of
      prefill — that is the per-tick admission budget (for a pure
      pipeline plan ``microbatches == num_stages``, so this is the same
      budget as before the hybrid search existed).  Both are clamped to
      the cache capacity so one chunk never wraps a request's own ring.

    ``role`` specializes the sizing for a disaggregated fleet pod
    (DESIGN.md §12).  A ``prefill`` pod's whole job is admission, so its
    chunk budget opens to the full saturating weight pass
    (``PREFILL_SAT`` tokens) instead of the interference-limited budget a
    mixed pod must respect.  A ``decode`` pod receives its work
    pre-filled over the fleet tier and spends its budget on residency
    instead: the chunk shrinks to the floor (it only prefills work shed
    to it) while the plan's full oversubscription K stays, maximizing
    slots x oversub.  ``mixed`` (the default) is byte-identical to the
    pre-fleet behaviour.

    ``mesh`` is the local mesh the engine will run on.  When its batch
    axes hold one device every block is resident and there is nothing to
    gather, so the mode is ``gspmd`` (a window would only copy blocks the
    layer scan reads in place) and, outside a pipeline plan, the chunk
    opens to ``PREFILL_SAT``: with no window traffic to hide behind, a
    tick's chunk is bounded by what one resident weight pass saturates.
    Without a mesh the config is the plan's, as before.
    """
    from repro.core.integration import pod_plan

    # tier-resident KV budget (DESIGN.md §10): on a pod whose off-core
    # memory is entirely finite, the cache can only grow to the staging
    # bytes left after weight placement.  0 = unbounded (every two-tier
    # default has an unbounded backing tier), so those configs are
    # value-identical to the pre-tier behaviour.
    cap = tier_kv_capacity(cfg, pod, batch=batch, kv_dtype=kv_dtype)
    if cap > 0:
        cache_capacity = min(cache_capacity, cap)

    # oversubscription (DESIGN.md §11): on an all-finite hierarchy the
    # rings left after weight placement beyond the resident batch become
    # virtual slots (K), each swap priced at spill_time of one ring; bytes
    # left after *those* rings fund the prefix-KV store.  Unbounded-backed
    # pods keep K=1 and everything below zero — value-identical to PR 8.
    oversub = tier_kv_oversub(cfg, pod, slots=batch,
                              cache_capacity=cache_capacity,
                              kv_dtype=kv_dtype)
    slot_spill_s = 0.0
    prefix_bytes = 0
    if oversub > 1.0:
        from repro.core.cost_model import AnalyticCostModel

        ring = kv_ring_bytes(cfg, cache_capacity, kv_dtype)
        slot_spill_s = AnalyticCostModel(pod).spill_time(
            ring, 0, pod.backing_tier)
        used = int(round(batch * oversub)) * ring
        prefix_bytes = int(max(0, _tier_bytes_left(cfg, pod) - used))

    if role not in ("mixed", "prefill", "decode"):
        raise ValueError(f"unknown pod role {role!r}; "
                         "known: mixed, prefill, decode")
    knobs = pod_plan(cfg, batch=batch, seq=cache_capacity, phase="decode",
                     num_chips=num_chips, design=design,
                     mode="hybrid" if pipeline else "flat", chip=pod)
    depth = max(knobs.prefetch_depth, 1)
    resident = mesh is not None and math.prod(
        mesh.shape[ax] for ax in batch_axes(mesh)) == 1
    if pipeline and knobs.microbatch > 0:
        per_interval = max(knobs.microbatch * max(knobs.microbatches, 1), 16)
        chunk = min(per_interval, PREFILL_SAT, cache_capacity)
    elif resident:
        chunk = min(PREFILL_SAT, cache_capacity)
    else:
        chunk = min(max(16, min(16 * depth, PREFILL_SAT)), cache_capacity)
    if role == "prefill":
        # nothing decodes here, so no interference budget to respect:
        # admit the full saturating pass every tick
        chunk = min(PREFILL_SAT, cache_capacity)
    elif role == "decode":
        # work arrives pre-filled over the fleet tier; keep only the
        # minimal chunk (shed/local work) and the full residency budget
        chunk = min(16, cache_capacity)
    return ServeConfig(batch=batch, cache_capacity=cache_capacity,
                       mode="gspmd" if resident else "elk_stream",
                       prefetch_depth=depth,
                       kv_dtype=kv_dtype, prefill_chunk=chunk,
                       steady_interval_s=knobs.interval_s,
                       oversub=oversub, slot_spill_s=slot_spill_s,
                       prefix_cache_bytes=prefix_bytes)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, mesh, params: PyTree,
                 scfg: ServeConfig):
        self.cfg = cfg
        self.mesh = mesh
        self.scfg = scfg
        fsdp = scfg.mode == "elk_stream"
        self.p_sh = param_shardings(params, mesh, fsdp=fsdp)
        self.params = jax.device_put(params, self.p_sh)

        self._spec = tfm.CacheSpec(
            capacity=scfg.cache_capacity, batch=scfg.batch,
            kv_dtype=jnp.dtype(scfg.kv_dtype))
        cache_shape = jax.eval_shape(lambda: tfm.init_cache(cfg, self._spec))
        self.c_sh = cache_shardings(cache_shape, mesh)

        bp = batch_axes(mesh)
        self._tok_sh = tok_sh = NamedSharding(mesh, P(bp))
        self._logit_sh = logit_sh = NamedSharding(mesh, P(bp, None, "model"))

        if scfg.mode == "elk_stream":
            from repro.serve.stream import streaming_decode_step

            def decode(params, token, cache):
                return streaming_decode_step(params, cfg, token, cache,
                                             mesh=mesh,
                                             prefetch=scfg.prefetch_depth)
        else:
            def decode(params, token, cache):
                return tfm.decode_step(params, cfg, token, cache, mesh=mesh)

        # the decode hot loop donates the cache: the compiled step aliases
        # it in-place instead of copying (L,B,Hkv,C,hd) every token
        self._decode = jax.jit(
            decode,
            in_shardings=(self.p_sh, tok_sh, self.c_sh),
            out_shardings=(logit_sh, self.c_sh),
            donate_argnums=(2,),
        )

        def prefill(params, tokens, cache, embeds=None, enc_embeds=None):
            kw = {}
            if embeds is not None:
                kw["embeds"] = embeds
            if enc_embeds is not None:
                kw["enc_embeds"] = enc_embeds
            return tfm.prefill(params, cfg, tokens, cache, mesh=mesh, **kw)

        self._prefill = jax.jit(prefill, donate_argnums=(2,))

        def prefill_fresh(params, tokens, embeds=None, enc_embeds=None):
            cache = tfm.init_cache(cfg, self._spec)
            return prefill(params, tokens, cache, embeds, enc_embeds)

        self._prefill_fresh = jax.jit(
            prefill_fresh, out_shardings=(logit_sh, self.c_sh))

        # -- continuous-batching state (built lazily by _ensure_slots) ----
        self.slot_cache: Optional[dict] = None
        self._chunk_jits: dict[int, Any] = {}
        # last-position logits (rows, V) of the latest ``step`` or
        # ``prefill_chunk``: what the slot path's greedy token came from
        self.last_logits: Optional[jax.Array] = None
        # routed rows per held expert (expert layers, E_held) of the
        # latest ``step(count_experts=True)``
        self.last_expert_rows: Optional[jax.Array] = None

    # -- public API --------------------------------------------------------
    def prefill(self, tokens: jax.Array, cache: Optional[dict] = None,
                **frontends) -> tuple[jax.Array, dict]:
        """Prefill the prompt.  With ``cache=None`` the initial cache is
        materialized inside the compiled step (nothing to copy); a cache
        passed explicitly is donated — use the returned one."""
        if cache is None:
            return self._prefill_fresh(self.params, tokens,
                                       frontends.get("embeds"),
                                       frontends.get("enc_embeds"))
        return self._prefill(self.params, tokens, cache,
                             frontends.get("embeds"),
                             frontends.get("enc_embeds"))

    def decode(self, token: jax.Array, cache: dict
               ) -> tuple[jax.Array, dict]:
        """One lock-step decode step.  ``cache`` is donated."""
        return self._decode(self.params, token, cache)

    def generate(self, prompts: jax.Array, steps: int,
                 greedy: bool = True) -> jax.Array:
        """prompts: (B, S0) -> (B, S0 + steps) greedy continuation."""
        if steps <= 0:
            return prompts
        fe = frontend_embeddings(self.cfg, prompts.shape[0])
        logits, cache = self.prefill(prompts, **fe)
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        out = [prompts, tok[:, None]]
        for _ in range(steps - 1):
            logits, cache = self.decode(tok, cache)
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            out.append(tok[:, None])
        return jnp.concatenate(out, axis=1)

    # -- slot-batched serving (continuous batching) ------------------------
    def _ensure_slots(self) -> None:
        if self.slot_cache is not None:
            return
        cfg, scfg, mesh = self.cfg, self.scfg, self.mesh
        self._slot_spec = dataclasses.replace(
            self._spec, batch=scfg.slots, per_slot=True)
        self._req_spec = dataclasses.replace(
            self._spec, batch=1, per_slot=True)
        slot_shape = jax.eval_shape(
            lambda: tfm.init_cache(cfg, self._slot_spec))
        self._slot_sh = slot_sh = cache_shardings(slot_shape, mesh)
        self._req_sh = req_sh = cache_shardings(
            jax.eval_shape(lambda: tfm.init_cache(cfg, self._req_spec)), mesh)
        self._rep = rep = NamedSharding(mesh, P())

        if scfg.mode == "elk_stream":
            from repro.serve.stream import streaming_decode_slots

            def decode_slots(params, token, cache):
                return streaming_decode_slots(params, cfg, token, cache,
                                              mesh=mesh,
                                              prefetch=scfg.prefetch_depth)
        else:
            def decode_slots(params, token, cache):
                return tfm.decode_slots(params, cfg, token, cache, mesh=mesh)

        def step(params, token, cache):
            logits, cache = decode_slots(params, token, cache)
            last = logits[:, -1, :]
            return jnp.argmax(last, axis=-1).astype(jnp.int32), last, cache

        def counted_step(params, token, cache):
            logits, cache, rows = tfm.decode_slots_counted(
                params, cfg, token, cache, mesh=mesh)
            last = logits[:, -1, :]
            return (jnp.argmax(last, axis=-1).astype(jnp.int32), last, cache,
                    rows)

        outs = (self._tok_sh,
                NamedSharding(mesh, P(batch_axes(mesh), "model")), slot_sh)
        self._step_slots = jax.jit(
            step,
            in_shardings=(self.p_sh, self._tok_sh, slot_sh),
            out_shardings=outs,
            donate_argnums=(2,),
        )
        self._counted_step = jax.jit(
            counted_step,
            in_shardings=(self.p_sh, self._tok_sh, slot_sh),
            out_shardings=outs + (rep,),
            donate_argnums=(2,),
        )
        self._insert = jax.jit(tfm.cache_insert_slot,
                               in_shardings=(slot_sh, rep, req_sh),
                               out_shardings=slot_sh, donate_argnums=(0,))
        self._evict = jax.jit(tfm.cache_evict_slot,
                              in_shardings=(slot_sh, rep),
                              out_shardings=slot_sh, donate_argnums=(0,))
        # extract reads the slot cache before _evict consumes it: no donate
        self._extract = jax.jit(tfm.cache_extract_slot,
                                in_shardings=(slot_sh, rep),
                                out_shardings=req_sh)
        self._req_cache0 = jax.jit(
            lambda: tfm.init_cache(cfg, self._req_spec),
            out_shardings=req_sh)
        self.slot_cache = jax.jit(
            lambda: tfm.init_cache(cfg, self._slot_spec),
            out_shardings=slot_sh)()

    def new_request_cache(self) -> dict:
        """Fresh single-request per-slot cache for chunked prefill."""
        self._ensure_slots()
        return self._req_cache0()

    def prefill_chunk(self, req_cache: dict, tokens: jax.Array
                      ) -> tuple[jax.Array, dict]:
        """Advance one request's prefill by a chunk of (1, T) tokens.
        Returns (greedy next token (1,), cache).  ``req_cache`` is donated;
        one jit per distinct T (the batcher quantizes chunk lengths to
        powers of two, so the set stays O(log prefill_chunk))."""
        self._ensure_slots()
        t = tokens.shape[1]
        if t not in self._chunk_jits:
            cfg, mesh = self.cfg, self.mesh

            def chunk(params, toks, cache):
                logits, cache = tfm.chunk_prefill(params, cfg, toks, cache,
                                                  mesh=mesh)
                last = logits[:, -1, :]
                return (jnp.argmax(last, axis=-1).astype(jnp.int32), last,
                        cache)

            self._chunk_jits[t] = jax.jit(
                chunk, in_shardings=(self.p_sh, self._rep, self._req_sh),
                out_shardings=(self._rep, self._rep, self._req_sh),
                donate_argnums=(2,))
        tok, self.last_logits, req_cache = self._chunk_jits[t](
            self.params, tokens, req_cache)
        return tok, req_cache

    def insert_slot(self, slot: int, req_cache: dict) -> None:
        """Splice a prefilled request into ``slot`` of the running batch
        (in place: the slot cache is donated through the insert)."""
        self._ensure_slots()
        self.slot_cache = self._insert(self.slot_cache,
                                       jnp.int32(slot), req_cache)

    def evict_slot(self, slot: int) -> dict:
        """Remove a finished (or preempted) request: reset the slot's
        position and mask its ring tags so the stale K/V is unreachable.
        Returns the evicted per-request state (KV ring + ``pos``/
        ``slot_pos``), which ``insert_slot``/``refill_slot`` round-trips
        bit-identically — callers that only finish a request can drop it."""
        self._ensure_slots()
        state = self._extract(self.slot_cache, jnp.int32(slot))
        self.slot_cache = self._evict(self.slot_cache, jnp.int32(slot))
        return state

    def offload_slot(self, slot: int) -> dict:
        """Spill ``slot`` to the backing tier: evict it and hand back a
        *host-resident copy* of its state (``np.array`` — a real copy, not
        a view, because every engine step donates its cache buffers).  The
        planned cost of this move is ``ServeConfig.slot_spill_s``."""
        return jax.tree.map(lambda a: np.array(a), self.evict_slot(slot))

    def refill_slot(self, slot: int, state: dict) -> None:
        """Refill ``slot`` from an offloaded state (host or device).  Host
        leaves are copied onto fresh device buffers first so a stored state
        (e.g. a prefix-store snapshot) is never aliased into the donated
        slot cache."""
        self._ensure_slots()
        state = jax.device_put(state, self._req_sh, may_alias=False)
        self.slot_cache = self._insert(self.slot_cache,
                                       jnp.int32(slot), state)

    def slot_state_bytes(self) -> int:
        """Bytes one ``offload_slot``/``refill_slot`` moves across the tier
        boundary (one slot's KV ring + metadata)."""
        self._ensure_slots()
        shape = jax.eval_shape(lambda: tfm.init_cache(self.cfg,
                                                      self._req_spec))
        return int(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(shape)))

    def step(self, tokens: jax.Array, count_experts: bool = False
             ) -> jax.Array:
        """One continuous-batching decode step over the mutable slot batch:
        tokens (slots,) int32 -> greedy next token per slot (slots,).  The
        slot cache advances in place (donated buffers, no copy).  With
        ``count_experts`` (a held share of sigmoid-routed experts, one
        device's mesh) the step is a program of its own that also keeps
        the rows it routed to each held expert of every expert layer in
        ``last_expert_rows``; the plain step counts nothing."""
        self._ensure_slots()
        if count_experts:
            (tok, self.last_logits, self.slot_cache,
             self.last_expert_rows) = self._counted_step(
                self.params, tokens, self.slot_cache)
            return tok
        tok, self.last_logits, self.slot_cache = self._step_slots(
            self.params, tokens, self.slot_cache)
        return tok
