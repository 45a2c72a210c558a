"""Deviceless compiles for TPU v5e at qwen3_14b's published widths.

The chip's own compiler runs here against a described (not attached)
v5e, so what it refuses — a kernel over its VMEM limit, a Mosaic call the
partitioner cannot split — fails a test instead of a chip run.  The
topology is described inside a fixture, never on import: only the worker
that runs this file loads the TPU compiler, and every test skips where the
topology cannot be described.  Code that asks the backend still sees the
CPU, so the tests steer kernel dispatch to the compiled kernel themselves.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.distributed.sharding import cache_shardings, param_shardings
from repro.kernels import dispatch
from repro.kernels.fused_mlp.kernel import fused_mlp_kernel
from repro.kernels.fused_mlp.ops import fused_mlp
from repro.models import transformer as T

QWEN3 = get_config("qwen3_14b")
SLOTS, CAPACITY, CHUNK = 8, 4096, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    def restore():
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    restore()


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)


def _mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]).reshape(n, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _shapes(tree, shardings):
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, shardings)


def _one_layer(topo):
    """qwen3_14b at every published width, one layer, on one chip."""
    cfg = dataclasses.replace(QWEN3, num_layers=1)
    mesh = _mesh(topo, 1)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    params = _shapes(shapes, param_shardings(shapes, mesh, fsdp=True))
    return cfg, mesh, params


def _cache(cfg, mesh, batch):
    shapes = jax.eval_shape(lambda: T.init_cache(cfg, T.CacheSpec(
        capacity=CAPACITY, batch=batch, per_slot=True)))
    return _shapes(shapes, cache_shardings(shapes, mesh))


def _assert_scoped(hlo: str) -> None:
    """The chip's compiler keeps the model's named scopes in the ops'
    metadata, where a profiler trace reads them (``tf_op``)."""
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("layer", "attn", "mlp", "kv_ring", "head"):
        assert any(f"/{scope}/" in n for n in names), scope


@pytest.mark.parametrize("m", [SLOTS, 128])     # decode slots, prefill chunk
def test_fused_mlp_fits_vmem_at_qwen3_widths(topo, m):
    one = SingleDeviceSharding(topo.devices[0])
    d, ff = QWEN3.d_model, QWEN3.d_ff

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    compiled = fused_mlp_kernel.lower(sds(m, d), sds(d, ff), sds(ff, d),
                                      sds(d, ff), act="silu").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_mlp_partitions_over_four_chips(topo, on_tpu):
    """Decode rows split over a (data=4, model=1) mesh: the kernel runs per
    shard instead of asking the partitioner to split a Mosaic call."""
    mesh = _mesh(topo, 4)
    d, ff = QWEN3.d_model, QWEN3.d_ff
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((SLOTS, 1, d), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    w = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=rep)
         for s in ((d, ff), (ff, d), (d, ff))]
    f = jax.jit(lambda x, wu, wd, wg: fused_mlp(x, wu, wd, w_gate=wg,
                                                 mesh=mesh))
    assert "tpu_custom_call" in f.lower(x, *w).compile().as_text()


def test_slot_decode_step_calls_kernel(topo, on_tpu):
    cfg, mesh, params = _one_layer(topo)
    tok = jax.ShapeDtypeStruct((SLOTS,), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    step = jax.jit(lambda p, t, c: T.decode_slots(p, cfg, t, c, mesh=mesh),
                   donate_argnums=(2,))
    compiled = step.lower(params, tok, _cache(cfg, mesh, SLOTS)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_scoped(compiled.as_text())


def test_chunk_prefill_step_calls_kernel(topo, on_tpu):
    cfg, mesh, params = _one_layer(topo)
    toks = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    chunk = jax.jit(lambda p, t, c: T.chunk_prefill(p, cfg, t, c, mesh=mesh),
                    donate_argnums=(2,))
    compiled = chunk.lower(params, toks, _cache(cfg, mesh, 1)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_scoped(compiled.as_text())


# the benchmark's configurations: (config, layers, slots, ring tokens)
BENCH_STEPS = {
    "h2o_danube_1_8b": (get_config("h2o_danube_1_8b"), 24, 8, 4224),
    "qwen3_14b": (QWEN3, 12, 12, 4096),
    # one chip's share: 12 of each layer's 384 experts, a latent ring
    "kimi_k2_1t_a32b": (dataclasses.replace(get_config("kimi_k2_1t_a32b"),
                                            moe_experts_held=12),
                        6, 16, 12288),
}


def _ring_moves(hlo: str, shape: tuple) -> list[str]:
    """The instructions of a compiled module that copy or concatenate
    into a buffer of ``shape``."""
    ring = "[" + ",".join(map(str, shape)) + "]"
    moves = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = (.*?) ([\w-]+)\(", line)
        if m and m.group(2) in ("copy", "copy-start", "copy-done",
                                "concatenate") and ring in m.group(1):
            moves.append(line.strip()[:120])
    return moves


@pytest.mark.parametrize("name", list(BENCH_STEPS))
def test_slot_decode_step_keeps_ring_in_place(topo, on_tpu, name):
    """At a benchmark configuration's widths, depth, slots and ring, the
    slot decode step writes each layer's new rows into the stacked ring
    where it lies: its temporaries stay under one layer's ring plus one
    layer's weights, and nothing copies or concatenates a buffer of the
    stacked ring's shape."""
    base, layers, slots, capacity = BENCH_STEPS[name]
    cfg = dataclasses.replace(base, num_layers=layers)
    mesh = _mesh(topo, 1)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    params = _shapes(shapes, param_shardings(shapes, mesh, fsdp=True))
    cache_shapes = jax.eval_shape(lambda: T.init_cache(cfg, T.CacheSpec(
        capacity=capacity, batch=slots, per_slot=True)))
    cache = _shapes(cache_shapes, cache_shardings(cache_shapes, mesh))
    tok = jax.ShapeDtypeStruct((slots,), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    step = jax.jit(lambda p, t, c: T.decode_slots(p, cfg, t, c, mesh=mesh),
                   donate_argnums=(2,))
    compiled = step.lower(params, tok, cache).compile()

    rings = [cache_shapes[k] for k in ("k", "v", "ckv", "kpe")
             if k in cache_shapes]
    layer_ring = sum(r.size * r.dtype.itemsize for r in rings) // layers
    layer_weights = sum(a.size * a.dtype.itemsize
                        for a in jax.tree.leaves(shapes["blocks"])) // layers
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_ring + layer_weights, (temp, layer_ring,
                                               layer_weights)
    for ring in rings:
        assert not _ring_moves(compiled.as_text(), ring.shape)
