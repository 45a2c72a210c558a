"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles, all in
interpret=True mode (assignment requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.elk_matmul.kernel import elk_matmul
from repro.kernels.elk_matmul.ref import matmul_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref

TOL = {jnp.float32: 2e-4, jnp.bfloat16: 6e-2}


def _tol(dtype, ref):
    return TOL[dtype] * (float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
                         + 1.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mnk", [(128, 128, 128), (256, 64, 512),
                                 (100, 60, 70), (33, 129, 257)])
def test_elk_matmul(mnk, dtype, rng):
    m, n, k = mnk
    x = jax.random.normal(rng, (m, k), dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    out = elk_matmul(x, y, bm=64, bn=64, bk=64, interpret=True)
    ref = matmul_ref(x, y)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err <= _tol(dtype, ref), (mnk, dtype, err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    # (B, Hq, Hkv, S, D, causal, window)
    (2, 4, 2, 128, 32, True, 0),       # GQA causal
    (1, 4, 4, 128, 16, True, 48),      # MHA sliding window
    (1, 2, 1, 64, 32, False, 0),       # bidirectional MQA
    (1, 8, 8, 256, 64, True, 0),       # MHA causal, bigger head
])
def test_flash_attention(case, dtype, rng):
    b, hq, hkv, s, d, causal, win = case
    q = jax.random.normal(rng, (b, hq, s, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=win,
                          bq=32, bk=32, interpret=True)
    ref = mha_ref(q, k, v, causal=causal, window=win)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err <= _tol(dtype, ref), (case, dtype, err)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    # (B, Hq, Hkv, C, D, window, pos)
    (2, 8, 2, 128, 32, 0, 100),        # partial cache
    (1, 4, 4, 256, 16, 64, 300),       # ring buffer + window
    (2, 2, 1, 64, 64, 0, 64),          # exactly full cache
])
def test_decode_attention(case, dtype, rng):
    b, hq, hkv, c, d, win, pos = case
    q = jax.random.normal(rng, (b, hq, d), dtype)
    kc = jax.random.normal(jax.random.PRNGKey(4), (b, hkv, c, d), dtype)
    vc = jax.random.normal(jax.random.PRNGKey(5), (b, hkv, c, d), dtype)
    idx = jnp.arange(c)
    if pos <= c:
        slot_pos = jnp.where(idx < pos, idx, 2 ** 30)
    else:
        start = pos - c
        slot_pos = start + (idx - start) % c
    out = decode_attention(q, kc, vc, slot_pos, pos, window=win, bk=64,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, slot_pos, pos, window=win)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err <= _tol(dtype, ref), (case, dtype, err)


def test_flash_matches_model_attention(rng):
    """The kernel and the model's reference GQA path agree, so swapping the
    kernel in on TPU changes performance, not semantics."""
    from repro.models.layers import AttnSpec, attn_mask_bias, gqa_attention
    b, hq, hkv, s, d = 1, 4, 2, 64, 32
    q = jax.random.normal(rng, (b, hq, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(7), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(8), (b, hkv, s, d), jnp.float32)
    spec = AttnSpec(hq, hkv, d, causal=True)
    pos = jnp.arange(s)
    bias = attn_mask_bias(spec, pos, pos)
    ref = gqa_attention(q, k, v, bias, spec)
    out = flash_attention(q, k, v, causal=True, bq=32, bk=32,
                          interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    # (M, D, FF, gated, bias, act)
    (128, 128, 256, True, False, "silu"),     # GLU, block-aligned
    (64, 96, 200, True, False, "silu"),       # GLU, non-multiple of bf
    (100, 80, 144, False, True, "relu"),      # plain + biases, ragged m
    (33, 64, 257, False, False, "gelu"),      # plain, everything ragged
])
def test_fused_mlp_kernel(case, dtype, rng):
    from repro.kernels.fused_mlp.kernel import fused_mlp_kernel
    from repro.kernels.fused_mlp.ref import composed_ref
    m, d, ff, gated, bias, act = case
    keys = jax.random.split(rng, 6)
    x = jax.random.normal(keys[0], (m, d), dtype)
    w_up = jax.random.normal(keys[1], (d, ff), dtype) / jnp.sqrt(d)
    w_down = jax.random.normal(keys[2], (ff, d), dtype) / jnp.sqrt(ff)
    kw = {}
    if gated:
        kw["w_gate"] = jax.random.normal(keys[3], (d, ff), dtype) / jnp.sqrt(d)
    if bias:
        kw["b_up"] = jax.random.normal(keys[4], (ff,), dtype)
        kw["b_down"] = jax.random.normal(keys[5], (d,), dtype)
    out = fused_mlp_kernel(x, w_up, w_down, act=act, bm=32, bf=128,
                           interpret=True, **kw)
    ref = composed_ref(x, w_up, w_down, act=act, **kw)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err <= _tol(dtype, ref), (case, dtype, err)


def test_fused_mlp_batched_lead_dims(rng):
    """The wrapper flattens (B, S, D) leads; parity must survive that."""
    from repro.kernels.fused_mlp.kernel import fused_mlp_kernel
    from repro.kernels.fused_mlp.ref import composed_ref
    x = jax.random.normal(rng, (2, 40, 64), jnp.float32)
    w_up = jax.random.normal(jax.random.PRNGKey(1), (64, 160), jnp.float32)
    w_gate = jax.random.normal(jax.random.PRNGKey(2), (64, 160), jnp.float32)
    w_down = jax.random.normal(jax.random.PRNGKey(3), (160, 64), jnp.float32)
    out = fused_mlp_kernel(x, w_up, w_down, w_gate=w_gate, act="silu",
                           bm=32, bf=64, interpret=True)
    ref = composed_ref(x, w_up, w_down, w_gate=w_gate, act="silu")
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) <= _tol(jnp.float32, ref)


def test_fused_mlp_matches_model_mlp(rng):
    """fused_mlp_ref is the exact einsum composition models.layers.mlp used
    before the fused path: CPU model outputs are bit-identical by
    construction, and the kernel agrees within kernel tolerance."""
    from repro.kernels.fused_mlp.kernel import fused_mlp_kernel
    from repro.kernels.fused_mlp.ref import fused_mlp_ref
    x = jax.random.normal(rng, (48, 64), jnp.float32)
    w_up = jax.random.normal(jax.random.PRNGKey(11), (64, 128), jnp.float32)
    w_gate = jax.random.normal(jax.random.PRNGKey(12), (64, 128), jnp.float32)
    w_down = jax.random.normal(jax.random.PRNGKey(13), (128, 64), jnp.float32)
    ref = fused_mlp_ref(x, w_up, w_down, w_gate=w_gate, act="silu")
    out = fused_mlp_kernel(x, w_up, w_down, w_gate=w_gate, act="silu",
                           bm=32, bf=64, interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) <= _tol(jnp.float32, ref)


def test_fused_mlp_ff_block_divides_ff_and_fits_vmem():
    """The FF block divides FF where a multiple of 128 can (no weight is
    padded per call) and steps down until the double-buffered step fits
    the VMEM budget; with no such divisor it keeps the requested block."""
    from repro.kernels.fused_mlp.kernel import (_VMEM_BUDGET, _ff_block,
                                                _vmem_bytes)
    # qwen3_14b GLU (d 5120, ff 17408): the 512 slab divides and fits
    assert _ff_block(512, 128, 5120, 17408, 5120, 2, 2) == 512
    # d 16384 / ff 53248 (llama-3-405b widths): 512 and 256 overflow
    bf = _ff_block(512, 128, 16384, 53248, 16384, 2, 2)
    assert bf == 128 and 53248 % bf == 0
    assert _vmem_bytes(128, bf, 16384, 16384, 2, 2) <= _VMEM_BUDGET
    assert _vmem_bytes(128, 256, 16384, 16384, 2, 2) > _VMEM_BUDGET
    assert _ff_block(128, 32, 96, 200, 96, 2, 4) == 128


def test_vmem_plan_within_budget():
    from repro.core.integration import vmem_plan
    plan = vmem_plan(8192, 8192, 8192)
    assert plan.vmem_bytes <= 128 * 1024 * 1024
    assert plan.bm % 128 == 0 and plan.bn % 128 == 0 and plan.bk % 128 == 0
    # bigger budget must never increase HBM traffic
    small = vmem_plan(8192, 8192, 8192, vmem_budget=16 * 2 ** 20)
    assert plan.hbm_traffic <= small.hbm_traffic
