"""Operations and bytes at published widths, against hand-computed values
(the dense family adapter's token FLOPs among them), and shares that stay
within 100% on a synthetic trace as fast as the chip's peaks allow."""

import math

import pytest

from bench import counts, model, trace_reduce as R
from bench.families import dense_gqa
from bench.metrics import RunData, fused_mlp_roofline, mfu
from bench.peaks import peaks
from bench.window import Tick, Window

V5E = "TPU v5 lite"
QWEN_CALL = (
    "%fused_mlp_kernel.7 = bf16[12,5120]{1,0:T(8,128)(2,1)S(1)} custom-call("
    "bf16[12,5120]{1,0:T(8,128)(2,1)S(1)} %copy-done.7, bf16[5120,17408]"
    "{1,0:T(8,128)(2,1)} %a, bf16[5120,17408]{1,0:T(8,128)(2,1)} %b, "
    "bf16[17408,5120]{1,0:T(8,128)(2,1)} %c), custom_call_target="
    "\"tpu_custom_call\"")


def test_fused_mlp_qwen3_decode_by_hand():
    flops, nbytes = counts.fused_mlp(12, 5120, 17408)
    assert flops == 2 * 12 * 5120 * 17408 * 3 == 6_417_285_120
    assert nbytes == 2 * (3 * 5120 * 17408 + 2 * 12 * 5120) == 535_019_520


def test_fused_mlp_staged_operands_leave_the_bytes():
    """danube's decode call with w_up and the rows already in VMEM."""
    _, nbytes = counts.fused_mlp(8, 2560, 6912,
                                 staged=frozenset({"x", "w_up", "out"}))
    assert nbytes == 2 * (2 * 2560 * 6912) == 70_778_880


def test_call_cost_parses_the_hlo():
    flops, nbytes = fused_mlp_roofline.call_cost(QWEN_CALL)
    assert flops == 6_417_285_120
    # x and the result are in VMEM (S(1)); the three weights are read
    assert nbytes == 2 * 3 * 5120 * 17408


def test_token_flops_qwen3_by_hand():
    hf = model.load_config("qwen3_14b")
    per_layer = 2 * (5120 * 40 * 128 + 2 * 5120 * 8 * 128 + 40 * 128 * 5120
                     + 3 * 5120 * 17408)
    assert per_layer == 660_602_880
    # the first prompt token (context 1) with the head
    assert dense_gqa.chunk_flops(hf, 0, 1, head=True) == (
        12 * (660_602_880 + 4 * 40 * 128) + 2 * 5120 * 151936)
    # a 128-token chunk from position 1000: context 1001..1128, no head
    attn = 4 * 40 * 128 * sum(range(1001, 1129))
    assert dense_gqa.chunk_flops(hf, 1000, 128, head=False) == 12 * (
        128 * 660_602_880 + attn)


def test_token_flops_danube_window_by_hand():
    hf = model.load_config("h2o_danube_1_8b")
    per_layer = 2 * (2560 * 2560 * 2 + 2 * 2560 * 8 * 80 + 3 * 2560 * 6912)
    assert per_layer == 138_936_320
    # position 9999 sees only the 4096-token window
    assert dense_gqa.chunk_flops(hf, 9999, 1, head=True) == (
        24 * (138_936_320 + 4 * 32 * 80 * 4096) + 2 * 2560 * 32000)
    assert dense_gqa.chunk_flops(hf, 99, 1, head=False) == 24 * (
        138_936_320 + 4 * 32 * 80 * 100)


def _fast_trace(n_calls=24):
    """A step program whose kernel calls each take exactly their bound."""
    p = peaks(V5E)
    bound = counts.roofline_s(*fused_mlp_roofline.call_cost(QWEN_CALL),
                              p["bf16_flops"], p["hbm_bytes_per_s"])
    ops, t = [], 0.0
    for _ in range(n_calls):
        ops.append(R.Span("fused_mlp_kernel", t, t + bound, QWEN_CALL))
        t += bound
    dev = R.Device(modules=[R.Span("jit_step(1)", 0.0, t)], ops=ops)
    host = [R.Span("bench.tick", 0.0, t)]
    return R.Trace([dev], host), t


def test_shares_stay_within_100_on_a_fast_trace():
    trace, t = _fast_trace()
    run = RunData(win=None, trace=trace, chips=1, device_kind=V5E)
    share = fused_mlp_roofline.read(run)
    assert math.isclose(share, 100.0, rel_tol=1e-9)
    assert trace.idle_share() == pytest.approx(0.0, abs=1e-12)
    assert trace.busy_s() == pytest.approx(trace.window_s)
    win = Window(seconds=1.0, slots=1)
    win.ticks = [Tick(0.0, 1.0, 1, 0, peaks(V5E)["bf16_flops"])]
    assert math.isclose(mfu.read(RunData(win, None, 1, V5E)), 100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("TPU v9 imaginary")
