"""The controls at a tiny size on the CPU: the plain reference computed
in fp8 (every weight product's inputs, or every tensor), put in the
program's place and judged by the harness's own check at the same served
positions, is not correct where the program is, on three seeds; nor is
the reference with a token altered where it is produced."""

import time

import pytest

from bench import harness, model
from bench_tiny import CASES, CountingClock


@pytest.mark.parametrize("cell", sorted(CASES))
def test_fp8_control_fails_where_the_program_passes(cell, monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    config, mix, limits = CASES[cell]
    hf = config()
    split = {}
    eng = harness.build_program(hf, 11, False, split)
    for seed in (11, 12, 13):
        if seed != 11:
            model.reseed(eng, seed, hf)
        out = harness.measure(eng, hf, mix, seed, 3.0, split=split,
                              t_start=time.perf_counter(),
                              clock=CountingClock())
        seqs = harness.sample(list(out["runner"].b.completed),
                              out["runner"], seed, out["probe"])
        res = harness.check(hf, seed, seqs, limits,
                            ("fp8_matmul", "fp8", "token_plus_one"))
        assert res["program"][0], (seed, res["program"])
        for control in ("fp8_matmul", "fp8"):
            ok, checks = res[control]
            assert not ok, (seed, control, checks)
            assert checks["logit_err"]["value"] > limits["logit_err"]
        ok, checks = res["token_plus_one"]
        assert not ok and checks["logit_gap"]["value"] > 1.0, checks
