"""Tiny configurations and mixes for running the harness on the CPU."""

import json
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent


def qwen_tiny() -> dict:
    """qwen3_14b's file with every size cut to a CPU test's scale."""
    hf = json.loads((BENCH / "configs" / "qwen3_14b.json").read_text())
    hf.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
              vocab_size=8192,
              serving={"slots": 3, "ring_tokens": 128, "prefill_chunk": 128})
    return hf


def danube_tiny() -> dict:
    """h2o_danube_1_8b's file cut down, with a 24-token window and a ring
    of window + chunk, so that long prompts wrap the ring."""
    hf = json.loads((BENCH / "configs" / "h2o_danube_1_8b.json").read_text())
    hf.update(hidden_size=80, intermediate_size=96, num_attention_heads=4,
              num_key_value_heads=2, num_hidden_layers=2, vocab_size=8192,
              sliding_window=24,
              serving={"slots": 2, "ring_tokens": 152, "prefill_chunk": 128})
    return hf


CHAT = {"prompt": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                   "min": 4, "max": 40},
        "output": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                   "min": 4, "max": 64},
        "max_total": 128, "arrivals": {"kind": "poisson", "rate_per_s": 4.0},
        "prefilled_slots": 2}

LONG = {"prompt": {"dist": "lognormal", "median": 150, "sigma": 0.4,
                   "min": 40, "max": 300},
        "output": {"dist": "lognormal", "median": 40, "sigma": 0.4,
                   "min": 20, "max": 80},
        "arrivals": {"kind": "backlog", "requests": 6},
        "prefilled_slots": "all"}


def cpu_device(chips):
    """Stands in for the harness's look for a chip."""
    return types.SimpleNamespace(platform="cpu", device_kind="TPU v5 lite")


class CountingClock:
    """A clock that moves ``step`` seconds each time it is read, so a
    window on the CPU serves the same ticks however busy the machine is."""

    def __init__(self, step: float = 0.004):
        self.step, self.n = step, 0

    def __call__(self) -> float:
        self.n += 1
        return self.n * self.step


# Limits of the tiny configurations, set from their CPU readings on the
# counting clock (seeds 11-15 and 2**33 + 5).  Widest gap: the program at
# most 0.038 (qwen) and 0.031 (danube); the controls at least 0.279
# (fp8_matmul) and 0.452 (fp8).  Logit error (median over tokens): the
# program 0.0090-0.0097 and 0.0119-0.0124; fp8_matmul at least 0.106 and
# 0.116, fp8 at least 0.145 and 0.165 (int8_matmul 0.028-0.032 passes).
CASES = {"qwen3_14b.chat": (qwen_tiny, CHAT,
                            {"logit_gap": 0.12, "logit_err": 0.04,
                             "min_tokens": 20}),
         "h2o_danube_1_8b.long_decode": (danube_tiny, LONG,
                                         {"logit_gap": 0.12,
                                          "logit_err": 0.04,
                                          "min_tokens": 20})}
