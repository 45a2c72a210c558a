"""The family plug-in point.

The dense GQA adapter reproduces, bit for bit, the weights and FLOP counts
that ``bench/model.py`` and ``bench/counts.py`` gave before the adapters
existed (recorded from that tree); the generic modules name no
architecture; and a family that is not dense, a toy mixture of experts
on the program's own MoE FFN, enters the harness by new files alone: its
run is correct on the CPU and fails with a token altered where it is
produced."""

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness, model
from bench.families import dense_gqa
from bench_tiny import CHAT, CountingClock, cpu_device

HERE = Path(__file__).resolve().parent
BENCH, ROOT = HERE.parent, HERE.parents[1]

# sha256 over each leaf's path, dtype, shape and bytes of the parameter
# tree, jitted from the seed's key (``DIGEST``), before the move into the
# adapter
WEIGHTS = {
    ("qwen", 11):
        "a376fcfc01fe978998f49609aa1292a6bf9c300b05e8358768326c05c233ab2b",
    ("qwen", 2 ** 33 + 5):
        "9a469173b41a952e2ffc934220538abead81bf542be5bb19cd1ee025a3a04b31",
    ("danube", 11):
        "91da0916042a0b550bc060bf744bcc47332a32763ea784e75a7a0809f5a6e30e",
    ("danube", 2 ** 33 + 5):
        "1045a7c877f43bdc8adac686bde7265478b19059c9ab8e0da1e73384e261d59d",
}

# counts.chunk_flops(hf, start, tokens, head=...) before the move; danube
# across its 4096-token window's edge
FLOPS = [
    ("qwen3_14b", 0, 1, True, 9483304960.0),
    ("qwen3_14b", 1000, 128, False, 1048172298240.0),
    ("qwen3_14b", 383, 1, True, 9577431040.0),
    ("qwen3_14b", 4095, 1, True, 10489692160.0),
    ("h2o_danube_1_8b", 0, 1, True, 3498557440.0),
    ("h2o_danube_1_8b", 99, 1, False, 3359047680.0),
    ("h2o_danube_1_8b", 4000, 128, False, 554540728320.0),
    ("h2o_danube_1_8b", 4094, 1, True, 4504698880.0),
    ("h2o_danube_1_8b", 4094, 2, False, 8681963520.0),
    ("h2o_danube_1_8b", 4095, 1, True, 4504944640.0),
    ("h2o_danube_1_8b", 4096, 1, True, 4504944640.0),
    ("h2o_danube_1_8b", 9999, 1, True, 4504944640.0),
]


DIGEST = """
import hashlib, json, sys
import jax, numpy as np
from bench import weights as W
from bench.families import dense_gqa
from bench_tiny import danube_tiny, qwen_tiny

out = {}
for tiny, make in (("qwen", qwen_tiny), ("danube", danube_tiny)):
    hf = make()
    cfg = dense_gqa.model_config(hf)
    draw = jax.jit(lambda k: dense_gqa.program_params(k, hf, cfg))
    for seed in json.loads(sys.argv[1]):
        h = hashlib.sha256()
        tree = draw(W.seed_key(seed))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            a = np.asarray(leaf)
            h.update(jax.tree_util.keystr(path).encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        out[f"{tiny} {seed}"] = h.hexdigest()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def digests():
    """The hashes, from a process of their own: compiling the weights
    program beside a later engine in one process makes the engine's
    zero-argument cache program expect an argument (PERF.md section 7)."""
    seeds = sorted({seed for _, seed in WEIGHTS})
    proc = subprocess.run(
        [sys.executable, "-c", DIGEST, json.dumps(seeds)], cwd=HERE,
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join([str(HERE), str(ROOT),
                                            str(ROOT / "src")])})
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("tiny,seed", sorted(WEIGHTS))
def test_dense_weights_match_the_recorded_draws(tiny, seed, digests):
    assert digests[f"{tiny} {seed}"] == WEIGHTS[tiny, seed]


@pytest.mark.parametrize("config,start,tokens,head,flops", FLOPS)
def test_dense_chunk_flops_match_the_recorded_values(config, start, tokens,
                                                     head, flops):
    hf = model.load_config(config)
    assert dense_gqa.chunk_flops(hf, start, tokens, head=head) == flops


@pytest.mark.parametrize("module", ["model.py", "counts.py", "harness.py"])
def test_generic_code_names_no_architecture(module):
    """Only the configuration's own keys are read; what is published is
    the adapters' and the references' to read."""
    src = (BENCH / module).read_text()
    keys = set(re.findall(r"hf\[[\"'](\w+)[\"']\]", src)) | set(
        re.findall(r"hf\.get\([\"'](\w+)[\"']", src))
    assert keys <= {"family", "serving"}, keys
    for name in ("ForCausalLM", "num_key_value_heads", "intermediate_size",
                 "head_dim", "sliding_window", "num_attention_heads",
                 "dense_gqa", "qwen", "mistral", "llama"):
        assert name not in src, name


# A toy MoE decoder at a CPU test's size: 4 experts, 2 per token.
TOY = {"architectures": ["ToyMoeForCausalLM"], "model_type": "toy_moe",
       "family": "toy_moe", "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
       "num_experts_per_tok": 2, "moe_intermediate_size": 32,
       "num_hidden_layers": 2, "vocab_size": 8192, "rms_norm_eps": 1e-6,
       "rope_theta": 10000.0,
       "serving": {"slots": 3, "ring_tokens": 128, "prefill_chunk": 128}}

# Set from CPU readings on the counting clock (seeds 11-22 and 2**33 + 5).
# Widest gap: the program 0.012-1.568, the token-plus-one fault 5.85-7.74
# (11-15 and 2**33 + 5).  The program's widest gaps sit on router
# near-ties that bfloat16 flips (1.568 where the reference's 2nd and 3rd
# expert logits lie 0.0005 apart); in float32 the program's path reads a
# gap of 0 and an error of 5.5e-7.  Logit error: the program 0.0084-0.0103,
# the tiny dense cases' limit (the toy has no precision control).
TOY_LIMITS = {"logit_gap": 3.2, "logit_err": 0.04, "min_tokens": 20}


@pytest.fixture
def toy_family(monkeypatch):
    """The toy family's adapter and reference, under the names the
    harness imports for ``"family": "toy_moe"``."""
    import toy_moe_family
    import toy_moe_reference

    monkeypatch.setitem(sys.modules, "bench.families.toy_moe",
                        toy_moe_family)
    monkeypatch.setitem(sys.modules, "bench.references.toy_moe",
                        toy_moe_reference)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")


def _toy_run():
    return harness.execute("qwen3_14b.chat", 2 ** 33 + 5, 3.0, False,
                           t_start=time.perf_counter(),
                           chips_check=cpu_device,
                           overrides={"config": TOY, "mix": CHAT,
                                      "limits": TOY_LIMITS},
                           clock=CountingClock())


def test_toy_moe_family_run_is_correct(toy_family):
    r = _toy_run()
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["checks"]["tokens_checked"]["value"] >= 100
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_toy_moe_family_token_altered_where_produced_fails(toy_family,
                                                           monkeypatch):
    """Every third decode step hands back each slot's token plus one."""
    from repro.serve.engine import ServeEngine

    step, calls = ServeEngine.step, [0]

    def altered(self, tokens):
        out = step(self, tokens)
        calls[0] += 1
        return (out + 1) % self.cfg.vocab_size if calls[0] % 3 == 0 else out

    monkeypatch.setattr(ServeEngine, "step", altered)
    r = _toy_run()
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > TOY_LIMITS["logit_gap"]
