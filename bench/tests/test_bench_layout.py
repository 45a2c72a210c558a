"""BENCHMARK.json and the files it names: everything resolves by name."""

import importlib
import json
import re
from pathlib import Path

import pytest

from bench import harness, model, traffic
from bench import weights as W
from bench.families import dense_gqa

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert (ROOT / BENCH["command"][1]).is_file()
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    w = {x["name"]: x for x in BENCH["workloads"]}[cell]
    hf = model.load_config(w["config"])
    assert traffic.load_mix(w["traffic"])
    assert set(harness.load_limits(cell)) >= {"logit_gap", "min_tokens"}
    adapter = importlib.import_module(f"bench.families.{hf['family']}")
    for fn in ("model_config", "program_params", "chunk_flops"):
        assert callable(getattr(adapter, fn))
    ref = importlib.import_module(f"bench.references.{hf['family']}")
    assert callable(ref.served_gaps)
    for m in harness.cell_metrics(BENCH, cell, trace=True):
        reader = importlib.import_module(
            f"bench.metrics.{m['name'].split('.')[0]}")
        assert callable(reader.read)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell, False)}
    layer = harness.cell_metrics(BENCH, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    assert all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_as_run(conf):
    """The file holds the configuration as it runs: every cut is listed in
    ``reduced``, no width is cut, and its family's adapter maps it."""
    hf = json.loads((ROOT / conf["file"]).read_text())
    assert hf["source"] == conf["source"]
    assert sorted(hf["reduced"]) == sorted(conf["reduced"])
    for key, cut in hf["reduced"].items():
        assert hf[key] == cut["here"] and not key.endswith(("_dim", "_size"))
    cfg = model.model_config(hf)
    assert (cfg.d_model, cfg.num_layers, cfg.vocab_size) == (
        hf["hidden_size"], hf["num_hidden_layers"], hf["vocab_size"])


DENSE = [c for c in BENCH["configs"] if json.loads(
    (ROOT / c["file"]).read_text())["family"] == "dense_gqa"]


@pytest.mark.parametrize("conf", DENSE, ids=lambda c: c["name"])
def test_dense_gqa_adapter_maps_every_width(conf):
    """The dense family's adapter gives the program every published width."""
    hf = json.loads((ROOT / conf["file"]).read_text())
    cfg = dense_gqa.model_config(hf)
    z = W.dims(hf)
    assert (cfg.d_model, cfg.d_ff, cfg.resolved_head_dim) == (
        z["d"], z["ff"], z["hd"])
    assert (cfg.num_heads, cfg.num_kv_heads) == (z["h"], z["kv"])
    assert cfg.norm_eps == hf["rms_norm_eps"]
