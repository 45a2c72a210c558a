"""The traffic generator: seeded, the same work in the same order for
every seed, lengths inside their clips and the ring's limits."""

import numpy as np
import pytest

from bench import model, traffic

BIG_SEED = 2 ** 31 + 12345      # seeds may exceed 32 signed bits


def _gen(mix, seed, hf):
    return traffic.generate(traffic.load_mix(mix), seed=seed, seconds=51,
                            slots=hf["serving"]["slots"],
                            vocab=hf["vocab_size"])


@pytest.mark.parametrize("config,mix", [("qwen3_14b", "chat"),
                                        ("h2o_danube_1_8b", "long_decode")])
def test_seeded_same_work_other_order(config, mix):
    """The same seed gives the same inputs; another seed draws other token
    ids for the same lengths, gaps and shares in the same order."""
    hf = model.load_config(config)
    a, b = _gen(mix, BIG_SEED, hf), _gen(mix, BIG_SEED, hf)
    c = _gen(mix, BIG_SEED + 1, hf)
    for x, y in zip(a.arrivals + a.prefilled, b.arrivals + b.prefilled):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
        assert x.arrival_s == y.arrival_s and np.array_equal(x.done, y.done)
    for x, z in zip(a.arrivals + a.prefilled, c.arrivals + c.prefilled):
        assert len(x.prompt) == len(z.prompt) and x.max_new == z.max_new
        assert x.arrival_s == z.arrival_s and len(x.done) == len(z.done)
    assert not all(np.array_equal(x.prompt, z.prompt)
                   for x, z in zip(a.arrivals, c.arrivals))
    assert len({len(r.prompt) for r in a.arrivals[:traffic.BLOCK]}) > 1


@pytest.mark.parametrize("config,mix", [("qwen3_14b", "chat"),
                                        ("h2o_danube_1_8b", "long_decode")])
def test_lengths_inside_clips_and_ring(config, mix):
    hf = model.load_config(config)
    spec = traffic.load_mix(mix)
    for seed in (0, 7, BIG_SEED):
        t = _gen(mix, seed, hf)
        for r in t.arrivals + t.prefilled:
            assert spec["output"]["min"] <= r.max_new <= spec["output"]["max"]
            assert len(r.prompt) <= spec["prompt"]["max"]
            assert 0 <= r.prompt.min() and r.prompt.max() < hf["vocab_size"]
            total = len(r.prompt) + r.max_new
            assert total <= hf["max_position_embeddings"]
            if not hf.get("sliding_window") or not hf.get(
                    "use_sliding_window", True):
                # full attention is exact only inside the ring
                assert total <= hf["serving"]["ring_tokens"]
            else:
                assert spec["prompt"]["min"] <= len(r.prompt)
            assert 1 <= len(r.done) < r.max_new or r not in t.prefilled


def test_prefilled_and_open_loop():
    hf = model.load_config("qwen3_14b")
    t = _gen("chat", 3, hf)
    assert t.open_loop and len(t.prefilled) == traffic.load_mix(
        "chat")["prefilled_slots"]
    arr = [r.arrival_s for r in t.arrivals]
    assert arr == sorted(arr) and arr[-1] > 51
    rate = traffic.load_mix("chat")["arrivals"]["rate_per_s"]
    assert abs(len(arr) / arr[-1] - rate) < 0.1 * rate
    d = _gen("long_decode", 3, model.load_config("h2o_danube_1_8b"))
    assert not d.open_loop and len(d.prefilled) == 8


def test_stratified_tiles_the_unit_interval():
    p = traffic.stratified(40, np.random.default_rng(1))
    assert np.allclose(np.sort(p), (np.arange(40) + 0.5) / 40)
    for i in range(0, 40, traffic.BLOCK):     # every block spans (0, 1)
        blk = np.sort(p[i:i + traffic.BLOCK])
        assert np.all(np.floor(blk * traffic.BLOCK) == np.arange(
            traffic.BLOCK))


def test_inverse_normal():
    from statistics import NormalDist

    p = np.array([1e-4, 0.01, 0.2, 0.5, 0.8, 0.99, 1 - 1e-4])
    want = [NormalDist().inv_cdf(x) for x in p]
    assert np.allclose(traffic._inv_normal(p), want, atol=1e-7)
