"""The traffic generator: the same seed gives the same traffic, and each
mix draws only its own lengths."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import traffic  # noqa: E402

SERVE_MIXES = sorted(p.stem for p in (traffic.HERE / "traffic").glob("*.json")
                     if traffic.load_mix(p.stem)["kind"] == "serve")
SEED = 2**31 + 7


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_requests_are_a_function_of_the_seed(mix):
    m = traffic.load_mix(mix)
    a = traffic.requests(m, 151936, SEED, 50)
    b = traffic.requests(m, 151936, SEED, 50)
    c = traffic.requests(m, 151936, SEED + 1, 50)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new"] == y["max_new"] for x, y in zip(a, b))
    assert any(len(x["prompt"]) != len(y["prompt"])
               or not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_requests_keep_to_the_mix(mix):
    m = traffic.load_mix(mix)
    reqs = traffic.requests(m, 1000, SEED, 400)
    reqs += traffic.first_wave(m, 1000, SEED, 20)
    assert {len(r["prompt"]) for r in reqs} <= set(m["prompt_buckets"])
    assert all(1 <= r["max_new"] <= m["out_max"] for r in reqs)
    assert all(r["max_new"] >= m["out_min"] for r in reqs[:400])
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000
               for r in reqs)


def test_poisson_arrivals_are_a_function_of_the_seed():
    m = traffic.load_mix("chat")
    a = traffic.arrivals(m, 0.5, 40.0, SEED)
    assert np.array_equal(a, traffic.arrivals(m, 0.5, 40.0, SEED))
    assert not np.array_equal(a, traffic.arrivals(m, 0.5, 40.0, SEED + 1))
    assert len(a) == 20 and np.all(np.diff(a) >= 0)
    assert 0 <= a[0] and a[-1] < 40.0
    # every seed gets the same gaps, in its own order
    b = traffic.arrivals(m, 0.5, 40.0, SEED + 1)
    gaps = lambda t: np.sort(np.diff(np.concatenate([[0.0], t, [40.0]])))
    assert np.allclose(gaps(a), gaps(b))


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_every_seed_gets_the_same_lengths(mix):
    m = traffic.load_mix(mix)
    sizes = lambda s: sorted((len(r["prompt"]), r["max_new"])
                             for r in traffic.requests(m, 1000, s, 30))
    assert sizes(SEED) == sizes(SEED + 1)
    wave = lambda s: sorted((len(r["prompt"]), r["max_new"])
                            for r in traffic.first_wave(m, 1000, s, 10))
    assert wave(SEED) == wave(SEED + 1)


def test_train_batches_differ_by_step_and_repeat_by_seed():
    m = traffic.load_mix("qad_4k")
    a = traffic.train_batch(m, 151936, 1, SEED, 0)
    assert a["tokens"].shape == (1, m["seq_len"])
    assert np.array_equal(a["tokens"][0, 1:], a["labels"][0, :-1])
    assert np.array_equal(a["tokens"],
                          traffic.train_batch(m, 151936, 1, SEED, 0)["tokens"])
    assert not np.array_equal(
        a["tokens"], traffic.train_batch(m, 151936, 1, SEED, 1)["tokens"])
