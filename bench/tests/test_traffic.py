"""The traffic draws: fixed by the seed, inside their clips, and the same
work for every seed (only the order and the token ids change)."""
import numpy as np
import pytest
import spec
import traffic

MIXES = ["render", "vqa"]
BIG_SEED = 2 ** 31 + 987654321


def _mix(name):
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_draws(name):
    a = traffic.generate(_mix(name), 2048, BIG_SEED, 30)
    b = traffic.generate(_mix(name), 2048, BIG_SEED, 30)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and x.due == y.due
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_draws_within_clips(name):
    mix = _mix(name)
    lo, hi = traffic.prompt_bounds(mix)
    glo, ghi = traffic.length_bounds(mix["gen"])
    for d in traffic.generate(mix, 2048, 3, 30):
        assert lo <= len(d.prompt) <= hi
        assert glo <= d.max_new <= ghi
        assert d.prompt.dtype == np.int32
        assert 0 <= d.prompt.min() and d.prompt.max() < 2048


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    mix = _mix(name)
    runs = [traffic.generate(mix, 2048, s, 30) for s in (1, 2, BIG_SEED)]
    gens = [sorted(d.max_new for d in r) for r in runs]
    assert gens[0] == gens[1] == gens[2]
    # the same set of arrival gaps, so the same offered rate
    gaps = [np.sort(np.diff(np.concatenate(
        [[0.0], np.unique([d.due for d in r])]))) for r in runs]
    np.testing.assert_allclose(gaps[0], gaps[1])
    np.testing.assert_allclose(gaps[0], gaps[2])
    orders = [[d.max_new for d in r] for r in runs]
    assert orders[0] != orders[1]


def test_open_loop_rate_and_bursts():
    mix = dict(_mix("vqa"), arrival="bursty", burst=8, rate_per_s=4.0)
    draws = traffic.generate(mix, 2048, 5, 30)
    due = np.array([d.due for d in draws])
    burst = mix["burst"]
    assert np.all(np.diff(due) >= 0)
    # every burst arrives at one instant
    assert np.all(due.reshape(-1, burst).std(axis=1) == 0)
    span = mix["preroll_s"] + 30
    assert abs(len(draws) / span - mix["rate_per_s"]) < 0.2 * mix[
        "rate_per_s"]


def test_stratified_lognormal_median():
    v = traffic.quantile_values({"dist": "lognormal", "median": 150,
                                 "sigma": 0.6, "min": 50, "max": 500}, 201)
    assert np.median(v) == 150 and v.min() >= 50 and v.max() <= 500


def test_shared_pool_parts_share_prefixes():
    mix = {"loop": "closed", "requests": 40,
           "prompt": [{"dist": "uniform", "min": 64, "max": 64,
                       "shared_pool": 4},
                      {"dist": "uniform", "min": 8, "max": 16}],
           "gen": {"dist": "uniform", "min": 4, "max": 8}}
    draws = traffic.generate(mix, 1000, 9, 10)
    heads = {tuple(d.prompt[:64]) for d in draws}
    assert len(heads) == 4
    tails = {tuple(d.prompt[64:]) for d in draws}
    assert len(tails) == 40
