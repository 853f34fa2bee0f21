"""Each traffic generator is a pure function of its seed, and the miss
mix's working set is far beyond the placement cache."""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402
from bench.generators import independent_envs, regime_walk, session_pool  # noqa: E402

BIG_SEED = 2**31 + 12345


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("gen,traffic", [(independent_envs, "req_miss"), (regime_walk, "req_reuse")])
def test_request_generators_are_pure_functions_of_the_seed(gen, traffic):
    params = mix(traffic)["params"]
    a = gen.requests(params, BIG_SEED, 3000)
    b = gen.requests(params, BIG_SEED, 3000)
    c = gen.requests(params, BIG_SEED + 1, 3000)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert a[1].shape == (3000, 6) and np.isfinite(a[1]).all() and (a[1] > 0).all()


def test_session_pool_is_a_pure_function_of_the_seed():
    params = dict(mix("sessions")["params"], capacity=400, initial=360, arrival_rate=18.0)
    runs = []
    for seed in (BIG_SEED, BIG_SEED, BIG_SEED + 1):
        pool = session_pool.SessionPool(params, seed, stream=1)
        runs.append([pool.step() for _ in range(6)])
    for (ea, aa, ra, da), (eb, ab, rb, db) in zip(runs[0], runs[1]):
        assert np.array_equal(ea, eb) and np.array_equal(aa, ab)
        assert np.array_equal(ra, rb) and np.array_equal(da, db)
    assert not np.array_equal(runs[0][-1][0], runs[2][-1][0])


def test_session_pool_holds_its_occupancy():
    params = mix("sessions")["params"]
    pool = session_pool.SessionPool(params, BIG_SEED, stream=0)
    active = [pool.step()[1].sum() for _ in range(60)]
    assert 0.85 * params["capacity"] < np.mean(active[20:]) < 0.95 * params["capacity"]


def test_miss_mix_working_set_exceeds_the_cache():
    m = mix("req_miss")
    config = json.loads((ROOT / "bench" / "configs" / "granite34b_layer_split.json").read_text())
    _, envs = independent_envs.requests(m["params"], BIG_SEED, 10_000)
    bins = {tuple(k) for k in reference.bin_keys(envs).tolist()}
    assert len(bins) > 2 * config["cache_capacity"]


def test_reuse_mix_fits_the_cache():
    m = mix("req_reuse")
    _, envs = regime_walk.requests(m["params"], BIG_SEED, 20_000)
    bins = {tuple(k) for k in reference.bin_keys(envs).tolist()}
    assert len(bins) < 200
