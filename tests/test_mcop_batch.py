"""Batched MCOP engine: mcop_batch vs the numpy oracle, the full Pallas
Stoer–Wagner kernel, the quantized placement cache, and the batched
adaptive sweep / placement tier sweep."""

import numpy as np
import pytest

from repro.core import (
    WCG,
    AdaptiveController,
    AppProfile,
    Environment,
    EnvQuantizer,
    PlacementCache,
    ResponseTimeModel,
    mcop_batch,
    mcop_reference,
    paper_example_graph,
    random_wcg,
)
from repro.core.placement import (
    StageSpec,
    TPUV5E_TIER,
    plan_placement,
    plan_placement_batch,
)


def _mixed_batch(bucket: int, count: int, seed0: int) -> list[WCG]:
    """Random graphs with mixed sizes/pinned sets filling one bucket."""
    out = []
    for k in range(count):
        rng = np.random.default_rng(seed0 + k)
        n = int(rng.integers(2, bucket + 1))
        out.append(
            random_wcg(
                n,
                edge_prob=float(rng.choice([0.1, 0.3, 0.6])),
                speedup=float(rng.choice([1.5, 2.0, 4.0])),
                n_unoffloadable=int(rng.integers(1, max(2, n // 3 + 1))),
                rng=rng,
            )
        )
    return out


def _assert_matches_reference(graphs, results):
    for g, r in zip(graphs, results):
        ref = mcop_reference(g)
        assert r.min_cut == pytest.approx(ref.min_cut, rel=1e-4, abs=1e-4)
        assert (r.local_mask == ref.local_mask).all()
        assert g.total_cost(r.local_mask) == pytest.approx(
            ref.min_cut, rel=1e-4, abs=1e-4
        )


# ----------------------------------------------------------------------
# mcop_batch vs mcop_reference
# ----------------------------------------------------------------------


@pytest.mark.parametrize("bucket", [16, 64, 128])
def test_mcop_batch_matches_reference_per_bucket(bucket):
    """≥20 random graphs per bucket, mixed sizes and pinned-vertex sets."""
    graphs = _mixed_batch(bucket, count=22, seed0=1000 * bucket)
    _assert_matches_reference(graphs, mcop_batch(graphs))


def _environments(k: int) -> list[Environment]:
    """k environments whose bandwidths span three decades."""
    rng = np.random.default_rng(k)
    return [
        Environment(
            bandwidth_up=float(10 ** rng.uniform(0.5, 3.5)),
            bandwidth_down=float(10 ** rng.uniform(0.5, 3.5)),
            speedup=float(rng.uniform(1.5, 12.0)),
        )
        for _ in range(k)
    ]


def _assert_bit_identical(results, expected):
    for a, b in zip(results, expected, strict=True):
        assert a.min_cut == b.min_cut
        assert np.array_equal(a.local_mask, b.local_mask)


@pytest.mark.parametrize("k", [1, 7, 33])
def test_layer_split_graphs_solve_at_bucket_128_as_at_256(granite_layer_split, k):
    """A 90-vertex graph lands in the 128 bucket, and its cuts and masks
    are bit-identical to the same batch padded to 256, packed per graph
    and built inside the fused program alike."""
    from repro.core.mcop import DEFAULT_BUCKETS, _bucket_size, solve_envs

    profile, envs, model = granite_layer_split, _environments(k), ResponseTimeModel()
    graphs = [model.build(profile, e) for e in envs]
    assert profile.n == 90 and _bucket_size(profile.n, DEFAULT_BUCKETS) == 128
    at_128 = mcop_batch(graphs)
    _assert_bit_identical(at_128, mcop_batch(graphs, buckets=(256,)))
    _assert_bit_identical(
        solve_envs(profile, model, envs),
        solve_envs(profile, model, envs, buckets=(256,)),
    )
    _assert_matches_reference(graphs[:1], at_128[:1])


def test_mcop_batch_mixed_buckets_preserves_order():
    graphs = _mixed_batch(16, 6, 10) + _mixed_batch(64, 6, 20) + _mixed_batch(16, 4, 30)
    _assert_matches_reference(graphs, mcop_batch(graphs))


def test_mcop_batch_edge_cases():
    cases = []
    # n=2: one pinned, one free
    cases.append(random_wcg(2, n_unoffloadable=1, rng=np.random.default_rng(0)))
    # all pinned but one
    cases.append(random_wcg(7, n_unoffloadable=6, rng=np.random.default_rng(1)))
    # no pinned vertices at all (anchor falls back to vertex 0)
    g = random_wcg(6, rng=np.random.default_rng(2))
    g.offloadable[:] = True
    cases.append(g)
    # the paper's worked example
    cases.append(paper_example_graph())
    _assert_matches_reference(cases, mcop_batch(cases))


def test_mcop_batch_pallas_backend_matches_reference():
    graphs = _mixed_batch(12, 6, 500) + [paper_example_graph()]
    results = mcop_batch(graphs, backend="pallas", buckets=(12,))
    _assert_matches_reference(graphs, results)


def test_mcop_batch_pallas_large_weights_not_swallowed_by_sentinel():
    """Graphs priced in FLOPs/bytes (cuts ≫ 2³⁰) must not collapse into the
    kernel's best-cut sentinel — regression for the old 2**30 POS_INF."""
    g = random_wcg(8, edge_prob=0.5, rng=np.random.default_rng(42))
    g.w_local *= 1e12
    g.w_cloud *= 1e12
    g.adj *= 1e12
    ref = mcop_reference(g)
    res = mcop_batch([g], backend="pallas", buckets=(8,))[0]
    assert res.min_cut == pytest.approx(ref.min_cut, rel=1e-4)
    assert (res.local_mask == ref.local_mask).all()


def test_mcop_batch_rejects_unknown_backend():
    with pytest.raises(ValueError):
        mcop_batch([paper_example_graph()], backend="cuda")


def test_full_kernel_direct_paper_example():
    from repro.core.mcop import mcop_stoer_wagner_kernel

    g = paper_example_graph()
    cuts, masks = mcop_stoer_wagner_kernel(
        g.adj[None], g.w_local[None], g.w_cloud[None], (~g.offloadable)[None]
    )
    assert float(cuts[0]) == pytest.approx(22.0)
    assert (np.asarray(masks[0]) == mcop_reference(g).local_mask).all()


# ----------------------------------------------------------------------
# Placement cache: quantization and hit/miss semantics
# ----------------------------------------------------------------------


def test_quantizer_bins_follow_relative_step():
    q = EnvQuantizer(rel_step=0.10)
    base = Environment.symmetric(8.0, 3.0)
    near = Environment.symmetric(8.2, 3.0)      # ~2.5% off — same bin
    far = Environment.symmetric(12.0, 3.0)      # 50% off — different bin
    assert q.key(base) == q.key(near)
    assert q.key(base) != q.key(far)
    assert q.key(base) != q.key(Environment.symmetric(8.0, 4.0))


def test_cache_hit_miss_counters_and_repricing():
    cache = PlacementCache()
    env = Environment.symmetric(5.0, 3.0)
    assert cache.get(env) is None
    mask = np.array([True, False, True])
    cache.put(env, mask)
    # same bin → hit, including a slightly different environment
    got = cache.get(Environment.symmetric(5.05, 3.0))
    assert got is not None and (got == mask).all()
    # different bin → miss
    assert cache.get(Environment.symmetric(50.0, 3.0)) is None
    st = cache.stats
    assert (st.hits, st.misses) == (1, 2)
    assert st.hit_rate == pytest.approx(1 / 3)


def test_cache_wrong_shape_mask_is_a_miss():
    """Sharing a cache across different-sized profiles must never surface a
    wrong-length mask — and the lookup counts as a miss, not a hit."""
    cache = PlacementCache()
    env = Environment.symmetric(2.0, 3.0)
    cache.put(env, np.array([True, False, True]))
    assert cache.get(env, expected_n=8) is None
    assert cache.get(env, expected_n=3) is not None
    st = cache.stats
    assert (st.hits, st.misses) == (1, 1)


def test_cache_lru_eviction():
    cache = PlacementCache(capacity=2)
    m = np.array([True])
    for bw in (1.0, 10.0, 100.0):
        cache.put(Environment.symmetric(bw, 3.0), m)
    assert len(cache) == 2
    assert cache.get(Environment.symmetric(1.0, 3.0)) is None  # evicted
    assert cache.get(Environment.symmetric(100.0, 3.0)) is not None


# ----------------------------------------------------------------------
# Batched adaptive sweep
# ----------------------------------------------------------------------


_TRACE = [
    (8.0, 3.0), (7.6, 3.0), (1.2, 3.0), (1.1, 3.0), (0.3, 3.0),
    (0.3, 1.5), (6.0, 3.0), (8.0, 3.0), (1.2, 3.0), (0.3, 3.0),
]


def _controller(**kw):
    g = random_wcg(8, rng=np.random.default_rng(3))
    prof = AppProfile.from_wcg_times(g)
    return AdaptiveController(
        prof, ResponseTimeModel(), threshold=0.15, min_interval=2, **kw
    )


@pytest.mark.parametrize("backend", ["reference", "jax"])
def test_sweep_matches_serial_observe(backend):
    envs = [Environment.symmetric(b, f) for b, f in _TRACE]
    serial = _controller(backend=backend)
    batched = _controller(backend=backend)
    ev_s = [serial.observe(e) for e in envs]
    ev_b = batched.sweep(envs)
    for a, b in zip(ev_s, ev_b):
        assert a.repartitioned == b.repartitioned
        assert b.partial_cost == pytest.approx(a.partial_cost, rel=1e-5)
        assert (a.result.local_mask == b.result.local_mask).all()


def test_sweep_cache_semantics_match_serial():
    envs = [Environment.symmetric(b, f) for b, f in _TRACE]
    c_serial, c_batched = PlacementCache(), PlacementCache()
    serial = _controller(cache=c_serial)
    batched = _controller(cache=c_batched)
    ev_s = [serial.observe(e) for e in envs]
    ev_b = batched.sweep(envs)
    assert [e.cache_hit for e in ev_s] == [e.cache_hit for e in ev_b]
    assert (c_serial.stats.hits, c_serial.stats.misses) == (
        c_batched.stats.hits, c_batched.stats.misses,
    )
    for a, b in zip(ev_s, ev_b):
        assert b.partial_cost == pytest.approx(a.partial_cost, rel=1e-9)


def test_shared_cache_serves_second_controller():
    envs = [Environment.symmetric(b, f) for b, f in _TRACE]
    cache = PlacementCache()
    first = _controller(cache=cache)
    ev1 = first.sweep(envs)
    misses_after_first = cache.stats.misses
    second = _controller(cache=cache)
    ev2 = second.sweep(envs)
    # every repartition of user 2 is served from user 1's placements
    assert all(e.cache_hit for e in ev2 if e.repartitioned)
    assert cache.stats.misses == misses_after_first
    # repriced costs are honest: identical envs → identical costs
    for a, b in zip(ev1, ev2):
        assert b.partial_cost == pytest.approx(a.partial_cost, rel=1e-9)


# ----------------------------------------------------------------------
# Placement tier sweep
# ----------------------------------------------------------------------


def _stages(n=6):
    return [
        StageSpec(
            name=f"s{i}",
            flops=(1.0 + i) * 1e15,
            bytes_hbm=(0.5 + i) * 1e12,
            act_bytes_out=2e9,
            pinned_tier=0 if i == 0 else None,
        )
        for i in range(n)
    ]


def test_plan_placement_batch_matches_serial_plans():
    stages = _stages()
    tl = TPUV5E_TIER
    tr = TPUV5E_TIER
    bws = [1e8, 1e9, 5e9, 1e15]
    plans = plan_placement_batch(
        stages, tl, tr, inter_tier_bws=bws, backend="reference"
    )
    for bw, plan in zip(bws, plans):
        ref = plan_placement(stages, tl, tr, inter_tier_bw=bw)
        assert plan.mcop_cost == pytest.approx(ref.mcop_cost, rel=1e-6)
        assert (plan.stage_tier == ref.stage_tier).all()
        assert plan.contiguous_boundary == ref.contiguous_boundary
