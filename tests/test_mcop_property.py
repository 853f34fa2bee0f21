"""Property-based validation: MCOP vs independent exact oracles.

The paper proves Theorem 1 (each phase cut is a min s–t cut) and claims
global optimality; our oracles show that claim does NOT survive signed
node gains — MCOP is exact on ~70% of adversarial random WCGs (mean gap
≈5%, paper's own worked example exact).  First counterexample:
``random_wcg(5, rng=default_rng(100))`` → MCOP 54.06 vs optimum 53.06.

The properties below are therefore the ones that actually hold:

  * optimum ≤ MCOP ≤ full-offloading cost (the last phase IS the
    full-offloading cut), and the reported placement achieves the
    reported cost;
  * brute force == max-flow reduction (two independent exact oracles);
  * MCOP == optimum on a large measured fraction of instances, and
    exactly on the paper's example/topologies (see test_paper_example);
  * the exact solver is monotone in bandwidth and hits the textbook
    limits (B→∞ / B→0).

The exact-rate bound itself is asserted by
``test_mcop_exact_rate_on_adversarial_distribution`` below.
"""

import numpy as np
import pytest

from _hyp import given, settings, st  # hypothesis or skip-shim (see _hyp.py)

from repro.core import (
    WCG,
    branch_and_bound,
    brute_force,
    chain_dp,
    full_offloading,
    linear_graph,
    loop_graph,
    maxflow_optimal,
    mcop,
    mcop_reference,
    mesh_graph,
    no_offloading,
    random_wcg,
    tree_graph,
)

SETTINGS = dict(max_examples=60, deadline=None)


@st.composite
def wcg_strategy(draw, max_n: int = 10):
    n = draw(st.integers(2, max_n))
    seed = draw(st.integers(0, 2**31 - 1))
    edge_prob = draw(st.sampled_from([0.1, 0.3, 0.6, 0.9]))
    speedup = draw(st.sampled_from([1.2, 2.0, 3.0, 10.0]))
    n_pin = draw(st.integers(1, max(1, n // 3)))
    integer = draw(st.booleans())
    return random_wcg(
        n,
        edge_prob=edge_prob,
        speedup=speedup,
        n_unoffloadable=n_pin,
        rng=np.random.default_rng(seed),
        integer_weights=integer,
    )


# ----------------------------------------------------------------------
# numpy-based smoke fallbacks — fixed-seed versions of the key properties
# that run in tier-1 even when hypothesis is unavailable.
# ----------------------------------------------------------------------


def _smoke_wcg(seed: int) -> WCG:
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 11))
    return random_wcg(
        n,
        edge_prob=float(rng.choice([0.1, 0.3, 0.6, 0.9])),
        speedup=float(rng.choice([1.2, 2.0, 3.0, 10.0])),
        n_unoffloadable=int(rng.integers(1, max(2, n // 3 + 1))),
        rng=rng,
    )


@pytest.mark.parametrize("seed", range(12))
def test_mcop_bounds_and_self_consistency_smoke(seed):
    g = _smoke_wcg(seed)
    res = mcop_reference(g)
    opt = brute_force(g)
    assert res.min_cut >= opt.cost - 1e-9
    assert res.min_cut <= full_offloading(g).cost + 1e-9
    assert g.total_cost(res.local_mask) == pytest.approx(res.min_cut, rel=1e-9)
    g.validate_placement(res.local_mask)


@pytest.mark.parametrize("seed", range(8))
def test_jax_backend_matches_reference_smoke(seed):
    g = _smoke_wcg(100 + seed)
    ref = mcop_reference(g)
    jx = mcop(g, backend="jax")
    assert jx.min_cut == pytest.approx(ref.min_cut, rel=1e-5, abs=1e-4)
    assert g.total_cost(jx.local_mask) == pytest.approx(ref.min_cut, rel=1e-5, abs=1e-4)


@pytest.mark.parametrize("seed", range(8))
def test_maxflow_oracle_agrees_with_brute_force_smoke(seed):
    g = _smoke_wcg(200 + seed)
    assert maxflow_optimal(g).cost == pytest.approx(
        brute_force(g).cost, rel=1e-9, abs=1e-9
    )


@given(wcg_strategy())
@settings(**SETTINGS)
def test_mcop_bounds_and_self_consistency(g):
    """optimum ≤ MCOP ≤ full offloading; reported mask achieves reported cost."""
    res = mcop_reference(g)
    opt = brute_force(g)
    assert res.min_cut >= opt.cost - 1e-9
    assert res.min_cut <= full_offloading(g).cost + 1e-9
    assert g.total_cost(res.local_mask) == pytest.approx(res.min_cut, rel=1e-9)
    g.validate_placement(res.local_mask)


@given(wcg_strategy())
@settings(**SETTINGS)
def test_maxflow_oracle_agrees_with_brute_force(g):
    assert maxflow_optimal(g).cost == pytest.approx(brute_force(g).cost, rel=1e-9, abs=1e-9)


@given(wcg_strategy(max_n=8))
@settings(**SETTINGS)
def test_jax_backend_matches_reference(g):
    """The jittable MCOP implements the same algorithm, bit-for-bit-ish."""
    ref = mcop_reference(g)
    jx = mcop(g, backend="jax")
    assert jx.min_cut == pytest.approx(ref.min_cut, rel=1e-5, abs=1e-4)
    assert g.total_cost(jx.local_mask) == pytest.approx(ref.min_cut, rel=1e-5, abs=1e-4)


@given(wcg_strategy(max_n=9))
@settings(max_examples=30, deadline=None)
def test_branch_and_bound_exact(g):
    assert branch_and_bound(g).cost == pytest.approx(brute_force(g).cost, rel=1e-9, abs=1e-9)


@given(st.integers(2, 12), st.integers(0, 10_000))
@settings(**SETTINGS)
def test_chain_dp_on_linear_graphs(n, seed):
    g = linear_graph(n, rng=np.random.default_rng(seed))
    assert chain_dp(g).cost == pytest.approx(brute_force(g).cost, rel=1e-9)


@pytest.mark.slow
def test_mcop_exact_rate_on_adversarial_distribution():
    """Statistical reproduction check: ≥60% exact, mean gap <8% on the
    hardest random distribution (measured ≈70% / 4.9%)."""
    gaps, exact = [], 0
    n_trials = 200
    for seed in range(n_trials):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(3, 12))
        g = random_wcg(
            n,
            edge_prob=float(rng.choice([0.1, 0.3, 0.6, 0.9])),
            speedup=float(rng.choice([1.2, 2.0, 3.0, 10.0])),
            n_unoffloadable=int(rng.integers(1, max(2, n // 3))),
            rng=rng,
        )
        gap = (mcop_reference(g).min_cut - brute_force(g).cost) / max(
            brute_force(g).cost, 1e-12
        )
        gaps.append(gap)
        exact += gap < 1e-9
    assert exact / n_trials >= 0.60, exact / n_trials
    assert np.mean(gaps) < 0.08, np.mean(gaps)


def test_known_counterexample_to_paper_theorem1():
    """Documented counterexample: MCOP strictly above the true optimum."""
    g = random_wcg(5, rng=np.random.default_rng(100))
    res = mcop_reference(g)
    opt = brute_force(g)
    assert res.min_cut > opt.cost + 0.5  # 54.06 vs 53.06
    assert maxflow_optimal(g).cost == pytest.approx(opt.cost, rel=1e-9)


@given(st.integers(3, 10), st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_paper_topologies_mcop_behaves(n, seed):
    """On the paper's own topology families MCOP is near-exact in practice;
    assert the bound properties plus exactness of the exact solver."""
    rng = np.random.default_rng(seed)
    for builder in (linear_graph, loop_graph, tree_graph):
        g = builder(n, rng=rng)
        res = mcop_reference(g)
        opt = brute_force(g)
        assert opt.cost - 1e-9 <= res.min_cut <= full_offloading(g).cost + 1e-9
        assert maxflow_optimal(g).cost == pytest.approx(opt.cost, rel=1e-9)
    g = mesh_graph(2, max(2, n // 2), rng=rng)
    assert mcop_reference(g).min_cut >= brute_force(g).cost - 1e-9


@given(wcg_strategy(max_n=8), st.sampled_from([0.25, 0.5, 2.0, 4.0]))
@settings(**SETTINGS)
def test_exact_solver_bandwidth_monotonicity(g, scale):
    """For the exact optimum: higher bandwidth never hurts (per-placement
    costs are monotone in edge weights, hence so is the min)."""
    base = maxflow_optimal(g).cost
    scaled = maxflow_optimal(g.with_bandwidth_scale(scale)).cost
    if scale >= 1.0:
        assert scaled <= base + 1e-9
    else:
        assert scaled >= base - 1e-9


@given(wcg_strategy(max_n=8))
@settings(max_examples=30, deadline=None)
def test_exact_solver_extreme_bandwidth_limits(g):
    """B→∞ ⇒ offload everything with positive gain; B→0 ⇒ no offloading."""
    gains = g.w_local - g.w_cloud
    g_inf = g.with_bandwidth_scale(1e12)
    best_inf = maxflow_optimal(g_inf).cost
    ideal = float(np.where(g.offloadable & (gains > 0), g.w_cloud, g.w_local).sum())
    assert best_inf == pytest.approx(ideal, rel=1e-6, abs=1e-5)

    g_zero = g.with_bandwidth_scale(1e-12)
    best0 = maxflow_optimal(g_zero).cost
    # with a dead link no edge may be cut, so the decision is per connected
    # component: offload a whole component iff it is fully offloadable and
    # its total gain is positive
    comp = np.arange(g.n)

    def find(i):
        while comp[i] != i:
            comp[i] = comp[comp[i]]
            i = comp[i]
        return i

    for i in range(g.n):
        for j in range(g.n):
            if g.adj[i, j] > 0:
                comp[find(i)] = find(j)
    ideal0 = 0.0
    for root in {find(i) for i in range(g.n)}:
        members = [i for i in range(g.n) if find(i) == root]
        movable = all(g.offloadable[i] for i in members)
        gain = sum(gains[i] for i in members)
        if movable and gain > 0:
            ideal0 += sum(g.w_cloud[i] for i in members)
        else:
            ideal0 += sum(g.w_local[i] for i in members)
    assert best0 == pytest.approx(ideal0, rel=1e-6, abs=1e-3)


@given(wcg_strategy(max_n=8), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_total_cost_eq2_matches_explicit_sum(g, seed):
    """Eq. 2 evaluated by WCG.total_cost == hand-rolled indicator sum."""
    rng = np.random.default_rng(seed)
    mask = rng.random(g.n) < 0.5
    mask |= ~g.offloadable
    expected = 0.0
    for v in range(g.n):
        expected += g.w_local[v] if mask[v] else g.w_cloud[v]
    for i in range(g.n):
        for j in range(i + 1, g.n):
            if g.adj[i, j] and mask[i] != mask[j]:
                expected += g.adj[i, j]
    assert g.total_cost(mask) == pytest.approx(expected, rel=1e-12)


def test_mcop_scales_to_hundreds_of_vertices():
    g = random_wcg(150, edge_prob=0.05, rng=np.random.default_rng(0))
    res = mcop_reference(g)
    mf = maxflow_optimal(g)
    assert res.min_cut >= mf.cost - 1e-6
    assert g.total_cost(res.local_mask) == pytest.approx(res.min_cut, rel=1e-9)
