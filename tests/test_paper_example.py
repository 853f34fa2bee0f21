"""Paper §5.5 case study — the reproduction's ground truth.

The paper prints, for its 6-vertex example, every phase's induced vertex
ordering, itemised cut value (Figs. 6–10), and the optimal partition
{a, c} local / {b, d, e, f} cloud at cost 22 (Fig. 11, confirmed again by
the GUI run in Fig. 16).  These tests assert all of it, phase by phase.
"""

import numpy as np
import pytest

from repro.core import (
    WCG,
    brute_force,
    branch_and_bound,
    chain_dp,
    face_recognition_graph,
    full_offloading,
    linear_graph,
    maxflow_optimal,
    mcop,
    mcop_reference,
    no_offloading,
    paper_example_graph,
)


@pytest.fixture(scope="module")
def g():
    return paper_example_graph()


def test_local_cost_total_is_45(g):
    assert g.local_cost_total == 45.0


def test_phase_cut_values_match_figs_6_to_10(g):
    result = mcop_reference(g)
    cuts = [ph.cut_value for ph in result.phases]
    assert cuts == [40.0, 35.0, 29.0, 22.0, 27.0]


def test_phase1_induced_ordering_matches_fig6(g):
    result = mcop_reference(g)
    assert result.phases[0].order == ["a", "c", "b", "e", "d", "f"]
    assert result.phases[0].s == "d"
    assert result.phases[0].t == "f"


def test_phase_orderings_match_figs_7_to_10(g):
    result = mcop_reference(g)
    assert result.phases[1].order == ["a", "c", "b", "e", "{df}"]
    assert result.phases[2].order == ["a", "c", "b", "{def}"]
    assert result.phases[3].order == ["a", "c", "{bdef}"]
    assert result.phases[4].order == ["a", "{bcdef}"]


def test_optimal_cut_is_22_between_ac_and_bdef(g):
    result = mcop_reference(g)
    assert result.min_cut == 22.0
    local = {g.names[i] for i in result.local_indices}
    cloud = {g.names[i] for i in result.cloud_indices}
    assert local == {"a", "c"}
    assert cloud == {"b", "d", "e", "f"}


def test_total_cost_of_optimal_placement_equals_cut_value(g):
    result = mcop_reference(g)
    assert g.total_cost(result.local_mask) == pytest.approx(result.min_cut)


def test_gui_comparison_costs(g):
    """Fig. 15/16: partial vs no-offloading vs full-offloading costs."""
    no = no_offloading(g)
    full = full_offloading(g)
    part = mcop_reference(g)
    assert no.cost == 45.0
    assert part.min_cut == 22.0
    assert part.min_cut < full.cost  # partial beats full offloading here
    assert part.min_cut < no.cost


def test_all_backends_agree_on_paper_example(g):
    ref = mcop_reference(g)
    jx = mcop(g, backend="jax")
    bf = brute_force(g)
    mf = maxflow_optimal(g)
    bb = branch_and_bound(g)
    kx = mcop(g, backend="pallas")
    for cost in (jx.min_cut, bf.cost, mf.cost, bb.cost, kx.min_cut):
        assert cost == pytest.approx(22.0)
    assert (kx.local_mask == ref.local_mask).all()
    assert (bf.local_mask == ref.local_mask).all()


def test_unoffloadable_vertex_always_local(g):
    result = mcop_reference(g)
    g.validate_placement(result.local_mask)  # raises if 'a' went to cloud


def test_face_recognition_graph_partitions_sensibly():
    """§7.2: F=2, B=1 MB/s; main and checkAgainst stay local."""
    g = face_recognition_graph(speedup=2.0, bandwidth_mbps=1.0)
    res = mcop_reference(g)
    names_local = {g.names[i] for i in res.local_indices}
    assert "main" in names_local and "checkAgainst" in names_local
    # optimality vs oracle
    assert res.min_cut == pytest.approx(brute_force(g).cost)
    # higher bandwidth must not increase the optimal cost
    g_fast = face_recognition_graph(speedup=2.0, bandwidth_mbps=8.0)
    res_fast = mcop_reference(g_fast)
    assert res_fast.min_cut <= res.min_cut + 1e-9


def test_chain_dp_matches_brute_on_linear():
    g = linear_graph(8, rng=np.random.default_rng(3))
    assert chain_dp(g).cost == pytest.approx(brute_force(g).cost)
