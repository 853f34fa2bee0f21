"""The lockstep Pallas solver and the rule that picks it.

The kernel runs in interpret mode here, at small buckets (16–32), and is
held bit-for-bit to the vmapped XLA solver (``_mcop_batch_jit``) on the
same float32 inputs: mixed true sizes in one bucket, several pins, tied
scores, batches that do not fill a group and batches of several groups.
The selection rule is a pure function of (platform, bucket, dtype).
"""

import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import WCG, brute_force, random_wcg
from repro.core.graph import WCGBatch
from repro.core.mcop import (
    LOCKSTEP_MIN_BUCKET,
    _mcop_batch_jit,
    _solver_for,
    mcop_batch,
)
from repro.core.mcop import mcop_stoer_wagner_kernel
from repro.kernels.mcop_phase import default_block_graphs
from repro.obs import Tracer


def _tied_graph(n: int) -> WCG:
    """Every vertex and edge weighs the same: each MTCV step is a tie."""
    adj = np.ones((n, n)) - np.eye(n)
    offloadable = np.ones(n, dtype=bool)
    offloadable[[0, n - 1]] = False
    return WCG(
        names=[f"v{i}" for i in range(n)],
        w_local=np.full(n, 4.0),
        w_cloud=np.full(n, 2.0),
        adj=adj,
        offloadable=offloadable,
    )


def _graphs(k: int, m: int, seed: int) -> list[WCG]:
    """k graphs of mixed true size ≤ m, 1..n/3 pins, every third with
    integer weights (ties), the last one all ties."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(k - 1):
        n = int(rng.integers(2, m + 1))
        out.append(
            random_wcg(
                n,
                edge_prob=float(rng.choice([0.1, 0.4, 0.8])),
                n_unoffloadable=int(rng.integers(1, max(2, n // 3 + 1))),
                integer_weights=i % 3 == 0,
                rng=rng,
            )
        )
    out.append(_tied_graph(min(m, 9)))
    return out


def _packed(graphs, m):
    wb = WCGBatch.from_wcgs(graphs, m=m)
    return (
        np.asarray(wb.adj, np.float32),
        np.asarray(wb.w_local, np.float32),
        np.asarray(wb.w_cloud, np.float32),
        wb.anchored_pinned(),
    )


@pytest.mark.parametrize(
    "k,m,seed,block_graphs",
    [
        (1, 16, 0, None),   # one graph, one group of 8 (7 inert)
        (3, 16, 1, None),   # a batch that does not fill its group
        (8, 32, 2, None),   # exactly one full group
        (11, 24, 3, 8),     # two groups, the second padded
    ],
)
def test_lockstep_matches_xla_solver_bit_for_bit(k, m, seed, block_graphs):
    graphs = _graphs(k, m, seed)
    args = _packed(graphs, m)
    cuts, masks = mcop_stoer_wagner_kernel(
        *args, interpret=True, block_graphs=block_graphs
    )
    cuts, masks = np.asarray(cuts), np.asarray(masks)
    want_cuts, want_masks = (
        np.asarray(a) for a in _mcop_batch_jit(*(jnp.asarray(a) for a in args))
    )
    assert cuts.shape == (k,) and masks.shape == (k, m) and masks.dtype == bool
    assert np.array_equal(cuts, want_cuts)
    assert np.array_equal(masks, want_masks)
    for g, cut, mask in zip(graphs, cuts, masks):
        local = mask[: g.n]
        # the mask priced at its own graph is the cut it came with
        assert g.total_cost(local) == pytest.approx(float(cut), rel=1e-5)
        assert local[~g.offloadable].all()
        if int(g.offloadable.sum()) <= 14:
            assert float(cut) >= brute_force(g).cost * (1 - 1e-5) - 1e-4


def test_lockstep_inert_graphs_and_all_pinned_rows():
    """A graph whose every vertex is pinned has no phase: like the XLA
    solver it returns the sentinel cut and the all-local mask."""
    g = random_wcg(6, rng=np.random.default_rng(9))
    pinned = WCG(
        names=g.names, w_local=g.w_local, w_cloud=g.w_cloud, adj=g.adj,
        offloadable=np.zeros(6, dtype=bool),
    )
    args = _packed([g, pinned], 16)
    cuts, masks = mcop_stoer_wagner_kernel(*args, interpret=True)
    want = _mcop_batch_jit(*(jnp.asarray(a) for a in args))
    assert np.array_equal(np.asarray(cuts), np.asarray(want[0]))
    assert np.array_equal(np.asarray(masks), np.asarray(want[1]))
    assert np.asarray(masks)[1].all()


@pytest.mark.parametrize("platform", ["tpu", "cpu", "gpu"])
@pytest.mark.parametrize("m", [16, 64, 128, 256])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_solver_rule_truth_table(platform, m, dtype):
    want = platform == "tpu" and m >= 64 and dtype == np.float32
    assert _solver_for(platform, m, dtype) == ("lockstep" if want else "xla")
    assert _solver_for(platform, m, np.dtype(dtype).name) == _solver_for(
        platform, m, dtype
    )


def test_solver_rule_threshold_sits_between_fig12_and_granite_buckets():
    assert LOCKSTEP_MIN_BUCKET == 64
    assert _solver_for("tpu", 63, np.float32) == "xla"
    assert _solver_for("tpu", 64, jnp.float32) == "lockstep"


def test_default_block_graphs_fills_vmem_in_whole_tiles():
    groups = {m: default_block_graphs(m) for m in (16, 64, 128, 256)}
    assert groups == {16: 256, 64: 256, 128: 88, 256: 16}
    assert all(g % 8 == 0 for g in groups.values())


def test_solve_wait_names_the_solver(monkeypatch):
    mcop_mod = sys.modules["repro.core.mcop"]  # ``repro.core.mcop`` is also a function

    graphs = [random_wcg(6, rng=np.random.default_rng(i)) for i in range(3)]
    tr = Tracer()
    default = mcop_batch(graphs, buckets=(16,), tracer=tr)
    mcop_batch(graphs, backend="pallas", buckets=(16,), tracer=tr)
    # a TPU platform routes the default path to the kernel
    monkeypatch.setattr(mcop_mod, "_solver_for", lambda platform, m, dtype: "lockstep")
    forced = mcop_batch(graphs, buckets=(16,), tracer=tr)
    assert [w.attrs["solver"] for w in tr.spans("solve.wait")] == [
        "xla", "lockstep", "lockstep",
    ]
    for a, b in zip(default, forced):
        assert a.min_cut == b.min_cut
        assert np.array_equal(a.local_mask, b.local_mask)
