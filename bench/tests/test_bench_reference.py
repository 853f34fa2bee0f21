"""The plain reference: batched MCOP against a line-by-line transcription
of the paper's Algorithms 1-3, the paper's worked example, pricing and
cache bins."""

import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import reference  # noqa: E402


def transcription(w_local, w_cloud, adj, offloadable):
    """Algorithms 1-3 for one graph, vertex by vertex, as the paper writes them."""
    n = len(w_local)
    adj, wl, wc = adj.copy(), w_local.copy(), w_cloud.copy()
    alive = np.ones(n, bool)
    members = [{i} for i in range(n)]
    total = wl.sum()

    def merge(s, t):
        adj[s, :] += adj[t, :]
        adj[:, s] += adj[:, t]
        adj[s, s] = 0.0
        adj[t, :] = 0.0
        adj[:, t] = 0.0
        wl[s] += wl[t]
        wc[s] += wc[t]
        wl[t] = wc[t] = 0.0
        members[s] |= members[t]
        members[t] = set()
        alive[t] = False

    pinned = [i for i in range(n) if not offloadable[i]]
    src = pinned[0] if pinned else 0
    for other in pinned[1:]:
        merge(src, other)
    best, best_members = np.inf, set()
    while alive.sum() > 1:
        gains = wl - wc
        in_a = {src}
        conn = adj[src].copy()
        added = [src]
        for _ in range(int(alive.sum()) - 1):
            top, v_top = -np.inf, -1
            for v in range(n):
                if alive[v] and v not in in_a and top < conn[v] - gains[v]:
                    top, v_top = conn[v] - gains[v], v
            in_a.add(v_top)
            conn += adj[v_top]
            added.append(v_top)
        s, t = added[-2], added[-1]
        cut = total - gains[t] + adj[t, alive].sum()
        if cut < best:
            best, best_members = cut, set(members[t])
        merge(s, t)
    return best, np.array([i not in best_members for i in range(n)])


def random_graphs(k, n, pinned, seed):
    rng = np.random.default_rng(seed)
    wl = rng.uniform(0, 20, (k, n))
    wc = wl / rng.uniform(1.5, 6, (k, 1))
    adj = rng.uniform(0, 10, (k, n, n)) * (rng.random((k, n, n)) < 0.4)
    adj = np.triu(adj, 1)
    adj = adj + np.swapaxes(adj, 1, 2)
    off = np.ones(n, bool)
    off[list(pinned)] = False
    return wl, wc, adj, off


@pytest.mark.parametrize("n,pinned", [(9, (0, 5)), (12, ()), (16, (3,))])
def test_batched_mcop_is_the_transcription(n, pinned):
    wl, wc, adj, off = random_graphs(6, n, pinned, seed=n)
    cut, local = reference.mcop(wl, wc, adj, off)
    for i in range(6):
        want_cut, want_local = transcription(wl[i], wc[i], adj[i], off)
        assert cut[i] == pytest.approx(want_cut, rel=1e-12)
        assert np.array_equal(local[i], want_local)


def test_paper_worked_example():
    """Section 5.5: optimal cost 22 with {a, c} local."""
    names = "abcdef"
    wl = np.array([[0.0, 9.0, 3.0, 12.0, 6.0, 15.0]])
    wc = np.array([[0.0, 3.0, 1.0, 4.0, 2.0, 5.0]])
    adj = np.zeros((1, 6, 6))
    for (u, v), w in {("a", "b"): 3, ("a", "c"): 8, ("a", "f"): 1, ("b", "c"): 1,
                      ("b", "d"): 3, ("b", "e"): 2, ("e", "f"): 4}.items():
        adj[0, names.index(u), names.index(v)] = adj[0, names.index(v), names.index(u)] = w
    cut, local = reference.mcop(wl, wc, adj, np.array([False] + [True] * 5))
    assert cut[0] == 22.0
    assert [names[i] for i in np.nonzero(local[0])[0]] == ["a", "c"]
    assert reference.price(wl, wc, adj, local)[0] == 22.0


def test_clamp_and_price_all_local():
    profile = {"t_local": np.array([1.0, 2.0, 3.0]), "data_in": np.full((3, 3), 100.0),
               "data_out": np.zeros((3, 3)), "offloadable": np.array([False, True, True])}
    envs = np.array([[1.0, 1.0, 2.0, 0.9, 0.3, 1.3]])
    cut, mask = reference.solve(profile, envs)
    assert mask.all() and cut[0] == 6.0  # transfers dwarf the savings: all local
    wl, wc, adj = reference.build(profile, envs)
    assert reference.price(wl, wc, adj, mask)[0] == 6.0


def test_bins_are_ten_percent_geometric():
    envs = np.array([[1.0, 1.1, 1.21, 0.9, 0.3, 1.3]])
    assert reference.bin_keys(envs)[0, :3].tolist() == [0, 1, 2]


def test_bf16_control_rounds():
    assert reference.round_bf16(np.array([1.0 + 2**-10]))[0] == 1.0
