"""Plain reference of the placement service's semantics.

Independent of the program under test: nothing here imports ``repro``.
It rebuilds each request's weighted consumption graph from the
configuration's profile and the request's environment (paper Eq. 1 and
Eq. 4), solves it with the paper's MCOP algorithm (Algorithms 1-3: fold
the unoffloadable vertices into one source, then |V|-1 phases of
most-tightly-connected-vertex absorption with the cut of the phase of
Eq. 10), prices placements with Eq. 2, applies the section 4.3 clamp to
the all-local plan, and quantizes environments into the cache's 10%
geometric bins.

Everything runs in numpy float64, batched over graphs of one profile so
that a few thousand requests check in seconds.  ``mcop`` takes a
rounding function: the identity for the reference, ``round_bf16`` for the
lower-precision control that must fail the comparison.
"""

from __future__ import annotations

import numpy as np

ENV_FIELDS = ("bandwidth_up", "bandwidth_down", "speedup", "p_compute", "p_idle", "p_transfer")
PAPER_POWERS = (0.9, 0.3, 1.3)  # p_compute, p_idle, p_transfer (paper section 7.1)
BIN_STEP = 0.10  # the placement cache's relative bin width


def identity(x):
    return x


def round_bf16(x):
    """Round to bfloat16 and hold the value in float32: every arithmetic
    result of the control passes through this."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


# ----------------------------------------------------------------------
# graphs: Eq. 1 (edges) and Eq. 4 (response-time node weights)
# ----------------------------------------------------------------------


def build(profile: dict, envs: np.ndarray):
    """K environments (K, 6) -> (w_local (K, n), w_cloud (K, n), adj (K, n, n)).

    Response time (Eq. 4): a vertex costs its local time on the device and
    local time / speedup in the cloud; a cut edge costs the transfer time,
    data sent over the uplink plus data returned over the downlink, summed
    over both directions of the pair.
    """
    envs = np.asarray(envs, np.float64).reshape(-1, 6)
    t_local = np.asarray(profile["t_local"], np.float64)
    data_in = np.asarray(profile["data_in"], np.float64)
    data_out = np.asarray(profile["data_out"], np.float64)
    up, down, speedup = envs[:, 0], envs[:, 1], envs[:, 2]
    w_local = np.broadcast_to(t_local, (len(envs), t_local.size)).copy()
    w_cloud = t_local[None, :] / speedup[:, None]
    one_way = data_in[None] / up[:, None, None] + data_out[None] / down[:, None, None]
    adj = one_way + np.swapaxes(one_way, -1, -2)
    return w_local, w_cloud, adj


def price(w_local, w_cloud, adj, masks):
    """Eq. 2 for K placements (True = local): node costs plus cut edges."""
    masks = np.asarray(masks, bool)
    node = np.where(masks, w_local, w_cloud).sum(axis=-1)
    cut = masks[:, :, None] != masks[:, None, :]
    return node + (adj * cut).sum(axis=(-1, -2)) / 2.0


# ----------------------------------------------------------------------
# MCOP (Algorithms 1-3), batched over graphs with one pinned pattern
# ----------------------------------------------------------------------


def mcop(w_local, w_cloud, adj, offloadable, rnd=identity):
    """Paper MCOP for K graphs of one profile.  Returns (cut (K,), local (K, n)).

    ``offloadable`` is the profile's (n,) mask, shared by every graph.
    Ties in the absorption order go to the lowest vertex index, as the
    paper's strict '<' scan does.  ``rnd`` is applied to every computed
    value (identity: float64 reference; ``round_bf16``: the control).
    """
    adj = rnd(np.array(adj, np.float64))
    wl = rnd(np.array(w_local, np.float64))
    wc = rnd(np.array(w_cloud, np.float64))
    k, n = wl.shape
    rows = np.arange(k)
    total_local = rnd(wl.sum(axis=1))
    members = np.broadcast_to(np.eye(n, dtype=bool), (k, n, n)).copy()
    alive = np.ones((k, n), bool)

    def merge(s, t):
        """Algorithm 1: fold vertex t into vertex s (per-graph indices)."""
        adj[rows, s, :] = rnd(adj[rows, s, :] + adj[rows, t, :])
        adj[rows, :, s] = rnd(adj[rows, :, s] + adj[rows, :, t])
        adj[rows, s, s] = 0.0
        adj[rows, t, :] = 0.0
        adj[rows, :, t] = 0.0
        wl[rows, s] = rnd(wl[rows, s] + wl[rows, t])
        wc[rows, s] = rnd(wc[rows, s] + wc[rows, t])
        wl[rows, t] = 0.0
        wc[rows, t] = 0.0
        members[rows, s] |= members[rows, t]
        members[rows, t] = False
        alive[rows, t] = False

    pinned = np.nonzero(~np.asarray(offloadable, bool))[0]
    source = int(pinned[0]) if pinned.size else 0
    src = np.full(k, source)
    for other in pinned[1:]:
        merge(src, np.full(k, int(other)))

    best = np.full(k, np.inf)
    best_cloud = np.zeros((k, n), bool)
    while alive[0].sum() > 1:
        gains = rnd(wl - wc)
        in_a = np.zeros((k, n), bool)
        in_a[rows, src] = True
        conn = adj[rows, src, :].copy()
        s = src.copy()
        t = src.copy()
        for _ in range(int(alive[0].sum()) - 1):
            scores = np.where(alive & ~in_a, rnd(conn - gains), -np.inf)
            v = np.argmax(scores, axis=1)
            in_a[rows, v] = True
            conn = rnd(conn + adj[rows, v, :])
            s, t = t, v
        comm = rnd((adj[rows, t, :] * alive).sum(axis=1))
        cut = rnd(rnd(total_local - gains[rows, t]) + comm)
        better = cut < best
        best = np.where(better, cut, best)
        best_cloud = np.where(better[:, None], members[rows, t], best_cloud)
        merge(s, t)
    return best, ~best_cloud


def clamp(cut, local, no_offload):
    """Section 4.3: the all-local plan wins where it is strictly cheaper."""
    worse = no_offload < cut
    return np.where(worse, no_offload, cut), np.where(worse[:, None], True, local)


def solve(profile: dict, envs: np.ndarray, rnd=identity):
    """The placement the service owes each environment: (cut, mask), clamped."""
    wl, wc, adj = build(profile, envs)
    cut, local = mcop(wl, wc, adj, profile["offloadable"], rnd)
    return clamp(cut, local, wl.sum(axis=1))


# ----------------------------------------------------------------------
# cache bins and the session engine's drift decision
# ----------------------------------------------------------------------


def bin_keys(envs: np.ndarray) -> np.ndarray:
    """(K, 6) environments -> (K, 6) integer bins, round(ln x / ln 1.1)."""
    x = np.asarray(envs, np.float64)
    safe = np.where(x > 0.0, x, 1.0)
    b = np.round(np.log(safe) / np.log1p(BIN_STEP)).astype(np.int64)
    return np.where(x > 0.0, b, np.int64(-(2**31)))


def drift_exceeded(anchor: np.ndarray, obs: np.ndarray, threshold: float) -> np.ndarray:
    """Relative drift of bandwidth up, down or speedup strictly above threshold."""
    rel = np.abs(obs[:, :3] - anchor) / np.maximum(np.abs(anchor), 1e-30)
    return (rel > threshold).any(axis=1)
