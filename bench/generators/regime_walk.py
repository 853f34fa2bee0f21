"""Requests from users who each walk a few recurring network regimes.

Each request comes from one of ``users`` users, drawn uniformly, and
carries that user's next environment.  A user's environments are the
regime walk of the program's workload model, copied here: dwell 2-5
observations in a regime, then hop to an adjacent one; each observation
is the regime's (bandwidth, speedup) with 2% relative noise, bandwidth
symmetric.  Users in one regime land in the same few cache bins, so
nearly every request is a cache hit or a coalesced follower.
"""

from __future__ import annotations

import numpy as np


def _walks(params: dict, rng, users: int, steps: int) -> np.ndarray:
    """(users, steps) regime indices, one seeded walk per user."""
    n_regimes = len(params["regimes"])
    lo, hi = params["dwell"]
    regime = rng.integers(n_regimes, size=users)
    left = rng.integers(lo, hi + 1, size=users)
    out = np.empty((users, steps), np.int64)
    for step in range(steps):
        out[:, step] = regime
        left -= 1
        hop = left <= 0
        move = rng.choice((-1, 1), size=users)
        regime = np.where(hop, np.clip(regime + move, 0, n_regimes - 1), regime)
        left = np.where(hop, rng.integers(lo, hi + 1, size=users), left)
    return out


def requests(params: dict, seed: int, count: int) -> tuple[np.ndarray, np.ndarray]:
    """(users (count,), environments (count, 6)): a pure function of the seed."""
    rng = np.random.default_rng([seed, 2])
    users = rng.integers(params["users"], size=count)
    # the k-th request of a user takes step k of that user's walk
    order = np.argsort(users, kind="stable")
    sorted_users = users[order]
    first = np.searchsorted(sorted_users, sorted_users, side="left")
    step = np.empty(count, np.int64)
    step[order] = np.arange(count) - first
    walks = _walks(params, rng, params["users"], int(step.max()) + 1 if count else 1)
    regime = walks[users, step]
    band = np.array([r["bandwidth"] for r in params["regimes"]])[regime]
    speed = np.array([r["speedup"] for r in params["regimes"]])[regime]
    noise = 1.0 + params["rel_noise"] * rng.standard_normal((count, 2))
    up, speed = band * noise[:, 0], speed * noise[:, 1]
    powers = np.broadcast_to(params["powers"], (count, 3))
    return users, np.column_stack([up, up, speed, powers])
