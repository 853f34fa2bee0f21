"""A fixed pool of session slots with arrivals, churn and regime walks.

A copy of the program's ``TrafficGenerator``: per step, each live session
departs with probability ``churn``; Poisson(``arrival_rate``) arrivals
(plus ``initial`` on the first step) fill the lowest free slots; live
sessions walk the regimes (dwell 2-5 steps, hop to an adjacent regime)
and observe the regime's (bandwidth, speedup) with 2% relative noise,
bandwidth symmetric.  Every draw is a fixed-size array per step, so the
stream is a pure function of (seed, capacity, step).  Inactive rows carry
the placeholder environment (1, 1, 1, powers).
"""

from __future__ import annotations

import numpy as np


class SessionPool:
    def __init__(self, params: dict, seed: int, stream: int):
        self.capacity = cap = int(params["capacity"])
        self.params = params
        self.rng = np.random.default_rng([seed, 3, stream])
        self.band = np.array([r["bandwidth"] for r in params["regimes"]])
        self.speed = np.array([r["speedup"] for r in params["regimes"]])
        self.active = np.zeros(cap, bool)
        self.regime = np.zeros(cap, np.int64)
        self.left = np.zeros(cap, np.int64)
        self.steps = 0

    def step(self):
        """One tick: (envs (cap, 6), active, arrived, departed) masks."""
        p, rng, cap = self.params, self.rng, self.capacity
        n_regimes = len(self.band)
        lo, hi = p["dwell"]
        departed = self.active & (rng.random(cap) < p["churn"])
        self.active &= ~departed
        n_arrivals = int(rng.poisson(p["arrival_rate"])) + (p["initial"] if self.steps == 0 else 0)
        arrived = np.zeros(cap, bool)
        arrived[np.nonzero(~self.active)[0][:n_arrivals]] = True
        arr_regime = rng.integers(n_regimes, size=cap)
        arr_dwell = rng.integers(lo, hi + 1, size=cap)
        hop_dir = rng.choice((-1, 1), size=cap)
        hop_dwell = rng.integers(lo, hi + 1, size=cap)
        noise = 1.0 + p["rel_noise"] * rng.standard_normal((cap, 2))
        self.regime = np.where(arrived, arr_regime, self.regime)
        self.left = np.where(arrived, arr_dwell, self.left)
        self.active |= arrived
        ongoing = self.active & ~arrived
        self.left = np.where(ongoing, self.left - 1, self.left)
        hop = ongoing & (self.left <= 0)
        self.regime = np.where(hop, np.clip(self.regime + hop_dir, 0, n_regimes - 1), self.regime)
        self.left = np.where(hop, hop_dwell, self.left)
        band = np.where(self.active, self.band[self.regime] * noise[:, 0], 1.0)
        speed = np.where(self.active, self.speed[self.regime] * noise[:, 1], 1.0)
        powers = np.broadcast_to(p["powers"], (cap, 3))
        envs = np.column_stack([band, band, speed, powers])
        self.steps += 1
        return envs, self.active.copy(), arrived, departed
