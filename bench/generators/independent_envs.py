"""Requests whose environments are drawn independently, one per request.

Each request comes from one of ``users`` users and carries its own
environment: uplink and downlink bandwidths independent log-normals
(median and sigma in ln), speedup log-uniform over a range, powers fixed.
Nearly every request opens its own placement-cache bin, so the working
set is far beyond the cache and the solve flush does the work.
"""

from __future__ import annotations

import numpy as np


def requests(params: dict, seed: int, count: int) -> tuple[np.ndarray, np.ndarray]:
    """(users (count,), environments (count, 6)): a pure function of the seed."""
    rng = np.random.default_rng([seed, 1])
    users = rng.integers(params["users"], size=count)
    sigma = params["bandwidth_sigma_ln"]
    up = params["bandwidth_median"] * np.exp(sigma * rng.standard_normal(count))
    down = params["bandwidth_median"] * np.exp(sigma * rng.standard_normal(count))
    lo, hi = np.log(params["speedup_range"])
    speedup = np.exp(rng.uniform(lo, hi, count))
    powers = np.broadcast_to(params["powers"], (count, 3))
    return users, np.column_stack([up, down, speedup, powers])
