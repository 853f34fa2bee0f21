"""Profile of an application given as its profiled call graph.

The configuration lists each method with its local execution time and
whether it may leave the device, and each call edge with the kilobytes it
moves.  Times stay in the configuration's unit (ms); an edge of ``kb``
kilobytes costs ``kb / (B * 1024) * 1000`` ms at B MB/s, half of it on the
uplink with the call and half on the downlink with the return.
"""

from __future__ import annotations

import numpy as np


def build(params: dict) -> dict:
    names = [v["name"] for v in params["vertices"]]
    index = {name: i for i, name in enumerate(names)}
    n = len(names)
    data_in = np.zeros((n, n))
    for caller, callee, kb in params["edges"]:
        data_in[index[caller], index[callee]] = kb * 1000.0 / 1024.0 / 2.0
    return {
        "names": names,
        "t_local": np.array([float(v["t_local"]) for v in params["vertices"]]),
        "data_in": data_in,
        "data_out": data_in.copy(),
        "offloadable": np.array([not v.get("pinned", False) for v in params["vertices"]]),
    }
