"""Profile of one decode step of a dense transformer, split per layer.

Vertices: the embedding, one per transformer layer, the LM head.  The
embedding and the head are pinned to the device (the sampler feeds the
front end).  A layer's local time is its FLOPs over the device tier's
assumed rate; each stage boundary moves one step's activations
(tokens x d_model x dtype bytes, in the configuration's data unit), so
the graph is a chain.
Per-layer FLOPs are those of the program's analytic profiler: weights
2 x params x tokens, attention reading the cache 4 x batch x context x
heads x head_dim; parameters per layer are attention (q, k, v, o with the
published KV heads), an FFN of ``ffn_matrices`` d_model x d_ff matrices
(two for a GELU MLP, three for a gated one) and two norms.
"""

from __future__ import annotations

import numpy as np


def build(params: dict) -> dict:
    m = params["model"]
    shape = params["shape"]
    d, heads, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    b = m["dtype_bytes"]
    layer_params = d * heads * hd + 2 * d * kv * hd + heads * hd * d + m["ffn_matrices"] * d * m["d_ff"] + 2 * d
    tokens = shape["batch"]  # decode: one new token per sequence
    layer_flops = 2.0 * layer_params * tokens + 4.0 * shape["batch"] * shape["seq_len"] * heads * hd
    flops = (
        [2.0 * tokens * d]
        + [layer_flops] * m["n_layers"]
        + [2.0 * shape["batch"] * d * m["vocab_size"]]
    )
    unit = params["data_unit_bytes"]
    act = tokens * d * b / unit
    n = len(flops)
    data_in = np.zeros((n, n))
    for i in range(n - 1):
        data_in[i, i + 1] = act
    offloadable = np.ones(n, dtype=bool)
    offloadable[[0, n - 1]] = False
    return {
        "names": ["embed"] + [f"layer{i}" for i in range(m["n_layers"])] + ["head"],
        "t_local": np.array(flops) / params["local_flops_per_s"],
        "data_in": data_in,
        "data_out": np.zeros((n, n)),
        "offloadable": offloadable,
    }
