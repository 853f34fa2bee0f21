"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret=True."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import brute_force, mcop, mcop_reference, paper_example_graph, random_wcg
from repro.kernels import flash_attention, mamba_chunk_scan, ref
from repro.kernels.flash_attention import flash_attention_kernel


# ----------------------------------------------------------------------
# Flash attention
# ----------------------------------------------------------------------

FLASH_CASES = [
    # (B, H, Hkv, Sq, Sk, hd, causal, window, dtype, block)
    (1, 2, 2, 16, 16, 8, True, None, jnp.float32, 8),
    (2, 4, 2, 33, 47, 16, True, None, jnp.float32, 16),
    (2, 4, 1, 40, 40, 32, True, 8, jnp.float32, 16),
    (1, 8, 8, 64, 64, 64, False, None, jnp.float32, 32),
    (1, 4, 2, 128, 128, 16, True, None, jnp.bfloat16, 64),
    (3, 2, 2, 17, 63, 8, False, 16, jnp.float32, 16),
    (1, 16, 4, 96, 96, 128, True, None, jnp.float32, 32),
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=[str(i) for i in range(len(FLASH_CASES))])
def test_flash_attention_matches_reference(case):
    b, h, hkv, sq, sk, hd, causal, window, dtype, blk = case
    rng = np.random.default_rng(hash(case) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, h, sq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, sk, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, sk, hd)), dtype)
    out = flash_attention_kernel(
        q, k, v, causal=causal, window=window, block_q=blk, block_k=blk
    )
    exp = ref.flash_reference(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_model_layout_wrapper():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 24, 4, 16)), jnp.float32)  # (B,S,H,hd)
    k = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 24, 2, 16)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    exp = ref.flash_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)


def test_flash_matches_model_chunked_attention():
    """The kernel agrees with the model-side jnp online-softmax path too."""
    from repro.models.attention import chunked_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    b = chunked_attention(q, k, v, mask_kind="causal", chunk_q=8, chunk_k=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------
# Mamba chunk scan
# ----------------------------------------------------------------------

MAMBA_CASES = [
    # (B, S, H, P, N, chunk)
    (1, 8, 1, 4, 2, 4),
    (2, 32, 3, 8, 4, 8),
    (1, 64, 2, 16, 16, 16),
    (2, 24, 4, 8, 8, 24),      # single chunk
    (1, 128, 1, 32, 8, 32),
]


@pytest.mark.parametrize("case", MAMBA_CASES, ids=[str(i) for i in range(len(MAMBA_CASES))])
def test_mamba_chunk_scan_matches_token_recurrence(case):
    b, s, h, p, n, chunk = case
    rng = np.random.default_rng(hash(case) % 2**31)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 1.0, size=(b, s, h)), jnp.float32)
    ld = -jnp.asarray(rng.uniform(0.01, 0.8, size=(b, s, h)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, h, p, n)), jnp.float32)
    y, hT = mamba_chunk_scan(x, dt, ld, bm, cm, h0, chunk=chunk)
    nc = s // min(chunk, s)
    q = s // nc
    yr, hr = ref.mamba_chunk_scan_reference(
        x.reshape(b, nc, q, h, p).transpose(0, 3, 1, 2, 4),
        dt.reshape(b, nc, q, h).transpose(0, 3, 1, 2),
        ld.reshape(b, nc, q, h).transpose(0, 3, 1, 2),
        bm.reshape(b, nc, q, n),
        cm.reshape(b, nc, q, n),
        h0,
    )
    yr = yr.transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr), atol=1e-4, rtol=1e-4)


def test_mamba_kernel_matches_model_ssd_path():
    """Kernel output == the model's chunked SSD math for one layer core."""
    from repro.configs import ARCHITECTURES, reduce_config
    from repro.models import ssm

    cfg = reduce_config(ARCHITECTURES["zamba2-1.2b"])
    p = ssm.init_mamba2(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 32, cfg.d_model)), jnp.float32)
    y_model, st_model = ssm.mamba2_forward(cfg, p, x)

    # recompute through the kernel using the same projections
    z, xbc, dt_raw = ssm._mamba_project(cfg, p, x)
    xbc, conv_state = ssm._causal_conv(p, xbc, None, valid_len=x.shape[1])
    xs, bmat, cmat = ssm._split_xbc(cfg, xbc)
    d_inner, n_heads, n_state = ssm._mamba_dims(cfg)
    hd = cfg.mamba_headdim
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    ld = dt * a
    xh = xs.reshape(2, 32, n_heads, hd)
    h0 = jnp.zeros((2, n_heads, hd, n_state), jnp.float32)
    y, hT = mamba_chunk_scan(xh, dt, ld, bmat, cmat, h0, chunk=cfg.ssm_chunk)
    y = y + np.asarray(p["d_skip"])[None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(2, 32, d_inner).astype(x.dtype)
    from repro.models import common

    y = common.rmsnorm(p["norm"], y * jax.nn.silu(z), eps=cfg.norm_eps)
    y = common.linear(p["out_proj"], y)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_model, np.float32), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(hT), np.asarray(st_model.h), atol=1e-4, rtol=1e-4
    )


# ----------------------------------------------------------------------
# MCOP full kernel
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,seed",
    [(5, 100), (8, 101), (12, 102), (15, 103), (10, 104)]
    + [(9, seed) for seed in range(8)],
)
def test_mcop_kernel_full_algorithm_matches_reference(n, seed):
    """The Pallas full kernel is the SAME algorithm as mcop_reference —
    same (possibly suboptimal, see test_mcop_property) cut, same mask."""
    g = random_wcg(n, rng=np.random.default_rng(seed))
    res = mcop(g, backend="pallas")
    ref_res = mcop_reference(g)
    assert res.min_cut == pytest.approx(ref_res.min_cut, rel=1e-5)
    assert (res.local_mask == ref_res.local_mask).all()
    assert g.total_cost(res.local_mask) == pytest.approx(res.min_cut, rel=1e-5)
    # never better than the true optimum (up to the kernel's f32 rounding)
    assert res.min_cut >= brute_force(g).cost * (1 - 1e-5) - 1e-4


def test_mcop_kernel_paper_example():
    g = paper_example_graph()
    res = mcop(g, backend="pallas")
    assert res.min_cut == pytest.approx(22.0)
    assert (res.local_mask == mcop_reference(g).local_mask).all()


# ----------------------------------------------------------------------
# Interpret-mode selection
# ----------------------------------------------------------------------


def test_default_interpret_env_override(monkeypatch):
    """REPRO_PALLAS_INTERPRET forces/suppresses interpret mode without
    code edits (the TPU-validation knob); unset falls back to backend
    detection, garbage raises."""
    from repro.kernels import ops

    try:
        for raw, want in [
            ("1", True), ("true", True), ("YES", True), (" on ", True),
            ("0", False), ("false", False), ("No", False), ("off", False),
        ]:
            monkeypatch.setenv("REPRO_PALLAS_INTERPRET", raw)
            ops.default_interpret.cache_clear()
            assert ops.default_interpret() is want, raw

        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
        ops.default_interpret.cache_clear()
        with pytest.raises(ValueError):
            ops.default_interpret()

        monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
        ops.default_interpret.cache_clear()
        assert ops.default_interpret() is (not ops.on_tpu())
    finally:
        ops.default_interpret.cache_clear()


# ----------------------------------------------------------------------
# Compiled (non-interpret) tier + blocked grid + fused build+solve
# ----------------------------------------------------------------------


def _sw_batch(b=10, n=9, seed=42):
    rng = np.random.default_rng(seed)
    graphs = [random_wcg(n, rng=rng) for _ in range(b)]
    adj = np.stack([g.adj for g in graphs]).astype(np.float32)
    wl = np.stack([g.w_local for g in graphs]).astype(np.float32)
    wc = np.stack([g.w_cloud for g in graphs]).astype(np.float32)
    pin = np.stack([~g.offloadable for g in graphs])
    return graphs, adj, wl, wc, pin


def test_mcop_kernel_compiled_noninterpret_path(monkeypatch):
    """REPRO_PALLAS_INTERPRET=0 routes the batch kernel through the real
    Pallas compile pipeline.  Only a TPU backend compiles the kernel, so
    other platforms skip; on TPU a compiler refusal fails the test, and
    the compiled tier is pinned to the interpret tier bitwise."""
    from repro.kernels import ops
    from repro.core.mcop import mcop_stoer_wagner_kernel

    if jax.devices()[0].platform != "tpu":
        pytest.skip("compiled Pallas kernels need a TPU backend")
    _, adj, wl, wc, pin = _sw_batch()
    cuts_i, masks_i = mcop_stoer_wagner_kernel(adj, wl, wc, pin, interpret=True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    ops.default_interpret.cache_clear()
    try:
        assert ops.default_interpret() is False
        cuts_c, masks_c = mcop_stoer_wagner_kernel(adj, wl, wc, pin)
        cuts_c = np.asarray(cuts_c)
        assert np.array_equal(cuts_c, np.asarray(cuts_i))
        assert np.array_equal(np.asarray(masks_c), np.asarray(masks_i))
    finally:
        ops.default_interpret.cache_clear()


def test_mcop_kernel_block_graphs_bitwise_invariant():
    """The lockstep group is a pure scheduling choice: g=1 and g=3 (both
    whole tiles of 8: two groups for b=10, the second padded) and the
    auto choice (one group of 16) must produce bit-identical cuts and
    masks, all matching the numpy oracle."""
    from repro.core.mcop import mcop_stoer_wagner_kernel
    from repro.kernels.mcop_phase import default_block_graphs

    graphs, adj, wl, wc, pin = _sw_batch()
    runs = {}
    for g in (1, 3, None):
        cuts, masks = mcop_stoer_wagner_kernel(
            adj, wl, wc, pin, interpret=True, block_graphs=g
        )
        runs[g] = (np.asarray(cuts), np.asarray(masks))
    base_cuts, base_masks = runs[1]
    for g in (3, None):
        assert np.array_equal(runs[g][0], base_cuts), g
        assert np.array_equal(runs[g][1], base_masks), g
    for i, wcg in enumerate(graphs):
        assert base_cuts[i] == pytest.approx(
            mcop_reference(wcg).min_cut, rel=1e-5
        )
    assert default_block_graphs(16) == 256  # a 16-vertex group fills VMEM at 256


def test_mcop_kernel_block_graphs_env_override(monkeypatch):
    from repro.kernels.mcop_phase import default_block_graphs

    monkeypatch.setenv("REPRO_MCOP_BLOCK_GRAPHS", "4")
    assert default_block_graphs(16) == 4
    monkeypatch.setenv("REPRO_MCOP_BLOCK_GRAPHS", "0")
    with pytest.raises(ValueError):
        default_block_graphs(16)


def test_fused_kernel_solve_envs_parity():
    """backend="pallas_fused" (in-kernel WCG weight build) must agree
    with the host-build "jax" path: identical masks, cut values equal to
    f32 reassociation tolerance, across all three cost-model kinds."""
    from repro.core import (
        AppProfile,
        EnergyModel,
        ResponseTimeModel,
        WeightedModel,
        linear_graph,
    )
    from repro.core.cost_models import EnvArrays
    from repro.core.mcop import solve_envs

    rng = np.random.default_rng(6)
    profile = AppProfile.from_wcg_times(linear_graph(9, rng=rng))
    envs = EnvArrays(*(rng.uniform(0.5, 5.0, 7) for _ in range(6)))
    for model in (ResponseTimeModel(), EnergyModel(), WeightedModel(0.35)):
        fused = solve_envs(profile, model, envs, backend="pallas_fused")
        plain = solve_envs(profile, model, envs, backend="jax")
        for rf, rp in zip(fused, plain):
            assert np.array_equal(rf.local_mask, rp.local_mask), model
            assert rf.min_cut == pytest.approx(rp.min_cut, rel=1e-6), model


def test_fused_kernel_rejects_unknown_model_kind():
    from repro.core import AppProfile, linear_graph
    from repro.core.cost_models import CostModel, EnvArrays
    from repro.core.mcop import solve_envs

    class Exotic(CostModel):
        name = "exotic"

        @property
        def fingerprint(self):
            return ("exotic",)

        def weights(self, graph, env):  # pragma: no cover - never called
            raise NotImplementedError

        def batch_weights(self, t_local, data_in, data_out, env):
            raise NotImplementedError  # pragma: no cover

    rng = np.random.default_rng(6)
    profile = AppProfile.from_wcg_times(linear_graph(6, rng=rng))
    envs = EnvArrays(*(rng.uniform(0.5, 5.0, 3) for _ in range(6)))
    with pytest.raises(ValueError, match="exotic"):
        solve_envs(profile, Exotic(), envs, backend="pallas_fused")
