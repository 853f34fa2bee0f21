"""Sharded solver fleet: plan math + bit-identical multi-device parity.

The PR-9 acceptance suite.  The host-side tests pin down the pure-numpy
shard plan (round-robin placement, inert padding, exact inverse) and the
``mesh=`` argument normalization.  The parity tests run in subprocesses
behind ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (jax
freezes the device count at first import — the main pytest process must
keep the real single CPU) and compare the 8-way sharded solve plane
against the forced single-device path with ``==`` — no tolerances:

* ``solve_envs`` across the Fig.-2 topologies × three cost models, with
  an uneven K=13 batch (padding + round-robin both engaged);
* the packed ``mcop_batch``/``WCGBatch`` flush path;
* a full ``tick_sessions`` tick — every event column, prices, cache
  counters — plus the empty-miss-set second tick (no solve dispatched;
  the sharded plane must stay out of the way entirely).
* Granite-34B-Code's 90-vertex layer split at bucket 128, against the
  single-device flush at 128 and at 256; its pack moves 128² rows.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.mcop_shard import ShardPlan, resolve_mesh, shard_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.service


def run_sub(body: str, devices: int = 8, timeout: int = 420) -> str:
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ----------------------------------------------------------------------
# Shard plan: pure host math
# ----------------------------------------------------------------------


def test_shard_plan_round_robin_property():
    plan = shard_plan(13, 8)
    assert plan.pad == 3 and plan.k == 13 and plan.rows_per_shard == 2
    # device-major layout: position p of the permuted batch belongs to
    # device p // rows_per_shard, and must hold a row whose original
    # index i satisfies i % shards == that device
    for p, i in enumerate(plan.perm):
        assert i % plan.shards == p // plan.rows_per_shard, (p, i)


def test_shard_plan_inverse_restores_order():
    for k, d in [(13, 8), (16, 8), (5, 2), (1, 4), (64, 8)]:
        plan = shard_plan(k, d)
        x = np.arange(k + plan.pad)
        assert np.array_equal(x[plan.perm][plan.inverse], x)
        assert (k + plan.pad) % d == 0


def test_shard_plan_no_pad_when_divisible():
    plan = shard_plan(16, 8)
    assert plan.pad == 0 and plan.rows_per_shard == 2


def test_shard_plan_rejects_degenerate_inputs():
    with pytest.raises(ValueError):
        shard_plan(0, 8)
    with pytest.raises(ValueError):
        shard_plan(8, 0)


def test_shard_plan_is_a_namedtuple_with_stable_fields():
    plan = shard_plan(4, 2)
    assert isinstance(plan, ShardPlan)
    assert plan._fields == ("shards", "k", "pad", "perm", "inverse")


# ----------------------------------------------------------------------
# mesh= argument normalization (single-device host: auto collapses)
# ----------------------------------------------------------------------


def test_resolve_mesh_false_forces_single_device():
    assert resolve_mesh(False) is None


def test_resolve_mesh_auto_is_none_on_single_device_host():
    import jax

    if jax.device_count() > 1:
        pytest.skip("host sees a real fleet; auto resolves to it")
    assert resolve_mesh(None) is None


def test_resolve_mesh_collapses_one_shard_mesh():
    from repro.launch.mesh import make_solver_mesh

    import jax

    mesh = make_solver_mesh(jax.devices()[:1])
    assert resolve_mesh(mesh) is None


def test_resolve_mesh_rejects_junk():
    with pytest.raises(TypeError):
        resolve_mesh(8)


# ----------------------------------------------------------------------
# 8-device parity (subprocess): solve_envs + mcop_batch, all topologies
# ----------------------------------------------------------------------


def test_sharded_solve_envs_and_mcop_batch_bit_identical_on_8_devices():
    run_sub(
        """
        import numpy as np, jax
        from repro.core import (AppProfile, EnergyModel, ResponseTimeModel,
                                WeightedModel, linear_graph, loop_graph,
                                mesh_graph, tree_graph)
        from repro.core.cost_models import EnvArrays
        from repro.core.mcop import WCGBatch, mcop_batch, solve_envs
        from repro.core.mcop_shard import default_solver_mesh
        from repro.obs import Tracer

        assert jax.device_count() == 8
        mesh = default_solver_mesh()
        assert mesh is not None

        TOPOLOGIES = {
            'linear': linear_graph(9, rng=np.random.default_rng(1)),
            'loop': loop_graph(8, rng=np.random.default_rng(2)),
            'tree': tree_graph(10, rng=np.random.default_rng(3)),
            'mesh': mesh_graph(3, 3, rng=np.random.default_rng(4)),
        }
        MODELS = {'time': ResponseTimeModel(), 'energy': EnergyModel(),
                  'weighted': WeightedModel(0.35)}
        rng = np.random.default_rng(7)
        k = 13  # uneven on 8 shards: pad=3 + round-robin both engaged
        envs = EnvArrays(*(rng.uniform(0.5, 5.0, k) for _ in range(6)))

        for tname, graph in TOPOLOGIES.items():
            profile = AppProfile.from_wcg_times(graph)
            for mname, model in MODELS.items():
                tr = Tracer()
                sharded = solve_envs(profile, model, envs, backend='jax',
                                     mesh=mesh, tracer=tr)
                single = solve_envs(profile, model, envs, backend='jax',
                                    mesh=False)
                for rs, r1 in zip(sharded, single):
                    assert rs.min_cut == r1.min_cut, (tname, mname)
                    assert np.array_equal(rs.local_mask, r1.local_mask)
                spans = tr.spans('solve_envs.shard')
                assert len(spans) == 8, (tname, mname, len(spans))
                assert {s.attrs['shard'] for s in spans} == set(range(8))
                assert all(s.attrs['devices'] == 8 for s in spans)

        # packed WCGBatch flush path (mcop_batch), both array backends
        graphs = [linear_graph(4 + (i % 10), rng=np.random.default_rng(10 + i))
                  for i in range(13)]
        batch = WCGBatch.from_wcgs(graphs, m=16)
        for backend in ('jax', 'pallas'):
            sharded = mcop_batch(batch, backend=backend, mesh=mesh)
            single = mcop_batch(batch, backend=backend, mesh=False)
            for rs, r1 in zip(sharded, single):
                assert rs.min_cut == r1.min_cut, backend
                assert np.array_equal(rs.local_mask, r1.local_mask)
        print('OK')
        """
    )


def test_sharded_tick_sessions_bit_identical_on_8_devices():
    run_sub(
        """
        import numpy as np, jax
        from repro.core import (AppProfile, EnvQuantizer, PlacementCache,
                                ResponseTimeModel, SessionBatch,
                                tree_graph, tick_sessions)
        from repro.core.cost_models import EnvArrays
        from repro.core.mcop_shard import default_solver_mesh

        assert jax.device_count() == 8
        mesh = default_solver_mesh()
        profile = AppProfile.from_wcg_times(
            tree_graph(10, rng=np.random.default_rng(3)))
        rng = np.random.default_rng(5)
        k = 13

        def drive(mesh_arg):
            batch = SessionBatch.create(k, profile.n, threshold=0.15,
                                        min_interval=2)
            batch.activate(np.arange(k))
            cache = PlacementCache(EnvQuantizer())
            envs = EnvArrays(*(np.asarray(c) for c in
                               (rng.uniform(0.5, 5.0, (6, k)))))
            reps = []
            # tick 0: k fresh sessions -> solve flush through the fleet;
            # tick 1: same envs, cooldown holds -> EMPTY miss set (the
            # sharded plane must not dispatch anything)
            for t in range(2):
                reps.append(tick_sessions(
                    batch, envs, profile=profile,
                    model=ResponseTimeModel(), cache=cache,
                    backend='jax', mesh=mesh_arg, tick=t))
            return reps, cache.stats

        rng_state = rng.bit_generator.state
        sharded, stats_sh = drive(mesh)
        rng.bit_generator.state = rng_state  # identical envs both runs
        single, stats_1 = drive(False)

        assert stats_sh == stats_1
        for t, (rs, r1) in enumerate(zip(sharded, single)):
            assert rs.solved == r1.solved and rs.coalesced == r1.coalesced
            assert np.array_equal(rs.repartitioned, r1.repartitioned), t
            assert np.array_equal(rs.placements, r1.placements), t
            assert np.array_equal(rs.partial_cost, r1.partial_cost), t
            assert np.array_equal(rs.min_cut, r1.min_cut, equal_nan=True), t
            assert np.array_equal(rs.no_offload_cost, r1.no_offload_cost), t
            assert np.array_equal(rs.full_offload_cost, r1.full_offload_cost), t
        assert sharded[0].solved > 0      # tick 0 really flushed
        assert sharded[1].solved == 0     # tick 1 really was empty
        print('OK')
        """
    )


def test_fleet_flush_at_bucket_128_bit_identical_on_8_devices():
    """Granite's 90-vertex layer split flushes at bucket 128 on the fleet:
    bit-identical to the single-device flush at 128 and at 256, and the
    pack moves rows of 128² adjacency, not 256²."""
    run_sub(
        """
        import numpy as np, jax
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.core import Environment, ResponseTimeModel
        from repro.core.graph import WCGBatch
        from repro.core.mcop import DEFAULT_BUCKETS, _bucket_size, mcop_batch
        from repro.core.mcop_shard import default_solver_mesh
        from repro.obs import Tracer
        from repro.profilers.program import app_profile_from_config

        assert jax.device_count() == 8
        mesh = default_solver_mesh()
        profile = app_profile_from_config(
            get_config('granite-34b'), ShapeConfig('decode_8k', 'decode', 8192, 128),
            local_flops_per_s=1e13)
        m = _bucket_size(profile.n, DEFAULT_BUCKETS)
        assert (profile.n, m) == (90, 128)
        rng = np.random.default_rng(11)
        k = 13  # uneven on 8 shards: pad=3 + round-robin both engaged
        graphs = [ResponseTimeModel().build(profile, Environment(
                      bandwidth_up=float(10 ** rng.uniform(0.5, 3.5)),
                      bandwidth_down=float(10 ** rng.uniform(0.5, 3.5)),
                      speedup=float(rng.uniform(1.5, 12.0))))
                  for _ in range(k)]

        tr = Tracer()
        sharded = mcop_batch(WCGBatch.from_wcgs(graphs, m=m), mesh=mesh, tracer=tr)
        single = mcop_batch(WCGBatch.from_wcgs(graphs, m=m), mesh=False)
        at_256 = mcop_batch(WCGBatch.from_wcgs(graphs, m=256), mesh=False)
        for rs, r1, r2 in zip(sharded, single, at_256, strict=True):
            assert rs.min_cut == r1.min_cut == r2.min_cut
            assert np.array_equal(rs.local_mask, r1.local_mask)
            assert np.array_equal(rs.local_mask, r2.local_mask)
        (pack,) = tr.spans('solve.shard_pack')
        # float32 adjacency, two weight rows and the pins, for 13 + 3 rows
        assert (pack.attrs['k'], pack.attrs['pad']) == (13, 3)
        assert pack.attrs['bytes'] == 16 * (128 * 128 * 4 + 2 * 128 * 4 + 128)
        print('OK')
        """
    )


def test_fleet_flush_packs_in_a_span_and_names_its_programs():
    run_sub(
        """
        import re
        import numpy as np, jax
        from repro.core import AppProfile, ResponseTimeModel, linear_graph
        from repro.core.cost_models import EnvArrays
        from repro.core.mcop import WCGBatch, _fused_solver, mcop_batch, solve_envs
        from repro.core.mcop_shard import _sharded_dispatch, default_solver_mesh
        from repro.obs import Tracer

        mesh = default_solver_mesh()
        graphs = [linear_graph(6, rng=np.random.default_rng(i)) for i in range(5)]
        batch = WCGBatch.from_wcgs(graphs, m=16)
        tr = Tracer()
        mcop_batch(batch, backend='jax', mesh=mesh, tracer=tr)
        (pack,) = tr.spans('solve.shard_pack')
        # 5 rows + 3 inert on 4 devices; adj, two weight rows and the pins
        assert pack.attrs == {'k': 5, 'pad': 3, 'devices': 4,
                              'bytes': 8 * (16 * 16 * 4 + 2 * 16 * 4 + 16)}
        (wait,) = tr.spans('solve.wait')
        assert pack.t1 <= wait.t0

        profile = AppProfile.from_wcg_times(graphs[0])
        envs = EnvArrays(*(np.full(6, 2.0) for _ in range(6)))
        tr = Tracer()
        solve_envs(profile, ResponseTimeModel(), envs, backend='jax', mesh=mesh, tracer=tr)
        (pack,) = tr.spans('solve.shard_pack')
        assert (pack.attrs['k'], pack.attrs['pad'], pack.attrs['bytes']) == (6, 2, 8 * 6 * 4)

        def module(lowered):
            return re.search(r'module @(\\S+)', lowered.as_text()).group(1)

        args = batch.adj[:4].astype(np.float32), *(np.zeros((4, 16), np.float32),) * 2
        fleet = _sharded_dispatch(mesh, 'jax', None).lower(*args, np.ones((4, 16), bool))
        assert module(fleet) == 'jit__mcop_fleet_solve'
        fused = _fused_solver(ResponseTimeModel(), 'jax', None, mesh)
        env4 = EnvArrays(*(np.ones(4, np.float32) for _ in range(6)))
        m16 = np.zeros(16, np.float32), np.zeros((16, 16), np.float32), np.zeros((16, 16), np.float32)
        assert module(fused.lower(*m16, np.ones(16, bool), env4)) == 'jit__mcop_fleet_fused'
        print('OK')
        """,
        devices=4,
    )


@pytest.mark.parametrize("devices", [4, 8])
def test_fleet_flush_with_the_lockstep_kernel_bit_identical(devices):
    """The rule forced to the lockstep kernel (as on a TPU, here in
    interpret mode): granite's bucket-128 flush on the fleet, through
    the packed path and the fused one, is bit-identical to the
    one-device flush, and each wait names the kernel."""
    run_sub(
        f"""
        import sys
        import numpy as np, jax
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.core import Environment, ResponseTimeModel
        from repro.core.cost_models import EnvArrays
        from repro.core.graph import WCGBatch
        from repro.core.mcop_shard import default_solver_mesh
        from repro.obs import Tracer
        from repro.profilers.program import app_profile_from_config

        mcop = sys.modules['repro.core.mcop']
        assert jax.device_count() == {devices}
        mesh = default_solver_mesh()
        profile = app_profile_from_config(
            get_config('granite-34b'), ShapeConfig('decode_8k', 'decode', 8192, 128),
            local_flops_per_s=1e13)
        rng = np.random.default_rng(17)
        k = 13  # uneven on the fleet: inert padding + round-robin
        envs = EnvArrays(10 ** rng.uniform(0.5, 3.5, k), 10 ** rng.uniform(0.5, 3.5, k),
                         rng.uniform(1.5, 12.0, k), *(rng.uniform(0.1, 2.0, k) for _ in range(3)))
        batch = ResponseTimeModel().build_batch(profile, envs, m=128)
        xla = mcop.mcop_batch(batch, mesh=False)

        mcop._solver_for = lambda platform, m, dtype: 'lockstep'
        tr = Tracer()
        sharded = mcop.mcop_batch(batch, mesh=mesh, tracer=tr)
        single = mcop.mcop_batch(batch, mesh=False, tracer=tr)
        fused_sh = mcop.solve_envs(profile, ResponseTimeModel(), envs, mesh=mesh, tracer=tr)
        fused_1 = mcop.solve_envs(profile, ResponseTimeModel(), envs, mesh=False, tracer=tr)
        for rs, r1, rf, rf1, rx in zip(sharded, single, fused_sh, fused_1, xla, strict=True):
            assert rs.min_cut == r1.min_cut
            assert np.array_equal(rs.local_mask, r1.local_mask)
            assert rf.min_cut == rf1.min_cut
            assert np.array_equal(rf.local_mask, rf1.local_mask)
            assert r1.min_cut == rx.min_cut
            assert np.array_equal(r1.local_mask, rx.local_mask)
        assert [w.attrs['solver'] for w in tr.spans('solve.wait')] == ['lockstep'] * 4
        assert len(tr.spans('solve.shard')) == {devices}
        print('OK')
        """,
        devices=devices,
    )
