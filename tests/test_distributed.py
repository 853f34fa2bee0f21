"""Distribution tests: sharding rules, pjit train step, pipeline — on 8
virtual host devices.

jax fixes the device count at first init, so these run in *subprocesses*
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the main
pytest process keeps the real single CPU (as required: only dryrun.py and
these child processes ever see virtual devices).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def test_param_shardings_place_leaves_on_mesh():
    run_sub(
        """
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import ARCHITECTURES, reduce_config
        from repro.models.transformer import build_model
        from repro.runtime import param_shardings, shard_params
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(data=2, model=4)
        # widen the reduced config so dims divide the mesh axes
        cfg = reduce_config(ARCHITECTURES['qwen2-7b'], d_model=64, n_heads=4,
                            n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sharded = shard_params(params, mesh)
        # attention wq sharded over model on its output dim
        wq = sharded['blocks']['attn']['wq']['w']
        assert wq.sharding.spec == P(None, None, 'model'), wq.sharding.spec
        # forward still works on sharded params
        batch = {'tokens': jax.numpy.zeros((4, 8), jax.numpy.int32),
                 'labels': jax.numpy.zeros((4, 8), jax.numpy.int32)}
        with jax.set_mesh(mesh):
            loss, _ = jax.jit(model.train_loss)(sharded, batch)
        assert bool(jax.numpy.isfinite(loss))
        print('OK')
        """
    )


def test_pjit_train_step_multidevice_matches_single_device():
    run_sub(
        """
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import ARCHITECTURES, reduce_config
        from repro.models.transformer import build_model
        from repro.train import AdamWConfig, TrainConfig, init_train_state, make_train_step
        from repro.data import DataConfig, SyntheticLMDataset
        from repro.runtime import shard_params
        from repro.launch.mesh import make_local_mesh

        cfg = reduce_config(ARCHITECTURES['qwen2-7b'], d_model=64, n_heads=4,
                            n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        data = SyntheticLMDataset(DataConfig(seq_len=16, global_batch=8,
                                             vocab_size=cfg.vocab_size), cfg)
        tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=1,
                                                 total_steps=10))
        step = make_train_step(lambda p, b: model.train_loss(p, b), tcfg)
        rng = jax.random.PRNGKey(0)

        # single-device result
        st = init_train_state(params, tcfg)
        p1, o1, _, m1 = jax.jit(step)(st.params, st.opt_state, None, data.batch(0), rng)

        # sharded result on the 2×4 mesh
        mesh = make_local_mesh(data=2, model=4)
        with jax.set_mesh(mesh):
            sp = shard_params(params, mesh)
            st2 = init_train_state(sp, tcfg)
            p2, o2, _, m2 = jax.jit(step)(st2.params, st2.opt_state, None,
                                          data.batch(0), rng)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 5e-2, \
            (float(m1['loss']), float(m2['loss']))
        # parameters agree after one update
        la = jax.tree_util.tree_leaves(p1)
        lb = jax.tree_util.tree_leaves(p2)
        worst = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                    for a, b in zip(la, lb))
        assert worst < 0.15, worst
        print('OK', float(m1['loss']), float(m2['loss']), worst)
        """
    )


def test_pipeline_apply_matches_sequential():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime.pipeline import pipeline_apply, stack_stage_params
        from repro.launch.mesh import _mk

        mesh = _mk((2, 4), ('pod', 'data'))
        L, d = 8, 16
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(L, d, d)) * 0.1 + np.eye(d), jnp.float32)

        def stage_fn(p, x):
            y, _ = jax.lax.scan(lambda x, wl: (jnp.tanh(x @ wl), None), x, p['w'])
            return y

        B, S = 16, 4
        x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
        ref = x
        for i in range(L):
            ref = jnp.tanh(ref @ w[i])
        stacked = stack_stage_params({'w': w}, 2)
        with jax.set_mesh(mesh):
            for n_micro in (1, 2, 4):
                out = pipeline_apply(stage_fn, stacked, x, mesh=mesh, n_micro=n_micro)
                err = float(jnp.abs(out - ref).max())
                assert err < 1e-6, (n_micro, err)
        print('OK')
        """
    )


def test_multipod_mesh_cross_pod_collectives():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import _mk

        mesh = _mk((2, 2, 2), ('pod', 'data', 'model'))
        x = jnp.arange(16.0).reshape(8, 2)
        with jax.set_mesh(mesh):
            xs = jax.device_put(x, NamedSharding(mesh, P(('pod', 'data'), 'model')))
            total = jax.jit(lambda a: a.sum())(xs)
        assert float(total) == float(x.sum())
        print('OK')
        """
    )


def test_checkpoint_restore_onto_different_mesh():
    """Elastic resume: save from a (2,4) mesh, restore onto (4,2)."""
    run_sub(
        """
        import tempfile, jax, numpy as np, jax.numpy as jnp
        from repro.checkpoint import CheckpointStore
        from repro.configs import ARCHITECTURES, reduce_config
        from repro.models.transformer import build_model
        from repro.runtime import param_shardings, shard_params
        from repro.launch.mesh import make_local_mesh

        cfg = reduce_config(ARCHITECTURES['qwen2-7b'], d_model=64, n_heads=4,
                            n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=256)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))

        mesh_a = make_local_mesh(data=2, model=4)
        sharded = shard_params(params, mesh_a)
        with tempfile.TemporaryDirectory() as d:
            store = CheckpointStore(d)
            store.save(1, sharded)

            mesh_b = make_local_mesh(data=4, model=2)
            shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
            target = param_shardings(shapes, mesh_b)
            restored, _extra = store.restore(1, params, shardings=target)
        # values identical, placement follows the NEW mesh
        for a, b, s in zip(jax.tree_util.tree_leaves(params),
                           jax.tree_util.tree_leaves(restored),
                           jax.tree_util.tree_leaves(target)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            assert b.sharding == s, (b.sharding, s)
        print('OK')
        """
    )
