"""End-to-end behaviour: the whole stack wired together, plus dry-run units."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointStore
from repro.configs import ARCHITECTURES, reduce_config
from repro.data import DataConfig, SyntheticLMDataset
from repro.models.transformer import build_model
from repro.serving import ServingConfig, ServingEngine
from repro.train import AdamWConfig, TrainConfig, train_loop


def test_train_checkpoint_serve_roundtrip(tmp_path):
    """Train a tiny model, checkpoint it, restore, serve from the restore."""
    cfg = reduce_config(ARCHITECTURES["qwen3-32b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticLMDataset(
        DataConfig(seq_len=32, global_batch=8, vocab_size=cfg.vocab_size), cfg
    )
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=15))
    state, hist = train_loop(
        lambda p, b: model.train_loss(p, b), params, data.take(15), tcfg
    )
    assert hist[-1]["loss"] < hist[0]["loss"]

    store = CheckpointStore(str(tmp_path))
    store.save(15, state.params)
    _, restored, _ = store.restore_latest(state.params)

    eng = ServingEngine(
        model, restored, ServingConfig(max_batch=2, max_prompt_len=8, max_len=24)
    )
    for i in range(3):
        eng.submit(np.arange(1, 5 + i), max_new_tokens=4)
    out = eng.run_to_completion()
    assert len(out) == 3 and all(len(v) == 4 for v in out.values())


def test_mcop_placement_drives_training_config():
    """The launcher path: profile → MCOP → plan, for a real assigned arch."""
    import dataclasses

    from repro.configs import SHAPES
    from repro.core.placement import TPUV5E_TIER, plan_placement
    from repro.profilers.program import stage_specs

    cfg = ARCHITECTURES["granite-34b"]
    stages = stage_specs(cfg, SHAPES["train_4k"], group=11)
    plan = plan_placement(
        stages,
        dataclasses.replace(TPUV5E_TIER, chips=64),
        dataclasses.replace(TPUV5E_TIER, chips=192),
    )
    # 88 layers / 11 = 8 stage groups + embed + head
    assert plan.stage_tier.shape[0] == 10
    assert np.isfinite(plan.mcop_cost)
    assert plan.result.local_mask[0]  # embed stays local


# ----------------------------------------------------------------------
# Dry-run units (the full dry-run runs out-of-band; these test its parts)
# ----------------------------------------------------------------------

SAMPLE_HLO = """
HloModule jit_step, is_scheduled=true

%fused (a: f32[128,256]) -> f32[128,256] {
  ROOT %r = f32[128,256] parameter(0)
}

ENTRY %main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ag = f32[256,256]{1,0} all-gather(%p0), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[128,256]{1,0} all-reduce(%p0), to_apply=%fused
  %rs = f32[64,256]{1,0} reduce-scatter(%p0), to_apply=%fused, dimensions={0}
  %cp = f32[128,256]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %a2a = f32[128,256]{1,0} all-to-all(%p0), dimensions={0}
  %ags = (f32[128,256], f32[256,256]) all-gather-start(%p0), dimensions={0}
  %agd = f32[256,256]{1,0} all-gather-done(%ags)
  ROOT %out = f32[128,256]{1,0} add(%ar, %cp)
}
"""


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    coll = collective_bytes(SAMPLE_HLO)
    leaf = 128 * 256 * 4  # f32[128,256]
    assert coll["all-reduce"] == leaf
    assert coll["collective-permute"] == leaf
    assert coll["all-to-all"] == leaf
    assert coll["reduce-scatter"] == leaf
    # all-gather appears twice: sync op + async -start (done is skipped)
    assert coll["all-gather"] == 2 * leaf
    assert coll["num_ops"] == 6
    assert coll["total"] == 6 * leaf


def test_model_flops_convention():
    from repro.configs import SHAPES, get_config
    from repro.launch.dryrun import model_flops

    cfg = get_config("qwen2-7b")
    train = model_flops(cfg, SHAPES["train_4k"])
    assert train == pytest.approx(6.0 * cfg.active_param_count() * 4096 * 256)
    dec = model_flops(cfg, SHAPES["decode_32k"])
    assert dec == pytest.approx(2.0 * cfg.active_param_count() * 128)


def test_build_cell_shapes_are_allocation_free():
    """build_cell must work purely in eval_shape land."""
    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.specs import build_cell

    mesh = make_local_mesh(model=1)
    cfg = reduce_config(get_config("qwen2-7b"))
    for shape_name in ("train_4k", "prefill_32k", "decode_32k"):
        import dataclasses

        shape = dataclasses.replace(
            SHAPES[shape_name], seq_len=64, global_batch=4
        )
        cell = build_cell(cfg, shape, mesh)
        for leaf in jax.tree_util.tree_leaves(cell.arg_shapes):
            assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)


def test_reduced_cell_lowers_and_compiles_on_local_mesh():
    """A miniature end-to-end dry-run on the real single device."""
    import dataclasses

    from repro.configs import SHAPES, get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.specs import build_cell

    mesh = make_local_mesh(model=1)
    cfg = reduce_config(get_config("qwen3-32b"))
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=4)
    cell = build_cell(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            cell.step_fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        ).lower(*cell.arg_shapes)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    assert float(cost.get("flops", 0)) > 0
