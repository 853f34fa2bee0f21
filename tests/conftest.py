"""Shared fixtures for the test suite.

``qwen_stages`` is THE canonical framework-level stage list —
qwen2-7b at the 4k-token training shape, 8-layer groups — previously
copy-pasted into every elastic/broker/pipeline test.  The specs are
built once per session (stage_specs is pure but not free) and handed
out as a fresh shallow list; StageSpec is a frozen dataclass, so tests
cannot corrupt each other through the shared elements.

``granite_layer_split`` is the 90-vertex graph the 128 shape bucket
exists for: one Granite-34B-Code decode step (batch 128, 8,192-token
context) split per layer, embed and head pinned.
"""

import pytest


@pytest.fixture(scope="session")
def _qwen_stages_cached():
    from repro.configs import ARCHITECTURES, SHAPES
    from repro.profilers.program import stage_specs

    return stage_specs(ARCHITECTURES["qwen2-7b"], SHAPES["train_4k"], group=8)


@pytest.fixture
def qwen_stages(_qwen_stages_cached):
    """qwen2-7b / train_4k / group=8 stage specs, fresh list per test."""
    return list(_qwen_stages_cached)


@pytest.fixture(scope="session")
def granite_layer_split():
    """AppProfile of granite-34b's decode step, one vertex per layer."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.profilers.program import app_profile_from_config

    return app_profile_from_config(
        get_config("granite-34b"),
        ShapeConfig("decode_8k", "decode", 8192, 128),
        local_flops_per_s=1e13,
    )
