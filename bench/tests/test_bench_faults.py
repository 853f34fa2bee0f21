"""``correct`` comes out false when the timed path is broken underneath.

Each run drives a whole cell on the host CPU at a tiny size with the
solver patched inside the server process (``bench/serve.py``'s patches):

* ``control``: the plain reference in bfloat16, one precision below the
  solver's float32, solves every flush;
* ``alter``: the first answer of every flush has its cut raised by 1%;
* ``half``: the second half of every flush is left unsolved and answered
  with the all-local plan.

A sound run of the same tiny cell passes, so each failure is the patch's.
"""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import _checkout  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = _checkout.make(tmp_path_factory.mktemp("faults"))
    miss = _checkout.tiny_mix(root, "req_miss", rate=20.0)
    _checkout.add_cell(root, "granite.tiny", "granite34b_layer_split", miss, like="granite34b.req_miss")
    sessions = _checkout.tiny_mix(root, "sessions")
    _checkout.add_cell(root, "sessions.tiny", "fig12_face_recognition", sessions, like="fig12.sessions")
    return root


def failing(result):
    return [k for k, c in result["checks"].items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("cell", ["granite.tiny", "sessions.tiny"])
def test_sound_run_passes(root, cell):
    result = _checkout.run_cell(root, cell, 3, 1.0)
    assert result["correct"], result["checks"]
    assert result["diagnostics"]["compiles_in_window"] == 0
    if cell == "granite.tiny":  # a budgeted mix warms every batch size up to its budget
        assert "warm_shapes=2 " in result["diagnostics"]["warm"]


@pytest.mark.parametrize("cell,patch", [
    ("granite.tiny", "control"),
    ("granite.tiny", "alter"),
    ("granite.tiny", "half"),
    ("sessions.tiny", "control"),
    ("sessions.tiny", "alter"),
    ("sessions.tiny", "half"),
])
def test_broken_path_is_not_correct(root, cell, patch):
    result = _checkout.run_cell(root, cell, 3, 1.0, patch=patch)
    assert not result["correct"]
    assert failing(result), result["checks"]
