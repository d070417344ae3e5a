"""``correct`` at a size a CPU test can hold, through the whole run with
the device check skipped: a sound run passes; a run whose server alters a
token where it produces it fails, and so does one whose rounds commit
nothing; and so does the control: the reference at a lower precision put
in the program's place."""
import json
import os
import subprocess
import sys

import _paths  # noqa: F401
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _tiny(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(HERE, "tiny_run.py"),
                          *args], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mode", ["cascade_fused", "tree_fused"])
def test_altered_token_fails(mode):
    res = _tiny(mode, "altered")
    assert res["correct"] is False
    gap = res["checks"]["mean_gap"]
    assert gap["value"] > gap["limit"]
    assert list(res["checks"])[-1] == "mean_gap"


def test_rounds_that_commit_nothing_fail():
    res = _tiny("cascade_fused", "stuck")
    assert res["correct"] is False
    assert res["checks"]["sampled_requests"]["value"] == 0


def test_sound_run_passes_and_control_reads_above_it():
    sound = _tiny("cascade_fused", "sound")
    assert sound["correct"] is True
    program = sound["checks"]["mean_gap"]
    assert program["value"] <= program["limit"]
    # int8 is the cell's control; fp8, a step further down, fails as well
    for precision in ("int8", "fp8"):
        control = _tiny("cascade_fused", precision)
        assert control["correct"] is False
        low = control["checks"]["mean_gap"]
        assert program["limit"] < low["value"]
        assert low["value"] >= 3 * program["value"]
