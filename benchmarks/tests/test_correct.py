"""`correct` has to be able to come out false.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

* the control — the reference in float32 in the program's place — fails
  the comparison in every cell, at the size a test run can hold;
* a whole run (everything but the look for a chip) with the timed path
  broken underneath reports `correct: false`, once per fault a cell of
  this benchmark can have: an answer altered where it is produced, and
  half of every streamed batch left out.  (No state is carried from step
  to step and one chip exchanges nothing, so the other two faults of the
  builder's list do not exist here.)
* the same run on the sound path reports `correct: true`.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import control  # noqa: E402
import run  # noqa: E402

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_control_is_not_correct(cell, seed):
    r = control.readings(run.Cell(cell), seed, rehearsal=True)
    assert r["served"]["correct"] and r["served"]["max_rel_gap"] == 0.0
    assert not r["control"]["correct"]
    assert r["control"]["max_rel_gap"] > 0.0
    assert r["control"]["results_differing"] == 1


def rehearse(cell, seed, launcher_args=()):
    launcher = os.path.join(HERE, "faulty_child.py") if launcher_args \
        else os.path.join(BENCH, "server_child.py")
    return run.run_cell(cell, seed, seconds=1.0, trace=False,
                        require_platform=None, launcher=launcher,
                        launcher_args=launcher_args)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = rehearse(cell, 11)
    assert result["correct"] and result["attempted"] >= 1
    assert result["failed"] == 0
    assert result["checks"]["max_rel_gap"] == {"value": 0.0, "limit": 0.0}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["altered_answer", "half_batch"])
def test_broken_run_is_not_correct(cell, fault):
    result = rehearse(cell, 12, ("--fault", fault))
    assert not result["correct"]
    assert result["checks"]["results_differing"]["value"] \
        == result["attempted"] >= 1
    assert result["checks"]["max_rel_gap"]["value"] > 0.0
