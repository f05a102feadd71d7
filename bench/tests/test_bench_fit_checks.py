"""The fit cells' check: passes the program, fails its control and each
fault a fit can have, driven through the harness on the CPU.

The faults (``faults.py``) are planted underneath the timed path
(``engine.fit``, which ``KMeans.fit`` calls): a fit that returns its
starting state, one whose means leave half the points out, and one
label altered where it is produced. The control is the reference Lloyd with its cross terms at
three bfloat16 passes in the program's place; at the rehearsal's 16K
points too few lie close enough to a tie for that error to flip a
label, so its test runs on the cell's 262,144 points for three
iterations (the reference alone is cheap). It is caught by ``label_gap``:
its final labels, argmins at three bfloat16 passes, miss the exact
nearest centroid at near-ties, while its ``inertia_rel_err`` reads like
the program's, whose trajectory also parts from the reference's where a
near-tie flips in float32.
"""
import pytest

from checks import failed, rehearse

import faults

CELLS = ["sift128-ivf1024.fit"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = rehearse(cell, 7, 0.1, traffic={"instances": 2})
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"


def test_control_is_not_correct():
    res = rehearse("sift128-ivf1024.fit", 4, 0.1, control=True,
                   config={"n_points": 1 << 18, "n_clusters": 1024,
                           "n_groups": 102, "max_iters": 3},
                   traffic={"instances": 1})
    assert not res["correct"]
    assert "label_gap" in failed(res)


@pytest.mark.parametrize("fault,number", [("unchanged", "inertia_rel_err"),
                                          ("half", "inertia_rel_err"),
                                          ("altered", "label_gap")])
def test_fault_is_not_correct(fault, number):
    with faults.planted("fit", fault):
        res = rehearse("sift128-ivf1024.fit", 7, 0.1,
                       traffic={"instances": 1})
    assert not res["correct"]
    assert number in failed(res)
