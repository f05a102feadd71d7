"""The ``census1990-d68-k50.fit`` cell through the harness on the CPU:
its check passes the program, fails the precision control and each
fault a fit can have; and the reader of ``centroid_sums_ms.fit``.

The rehearsal size keeps the cell's shape class: N = 9,645 is 173 past a
multiple of the 256-point tile, as the published 2,458,285 is, with
D = 68, K = 50, G = 5, overlapping blobs and 20 fixed iterations. The
control (the reference Lloyd with its cross terms at three bfloat16
passes) flips a label only at a near-tie, and the rehearsal's 9,645
points hold too few; its test runs 262,144 points of the cell's first
problem for five iterations, where one of its final labels misses the
exact nearest centroid by 5.4e-6 of the distance, above the cell's
``label_gap`` limit (the reference alone is cheap).
"""
import pytest

from checks import BENCH, failed, rehearse, run

import faults
import reduce

CELL = "census1990-d68-k50.fit"
SEED = 2**31 + 15               # the driver's seeds are this large


def test_program_is_correct():
    res = rehearse(CELL, SEED, 0.1, traffic={"instances": 2})
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["info"]["backend"] == "pallas"
    assert res["info"]["n_iter"] == [20, 20]


def test_control_is_not_correct():
    res = rehearse(CELL, SEED, 0.1, control=True,
                   config={"n_points": 1 << 18, "max_iters": 5},
                   traffic={"instances": 1})
    assert not res["correct"]
    assert "label_gap" in failed(res)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_is_not_correct(fault):
    with faults.planted("fit", fault):
        res = rehearse(CELL, SEED, 0.1, traffic={"instances": 1})
    assert not res["correct"], res["checks"]


SUMS_OP = ("jit(_run_loop)/while/body/kpynq/move_and_bounds/"
           "kpynq/centroid_sums/scatter-add")
MOVE_OP = "jit(_run_loop)/while/body/kpynq/move_and_bounds/sub"


def _read(ops, iterations=60):
    trace = reduce.Reduced(busy_s=4.0, window_s=5.0, ops=ops, spans=[],
                           gaps=[])
    ctx = run.ReaderContext(trace, {"iterations": iterations}, {}, "tpu",
                            "TPU v5 lite")
    return run.load_module(BENCH / "metrics" /
                           "centroid_sums_ms.fit.py").read(ctx)


def test_centroid_sums_ms_is_the_scope_per_iteration():
    ops = [("fusion.55", SUMS_OP, 0.3), ("fusion.54", SUMS_OP, 0.06),
           ("fusion.9", MOVE_OP, 1.0), ("fusion.58", "", 0.5)]
    assert _read(ops) == pytest.approx(6.0)     # 0.36 s over 60 iterations
    assert reduce.Reduced(4.0, 5.0, ops, [], []).scope_seconds(
        "kpynq/move_and_bounds") == pytest.approx(1.36)


@pytest.mark.parametrize("ops", [
    [],                                                 # no op table
    [("fusion.9", MOVE_OP, 1.0)],                       # no such scope
    [("fusion.1", "jit(f)/kpynq/centroid_sums_old/add", 0.6)],
])
def test_centroid_sums_ms_reads_nothing_without_the_scope(ops):
    assert _read(ops) is None
