"""The reader of ``seed_ms.fit`` on synthetic reductions: k-means++'s
device time under ``kpynq/seed`` per fit, and nothing where the program
names no such scope."""
import pytest

from checks import BENCH, run

import reduce

SEED_OP = "jit(scan)/while/body/closed_call/kpynq/seed/dot_general"
KERNEL_OP = ("jit(_run_loop)/while/body/kpynq/candidate_pass/"
             "jit(grouped_assign)/pallas_call")


def _read(ops, fits=3):
    trace = reduce.Reduced(busy_s=4.0, window_s=5.0, ops=ops, spans=[],
                           gaps=[])
    ctx = run.ReaderContext(trace, {"fits": fits}, {}, "tpu", "TPU v5 lite")
    return run.load_module(BENCH / "metrics" / "seed_ms.fit.py").read(ctx)


def test_seed_ms_is_the_scope_per_fit():
    ops = [("fusion.1", SEED_OP, 0.5), ("fusion.2", SEED_OP, 0.1),
           ("grouped_assign.3", KERNEL_OP, 2.0), ("fusion.58", "", 0.3)]
    assert _read(ops) == pytest.approx(200.0)


@pytest.mark.parametrize("ops", [
    [],                                                 # no op table
    [("fusion.1", "jit(scan)/while/body/closed_call/dot_general", 0.6),
     ("grouped_assign.3", KERNEL_OP, 2.0)],             # no such scope
    [("fusion.1", "jit(scan)/kpynq/seedling/dot_general", 0.6)],
])
def test_seed_ms_reads_nothing_without_the_scope(ops):
    assert _read(ops) is None
