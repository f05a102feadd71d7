"""The trace reduction and the roofline arithmetic, on the CPU.

``data/tiny_v5e.xplane.pb`` is a profile recorded on one TPU v5 lite of
three calls of a jitted ``sum((x @ y) ** 2)`` over 1024 x 1024 float32,
with the product under ``jax.named_scope("probe/mm")`` and the sum under
``probe/sum`` (XLA fused both into one op). Reading it loads no TPU
library.
"""
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import reduce  # noqa: E402
import roofline  # noqa: E402

TRACE = BENCH / "tests" / "data" / "tiny_v5e.xplane.pb"

# the device's ops in the trace, (start ns, duration ns), read off the
# XLA Ops line: per call a copy-start, a copy-done and the fusion
OPS = [(50179657, 14), (50179672, 3), (50179677, 17950),
       (51094596, 14), (51094611, 2), (51094614, 17733),
       (51696966, 13), (51696980, 2), (51696983, 17943)]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    # a copy: xprof writes its op table beside the trace it reads
    copy = tmp_path_factory.mktemp("trace") / TRACE.name
    shutil.copy(TRACE, copy)
    return reduce.reduce_trace(copy)


def test_busy_is_the_union_of_device_ops(reduced):
    # no two ops of the trace overlap, so the union is the sum
    assert reduced.busy_s == pytest.approx(sum(d for _, d in OPS) * 1e-9,
                                           abs=10e-9)


def test_window_is_the_profile_span(reduced):
    assert reduced.window_s == pytest.approx(0.293638116, abs=1e-9)
    assert reduced.idle_share() == pytest.approx(
        1 - reduced.busy_s / 0.293638116)


def test_scope_time_reads_the_named_scope(reduced):
    # the fused op carries the scope of the product; the sum has no op
    assert reduced.scope_seconds("probe/mm") == pytest.approx(53.6275e-6,
                                                              rel=1e-6)
    assert reduced.scope_seconds("probe/sum") == 0.0
    assert reduced.scope_seconds("probe") == pytest.approx(53.6275e-6,
                                                           rel=1e-6)


def test_idle_gaps_are_between_ops_longest_first(reduced):
    gaps = reduced.idle_gaps()
    # the two gaps between the three calls, then the short ones inside
    ends = [s + d for s, d in OPS]
    between = sorted((OPS[i + 1][0] - ends[i]) * 1e-9 for i in (2, 5))[::-1]
    assert [g for _, g in gaps[:2]] == pytest.approx(between, abs=10e-9)
    assert all(label == "host" for label, _ in gaps)   # no bench.* span


def test_top_ops_name_the_scope(reduced):
    name, seconds = reduced.top_ops()[0]
    assert "probe/mm" in name
    assert seconds == pytest.approx(53.6275e-6, rel=1e-6)


def test_union_merges_overlaps():
    assert reduce._union_ns([(5, 9), (0, 3), (2, 4), (9, 12)]) == [[0, 4],
                                                                  [5, 12]]


@pytest.mark.parametrize("n,d,g,nbytes", [
    # census1990-d68-k50: 4 * (N*68 + 2N + 5N) = 300 N
    (2_458_285, 68, 5, 737_485_500),
    # sift128-ivf1024: 4 * (N*128 + 2N + 102N) = 928 N
    (262_144, 128, 102, 243_269_632),
])
def test_candidate_pass_bytes(n, d, g, nbytes):
    assert roofline.candidate_pass_bytes(n, d, g) == nbytes


@pytest.mark.parametrize("d,evals,flops", [(32, 96_000_000, 6.144e9),
                                           (128, 10_000_000, 2.56e9)])
def test_candidate_pass_flops(d, evals, flops):
    assert roofline.candidate_pass_flops(d, evals) == pytest.approx(flops)


def test_least_time_on_v5e():
    pk = roofline.peaks("TPU v5 lite")
    # 247,463,936 B at 819 GB/s against 6.144 GFLOP at 197/6 TFLOP/s
    t, bound = roofline.least_seconds(6.144e9, 247_463_936, pk)
    assert bound == "bytes"
    assert t == pytest.approx(247_463_936 / 819e9)
    t, bound = roofline.least_seconds(6.9e10, 243_269_632, pk)
    assert bound == "flops"
    assert t == pytest.approx(6.9e10 * 6 / 197e12)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
