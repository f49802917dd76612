"""The profiler-trace reduction against a small trace recorded on a TPU v5e.

`bench/testdata/seg_tiny.xplane.pb` is a quarter-second slice of the
`seg-s3dis-closed` cell, recorded on one chip by the traced run. Reading it
needs only `jax.profiler`, so the test runs on the CPU.
"""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import xtrace  # noqa: E402

XPLANE = ROOT / "bench" / "testdata" / "seg_tiny.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    """The recorded trace, reduced once for the module."""
    return xtrace.load(str(XPLANE))


def _naive_busy_ns(events):
    """Busy time by marking every covered nanosecond interval, one by one."""
    edges = sorted({e.start for e in events} | {e.end for e in events})
    covered = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(e.start <= mid < e.end for e in events):
            covered += b - a
    return covered


def test_one_chip_with_ops_and_programs(trace):
    """The trace holds one chip with ops, programs and host events."""
    assert trace.chips == [0]
    assert len(trace.ops[0]) > 100
    assert len(trace.modules[0]) >= 2
    assert trace.host


def test_busy_is_the_union_of_op_intervals(trace):
    """Busy time equals a naive union of the op intervals."""
    ops = trace.ops[0]
    span = max(e.end for e in ops) - min(e.start for e in ops)
    busy = xtrace.busy_s(trace)
    assert 0 < busy <= span * 1e-9
    assert busy == pytest.approx(_naive_busy_ns(ops) * 1e-9, rel=1e-9)


def test_kernels_are_found_by_name(trace):
    """Kernel events are found by instruction name."""
    for kernel in ("pc2im_fps_tile", "pc2im_lattice"):
        evs = xtrace.kernel_events(trace, kernel)
        assert evs and all(e.dur > 0 for e in evs)
    assert xtrace.kernel_events(trace, "pc2im_sc_matmul") == []  # fp32 cell


def test_gaps_between_programs(trace):
    """One gap between each pair of consecutive programs, none negative."""
    gaps = xtrace.module_gaps_s(trace)
    assert len(gaps) == len(trace.modules[0]) - 1
    assert all(g >= 0 for g in gaps)


def test_breakdown_lists(trace):
    """Top ops and idle gaps are sorted, bounded and sum within the total."""
    top = xtrace.top_ops(trace)
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    total = sum(e.dur for e in trace.ops[0]) * 1e-9
    assert sum(s for _, s in top) <= total * (1 + 1e-9)
    gaps = xtrace.idle_gaps(trace)
    assert 0 < len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
