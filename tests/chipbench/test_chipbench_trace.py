"""The reduction from a profiler trace to the device numbers: interval
arithmetic, the split inside and outside the slot scan, the idle gaps
and their labels — on a hand-made trace and on a small trace recorded
on one TPU v5 lite through the harness's own window."""
from __future__ import annotations

import gzip

import numpy as np
import pytest

from chipbench import trace
from chipbench_testkit import ROOT

RECORDED = ROOT / "chipbench" / "testdata" / "small_sweep.xplane.pb.gz"


def hand_made():
    """Window [0, 100) ns; the host runs call [0, 56), between [56, 68),
    call [68, 100).  The chip runs the scan program [4, 56) with its loop
    [5, 50) (two body ops inside) and one op after it, then another
    program [69, 91) with one op."""
    dev = trace.Device(
        ops=np.array([[5, 50], [10, 20], [30, 40], [50, 55], [70, 90]]),
        names=["%while.1 = (s32[]) while(...)",
               "%fusion.1 = s32[8]{0} fusion(...)",
               "%fusion.2 = pred[8]{0} fusion(...)",
               "%fusion.3 = s8[4]{0} fusion(...)",
               "%copy.1 = s8[4]{0} copy(...)"],
        modules=np.array([[4, 56], [69, 91]]))
    spans = [("chipbench.window", 0, 100), ("chipbench.call", 0, 56),
             ("chipbench.between", 56, 68), ("chipbench.call", 68, 100)]
    return trace.TraceSummary(window_ns=(0, 100), spans=spans,
                              devices=[dev])


def test_union_intersect_clip():
    iv = np.array([[5, 10], [1, 3], [2, 4], [9, 12], [20, 21]])
    assert trace.union(iv).tolist() == [[1, 4], [5, 12], [20, 21]]
    assert trace.length(trace.union(iv)) == 11
    a = np.array([[0, 10], [20, 30]])
    b = np.array([[5, 25]])
    assert trace.intersect(a, b).tolist() == [[5, 10], [20, 25]]
    assert trace.clip(a, 8, 22).tolist() == [[8, 10], [20, 22]]
    assert trace.union(np.zeros((0, 2), np.int64)).shape == (0, 2)


def test_busy_union_and_idle_share():
    s = hand_made()
    # union of [5, 55) and [70, 90): nested body ops are not counted twice
    assert s.busy_s() == pytest.approx(70e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert 1 - s.busy_s() / s.window_s == pytest.approx(0.30)


def test_split_inside_and_outside_the_scan():
    s = hand_made()
    assert s.scan_ns() == 45            # the while op's own span
    assert s.predraw_ns() == 5          # the scan program, outside its loop


def test_idle_gaps_are_labelled_by_harness_spans():
    gaps = hand_made().idle_gaps()
    assert gaps == [["chipbench.between", 15e-9], ["chipbench.call", 10e-9],
                    ["chipbench.call", 5e-9]]


def test_top_ops_leave_out_loops():
    top = hand_made().top_ops()
    assert top[0] == ["%copy.1 = s8[4]", 20e-9]
    assert {n for n, _ in top} == {"%copy.1 = s8[4]", "%fusion.1 = s32[8]",
                                   "%fusion.2 = pred[8]",
                                   "%fusion.3 = s8[4]"}


def test_recorded_chip_trace_is_small():
    assert RECORDED.stat().st_size < 1 << 20


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "small_sweep.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    s = trace.reduce_trace(str(path))
    assert len(s.devices) == 1
    calls = [sp for sp in s.spans if sp[0] == "chipbench.call"]
    assert calls
    lo, hi = s.window_ns
    assert all(lo <= a < b <= hi for _, a, b in calls)
    busy = s.busy_s()
    assert 0 < busy < s.window_s
    # one scan program per call, each holding one slot loop
    dev = s.devices[0]
    loops = trace.union(dev.ops[[n.startswith("%while")
                                 for n in dev.names]])
    assert s.scan_ns() > 0 and s.predraw_ns() > 0
    assert s.scan_ns() + s.predraw_ns() <= busy * 1e9
    assert s.scan_ns() == trace.length(trace.clip(loops, lo, hi))
    gaps = s.idle_gaps(k=1000)
    labels = {g[0] for g in gaps}
    assert labels <= {"chipbench.call", "chipbench.between"}
    assert sum(g[1] for g in gaps) == pytest.approx(s.window_s - busy)
    assert all(n.startswith("%") for n, _ in s.top_ops())
