"""The program's own names read from a profiler trace
(`chipbench/scopes.py`): the name-stack rule, the wire-format walk on a
hand-encoded trace, the old recorded chip trace (no scopes: every phase
reads None, every outside-in number as before), and a recorded chip
trace of the scoped program.

`phase_trace.xplane.pb.gz` was recorded on one TPU v5 lite with the
program's compile cache empty: T(8,8,4) (256 nodes, `tables` built
once), one `simulate_sweep` over loads (0.3, 0.7) × 2 seeds, 16 slots,
warmup 4, 16 buckets, and one `simulate` at 0.4 with vcs 2, credits 4,
queue 4, 16 buckets and `FaultSchedule.link_flap((0, 0), 4, 10,
policy="adaptive")`, 16 slots; both warmed once, then traced as two
`chipbench.call` spans inside one `chipbench.window`, with the
harness's profiler options.  To keep the file small, the
`/host:metadata` plane (the compiled modules' HLO) and the host plane's
runtime threads were dropped; the host plane keeps the Python thread's
line, which holds every `chipbench.*` and `sim.*` span."""
from __future__ import annotations

import gzip
import struct

import numpy as np
import pytest

from chipbench import scopes, trace
from chipbench_testkit import ROOT

TESTDATA = ROOT / "chipbench" / "testdata"
OLD = TESTDATA / "small_sweep.xplane.pb.gz"
NEW = TESTDATA / "phase_trace.xplane.pb.gz"


@pytest.mark.parametrize("stack,phase", [
    ("jit(runner)/vmap()/while/body/closed_call/sim.arbitrate/"
     "jit(take_along_axis)/gather", "sim.arbitrate"),
    ("jit(runner)/vmap(vmap(sim.predraw))/jit(_uniform)/mul",
     "sim.predraw"),
    ("sim.apply/reduce_sum", "sim.apply"),
    ("jit(runner)/while/body/sim.apply/sim.histogram/dot_general",
     "sim.histogram"),
    ("jit(runner)/vmap(vmap())/while/body/closed_call/sub", None),
    ("jit(runner)/xsim.apply/add", None),
    ("", None),
    (None, None),
])
def test_innermost_sim_token(stack, phase):
    assert scopes.phase_of(stack) == phase


# -- a hand-encoded XSpace ---------------------------------------------------

def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _f(num: int, v) -> bytes:
    """One field: an int as a varint, bytes/str length-delimited, a
    float as a fixed64 double."""
    if isinstance(v, float):
        return _varint(num << 3 | 1) + struct.pack("<d", v)
    if isinstance(v, int):
        return _varint(num << 3) + _varint(v)
    v = v.encode() if isinstance(v, str) else v
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _plane(name, lines, event_meta, stat_meta) -> bytes:
    out = _f(2, name)
    for ln in lines:
        out += _f(3, ln)
    for mid, meta in event_meta.items():
        out += _f(4, _f(1, mid) + _f(2, meta))
    for sid, sname in stat_meta.items():
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return out


def _line(name, ts_ns, events) -> bytes:
    out = _f(2, name) + _f(3, ts_ns)
    for mid, off_ps, dur_ps, stats in events:
        out += _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps)
                  + b"".join(_f(4, s) for s in stats))
    return out


def hand_encoded() -> bytes:
    """Window [1000, 2000) ns.  The chip runs a loop [1100, 1900) holding
    an arbitrate op [1100, 1300) (its stack a string), an apply op
    [1300, 1600) (its stack a reference to a stat name) and an unscoped
    copy [1600, 1700); a predraw op [1000, 1100) runs before it and an
    arbitrate op [2100, 2200) after the window.  The host runs one sweep
    [1000, 1950) with plan [1000, 1090) and fetch [1900, 1950)."""
    tf_op, ref_target = 1, 2
    stats = {tf_op: "tf_op", ref_target:
             "jit(runner)/while/body/closed_call/sim.apply/and"}

    def str_stat(stack):
        return _f(1, tf_op) + _f(5, stack)

    dev_meta = {
        1: _f(1, 1) + _f(2, "%while.1 = (s32[]) while()"),
        2: _f(1, 2) + _f(2, "%fusion.7 = s16[64] fusion()") + _f(5, str_stat(
            "jit(runner)/while/body/closed_call/sim.arbitrate/"
            "jit(take_along_axis)/gather")),
        3: _f(1, 3) + _f(2, "%fusion.8 = pred[64] fusion()")
        + _f(5, _f(1, tf_op) + _f(7, ref_target)),
        4: _f(1, 4) + _f(2, "%copy.1 = s8[64] copy()"),
        5: _f(1, 5) + _f(2, "%fusion.1 = s8[64] fusion()") + _f(5, str_stat(
            "jit(runner)/vmap(sim.predraw)/mul")),
    }
    ops = _line("XLA Ops", 1000, [
        (5, 0, 100_000, []), (1, 100_000, 800_000, []),
        (2, 100_000, 200_000, []), (3, 300_000, 300_000, []),
        (4, 600_000, 100_000, []), (2, 1_100_000, 100_000, [])])
    device = _plane("/device:TPU:0", [ops], dev_meta, stats)
    nodes, slots, lanes = 10, 11, 12
    host_stats = {nodes: "nodes", slots: "slots", lanes: "lanes",
                  13: "ratio"}
    host_meta = {1: _f(1, 1) + _f(2, "chipbench.window"),
                 2: _f(1, 2) + _f(2, "sim.sweep"),
                 3: _f(1, 3) + _f(2, "sim.plan"),
                 4: _f(1, 4) + _f(2, "sim.fetch"),
                 5: _f(1, 5) + _f(2, "PjitFunction(runner)")}
    args = [_f(1, nodes) + _f(4, 32), _f(1, slots) + _f(3, 16),
            _f(1, lanes) + _f(4, -1), _f(1, 13) + _f(2, 0.5)]
    host = _plane("/host:CPU", [_line("python3", 0, [
        (1, 1_000_000, 1_000_000, []), (2, 1_000_000, 950_000, args),
        (3, 1_000_000, 90_000, []), (5, 1_090_000, 10_000, []),
        (4, 1_900_000, 50_000, [])])], host_meta, host_stats)
    other = _plane("/host:metadata", [], {}, {})
    return _f(1, other) + _f(1, device) + _f(1, host)


def test_hand_encoded_trace():
    s = scopes.reduce_scopes(hand_encoded())
    assert s.window_ns == (1000, 2000)
    (d,) = s.devices
    assert d.ops.tolist() == [[1000, 1100], [1100, 1900], [1100, 1300],
                              [1300, 1600], [1600, 1700], [2100, 2200]]
    assert d.phases == ["sim.predraw", None, "sim.arbitrate", "sim.apply",
                        None, "sim.arbitrate"]
    assert s.scoped()
    assert s.phase_ns("sim.arbitrate") == 200          # the op after: out
    assert s.phase_ns("sim.apply") == 300
    assert s.phase_ns("sim.predraw") == 100
    assert s.phase_ns("sim.accept") is None            # absent, not 0
    assert s.loop_ns() == 800
    assert s.in_loops_ns(scopes.STEP_PHASES) == 500
    assert [(x.name, x.start, x.end) for x in s.spans] == [
        ("sim.sweep", 1000, 1950), ("sim.plan", 1000, 1090),
        ("sim.fetch", 1900, 1950)]
    assert s.spans[0].args == {"nodes": 32, "slots": 16, "lanes": -1,
                               "ratio": 0.5}
    assert [c.name for c in s.calls()] == ["sim.sweep"]
    assert s.span_ms_per_call("sim.plan") == pytest.approx(90e-6)
    assert s.span_ms_per_call("sim.fetch") == pytest.approx(50e-6)
    assert s.span_ms_per_call("sim.run") is None


def test_no_scope_anywhere_reads_none_for_every_phase():
    s = scopes.reduce_scopes(hand_encoded())
    for d in s.devices:
        d.phases = [None] * len(d.phases)
    assert not s.scoped()
    for phase in (scopes.PREDRAW,) + scopes.STEP_PHASES:
        assert s.phase_ns(phase) is None


# -- the recorded chip traces --------------------------------------------------

def _summaries(path, tmp_path):
    raw = gzip.decompress(path.read_bytes())
    plain = tmp_path / "trace.xplane.pb"
    plain.write_bytes(raw)
    return scopes.reduce_scopes(raw), trace.reduce_trace(str(plain))


def test_old_trace_has_no_scopes_and_the_same_outside_in_numbers(tmp_path):
    s, t = _summaries(OLD, tmp_path)
    # the walk reads the very ops and window that ProfileData reads
    assert s.window_ns == t.window_ns
    assert len(s.devices) == len(t.devices) == 1
    assert np.array_equal(s.devices[0].ops, t.devices[0].ops)
    assert s.devices[0].names == t.devices[0].names
    assert not s.scoped() and s.spans == [] and s.calls() == []
    for phase in (scopes.PREDRAW,) + scopes.STEP_PHASES:
        assert s.phase_ns(phase) is None
    assert s.span_ms_per_call("sim.plan") is None
    assert s.span_ms_per_call("sim.fetch") is None
    # the outside-in numbers, as the benchmark's readers read them
    assert s.loop_ns() == t.scan_ns() == 20406494
    assert t.predraw_ns() == 229393
    assert t.busy_s() == pytest.approx(0.020656088)


def test_recorded_phase_trace_is_small():
    assert NEW.stat().st_size <= 300_000


def test_recorded_phase_trace_names_every_phase(tmp_path):
    s, t = _summaries(NEW, tmp_path)
    assert s.scoped()
    for phase in (scopes.PREDRAW,) + scopes.STEP_PHASES:
        assert s.phase_ns(phase) > 0, phase
    # the scoped phases cover the slot scans, as the outside-in split
    # draws them
    assert s.loop_ns() == t.scan_ns() > 0
    assert s.in_loops_ns(scopes.STEP_PHASES) >= 0.95 * s.loop_ns()
    assert s.in_loops_ns(scopes.STEP_PHASES) <= s.loop_ns()
    calls = s.calls()
    assert [c.name for c in calls] == ["sim.sweep", "sim.simulate"]
    assert [c.args for c in calls] == [
        {"nodes": 256, "slots": 16, "lanes": 4},
        {"nodes": 256, "slots": 16, "lanes": 1}]
    for c in calls:
        inside = [x.name for x in s.spans
                  if c.start <= x.start and x.end <= c.end and x is not c]
        assert inside == ["sim.plan", "sim.runner", "sim.keys", "sim.run",
                          "sim.fetch"]
    assert s.span_ms_per_call("sim.plan") > 0
    assert s.span_ms_per_call("sim.fetch") > 0
