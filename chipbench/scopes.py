"""The program's own names in a profiler trace (`*.xplane.pb`): the
`sim.*` scopes that the slot simulator puts on its device ops, and the
`sim.*` host spans of its public entries (docs/simulator.md, "Profiling
a run").

  * device: `jax.named_scope` lands in each op's JAX name stack, which
    the profiler keeps as the ``tf_op`` stat of the op's event metadata
    (``jit(runner)/vmap(vmap())/while/body/closed_call/sim.arbitrate/
    jit(take_along_axis)/gather``).  An op goes to the innermost
    ``sim.<phase>`` token of its own name stack, wherever that token
    sits (``vmap(sim.predraw)/mul`` and a bare ``sim.apply/reduce_sum``
    count too), so a fusion goes with its root instruction;
  * host: `jax.profiler.TraceAnnotation` spans on the ``/host:CPU``
    plane, on the device ops' clock, with their arguments.

`jax.profiler.ProfileData` does not expose event-metadata stats, so the
file is read here from its protobuf wire format: XSpace.planes = 1;
XPlane name = 2, lines = 3, event_metadata = 4, stat_metadata = 5;
XLine name = 2, timestamp_ns = 3, events = 4; XEvent metadata_id = 1,
offset_ps = 2, duration_ps = 3, stats = 4; XEventMetadata id = 1,
name = 2, stats = 5; XStatMetadata id = 1, name = 2; XStat
metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5, ref = 7.

A trace whose programs carry no ``sim.`` scope (an old trace, or an
executable loaded from a compile cache that a scope-free build filled,
whose metadata is that build's) reads None for every phase, never 0.
"""
from __future__ import annotations

import gzip
import re
import struct
from dataclasses import dataclass, field

import numpy as np

from .trace import WINDOW, clip, length, union

PREDRAW = "sim.predraw"
# the slot step's phases, in the order a slot runs them
STEP_PHASES = ("sim.epoch", "sim.vc_select", "sim.arbitrate",
               "sim.link_view", "sim.accept", "sim.apply", "sim.histogram",
               "sim.finish")
ENTRIES = ("sim.simulate", "sim.sweep", "sim.scenario_sweep",
           "sim.schedule_sweep")
_SCOPE = re.compile(r"(?<![A-Za-z0-9_.])sim\.[A-Za-z_][A-Za-z0-9_]*")


def phase_of(name_stack: str | None) -> str | None:
    """The innermost ``sim.<phase>`` token of a JAX name stack, or None."""
    found = _SCOPE.findall(name_stack or "")
    return found[-1] if found else None


# -- protobuf wire format --------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: int | None = None):
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in `buf`."""
    end = len(buf) if end is None else end
    while i < end:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, v


def _text(buf, se) -> str:
    return bytes(buf[se[0]:se[1]]).decode("utf-8", "replace")


def _stat(buf, se, stat_names: dict) -> tuple[str, object]:
    mid, val = 0, None
    for num, _, v in _fields(buf, *se):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = v - (1 << 64) if v >= 1 << 63 else v
        elif num == 5:
            val = _text(buf, v)
        elif num == 7:
            val = stat_names.get(v)
    return stat_names.get(mid, ""), val


def _plane(buf, se):
    """(lines, event metadata, stat names) of one XPlane: a line is
    (name, timestamp_ns, [(metadata_id, offset_ps, duration_ps,
    stats)]), an event metadata entry is (name, stats)."""
    raw_lines, raw_meta, stat_names = [], [], {}
    for num, _, v in _fields(buf, *se):
        if num == 3:
            raw_lines.append(v)
        elif num == 4:
            raw_meta.append(v)
        elif num == 5:
            for k, _, sv in _fields(buf, *v):
                if k == 2:
                    sid, sname = 0, ""
                    for f, _, x in _fields(buf, *sv):
                        if f == 1:
                            sid = x
                        elif f == 2:
                            sname = _text(buf, x)
                    stat_names[sid] = sname
    meta = {}
    for se_entry in raw_meta:
        for k, _, mv in _fields(buf, *se_entry):
            if k != 2:
                continue
            mid, mname, stats = 0, "", {}
            for f, _, x in _fields(buf, *mv):
                if f == 1:
                    mid = x
                elif f == 2:
                    mname = _text(buf, x)
                elif f == 5:
                    key, val = _stat(buf, x, stat_names)
                    stats[key] = val
            meta[mid] = (mname, stats)
    lines = []
    for lse in raw_lines:
        lname, ts, events = "", 0, []
        for f, _, x in _fields(buf, *lse):
            if f == 2:
                lname = _text(buf, x)
            elif f == 3:
                ts = x
            elif f == 4:
                mid = off = dur = 0
                stats = None
                for g, _, y in _fields(buf, *x):
                    if g == 1:
                        mid = y
                    elif g == 2:
                        off = y
                    elif g == 3:
                        dur = y
                    elif g == 4:
                        stats = stats if stats is not None else []
                        stats.append(y)
                events.append((mid, off, dur, stats))
        lines.append((lname, ts, events))
    return lines, meta, stat_names


# -- the summary -----------------------------------------------------------

@dataclass
class DeviceOps:
    ops: np.ndarray                       # (k, 2) ns, every XLA op event
    names: list[str]
    phases: list[str | None]              # innermost sim. scope per op


@dataclass
class Span:
    name: str
    start: int                            # ns, the device ops' clock
    end: int
    args: dict = field(default_factory=dict)


@dataclass
class ScopeTrace:
    window_ns: tuple[int, int] | None     # the harness's window, if any
    devices: list[DeviceOps]
    spans: list[Span]                     # the program's sim.* host spans

    def _window(self) -> tuple[int, int]:
        return self.window_ns or (np.iinfo(np.int64).min,
                                  np.iinfo(np.int64).max)

    def _select(self, d: DeviceOps, keep) -> np.ndarray:
        sel = np.array([keep(n, p) for n, p in zip(d.names, d.phases)],
                       bool).reshape(-1)
        iv = d.ops[sel] if sel.any() else np.zeros((0, 2), np.int64)
        return clip(union(iv), *self._window())

    def scoped(self) -> bool:
        """Whether any op in the window carries a ``sim.`` scope."""
        return any(length(self._select(d, lambda n, p: p is not None))
                   for d in self.devices)

    def phase_ns(self, phase: str) -> int | None:
        """Device time of the ops under `phase` in the window (the union
        of their intervals, `while` ops left out since their body ops
        carry their own scopes), summed over the chips; None when the
        trace names no phase at all or not this one."""
        if not self.scoped():
            return None
        ns = sum(length(self._select(
            d, lambda n, p: p == phase and not n.startswith("%while")))
            for d in self.devices)
        return ns or None

    def loop_ns(self) -> int:
        """Device time inside the `while` spans in the window: the slot
        scans, as `chipbench.trace.TraceSummary.scan_ns` counts them."""
        return sum(length(self._select(
            d, lambda n, p: n.startswith("%while"))) for d in self.devices)

    def in_loops_ns(self, phases) -> int:
        """Device time, inside the `while` spans, of the ops under any of
        `phases`."""
        total = 0
        for d in self.devices:
            loops = self._select(d, lambda n, p: n.startswith("%while"))
            ops = self._select(d, lambda n, p: p in phases
                               and not n.startswith("%while"))
            total += sum(length(clip(ops, a, b)) for a, b in loops)
        return total

    def calls(self) -> list[Span]:
        """The entry spans that start in the window."""
        lo, hi = self._window()
        return [s for s in self.spans
                if s.name in ENTRIES and lo <= s.start < hi]

    def span_ms_per_call(self, name: str) -> float | None:
        """Host milliseconds under the spans called `name` that start in
        the window, per entry call there; None without either."""
        lo, hi = self._window()
        ns = sum(min(s.end, hi) - s.start for s in self.spans
                 if s.name == name and lo <= s.start < hi)
        calls = len(self.calls())
        return ns / 1e6 / calls if ns and calls else None


def reduce_scopes(data: bytes) -> ScopeTrace:
    """Read one serialized XSpace into a `ScopeTrace`."""
    buf = memoryview(data)
    devices, spans, windows = [], [], []
    for num, _, se in _fields(buf):
        if num != 1:
            continue
        # the plane's name comes first in the files the profiler writes;
        # skip planes of no interest without decoding their events
        pname = next((_text(buf, v) for f, _, v in _fields(buf, *se)
                      if f == 2), "")
        if not (pname.startswith("/host:") or
                pname.startswith("/device:TPU:")):
            continue
        lines, meta, stat_names = _plane(buf, se)
        if pname.startswith("/device:TPU:"):
            ops, names, phases = [], [], []
            for lname, ts, events in lines:
                if lname != "XLA Ops":
                    continue
                for mid, off, dur, _ in events:
                    mname, mstats = meta.get(mid, ("", {}))
                    start = ts + off // 1000
                    ops.append((start, start + dur // 1000))
                    names.append(mname)
                    phases.append(phase_of(mstats.get("tf_op")))
            devices.append(DeviceOps(
                ops=np.array(ops, np.int64).reshape(-1, 2), names=names,
                phases=phases))
            continue
        for _, ts, events in lines:
            for mid, off, dur, stats in events:
                mname = meta.get(mid, ("", {}))[0]
                if mname == WINDOW:
                    windows.append((ts + off // 1000,
                                    ts + (off + dur) // 1000))
                if not mname.startswith("sim."):
                    continue
                args = dict(_stat(buf, s, stat_names) for s in stats or ())
                spans.append(Span(mname, ts + off // 1000,
                                  ts + (off + dur) // 1000, args))
    if len(windows) > 1:
        raise ValueError(f"expected at most one {WINDOW} span, "
                         f"found {len(windows)}")
    spans.sort(key=lambda s: (s.start, -s.end))
    return ScopeTrace(window_ns=windows[0] if windows else None,
                      devices=devices, spans=spans)


def read(path: str) -> ScopeTrace:
    """`reduce_scopes` of one `.xplane.pb` (gzipped if it ends in .gz)."""
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_scopes(f.read())
    with open(path, "rb") as f:
        return reduce_scopes(f.read())
