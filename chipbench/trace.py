"""Reduction of a profiler trace (`*.xplane.pb`) to the benchmark's
device numbers.

What the trace holds on a TPU (read by hand from a chip trace first):

  * one plane per chip, ``/device:TPU:<i>``, whose ``XLA Ops`` line has
    one event per executed HLO instruction, named by its HLO text
    (``%fusion.268 = pred[...] fusion(...)``), and whose ``XLA Modules``
    line has one event per executed program (``jit_runner(...)``);
  * a ``while`` instruction is itself an event spanning its whole loop,
    and the ops of its body are events nested inside that span.  The op
    events carry no name stack, so the split "inside the slot scan" is
    made by the ``while`` op's own span: device time inside the union
    of ``%while`` spans is the scan, the rest of the same program's op
    time is outside it (the traffic pre-draw);
  * the host plane ``/host:CPU`` carries the harness's own
    `jax.profiler.TraceAnnotation` spans (``chipbench.*``) on the same
    clock.

`reduce_trace` returns every number as measured; nothing is clamped.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "chipbench."
WINDOW = "chipbench.window"


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (k, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return np.zeros((0, 2), np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    stops = np.append(ends[idx[1:] - 1], ends[-1])
    return np.stack([starts, stops], 1)


def length(iv: np.ndarray) -> int:
    return int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0


def clip(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    if len(iv) == 0:
        return iv
    out = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval sets."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, np.int64).reshape(-1, 2)


def op_label(name: str) -> str:
    """An op's trace name up to its result type: ``%fusion.268 =
    pred[2621440]``."""
    head, _, rest = name.partition(" = ")
    return head if not rest else f"{head} = {rest.split('{')[0].split(' ')[0]}"


@dataclass
class Device:
    ops: np.ndarray                       # (k, 2) ns, every XLA op event
    names: list[str]
    modules: np.ndarray                   # (m, 2) ns, program executions


@dataclass
class TraceSummary:
    window_ns: tuple[int, int]
    spans: list[tuple[str, int, int]]
    devices: list[Device] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def _busy(self, d: Device) -> np.ndarray:
        return clip(union(d.ops), *self.window_ns)

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return float(np.mean([length(self._busy(d)) for d in self.devices])
                     ) / 1e9

    def _loops(self, d: Device) -> np.ndarray:
        keep = np.array([n.startswith("%while") for n in d.names], bool)
        return clip(union(d.ops[keep]) if keep.any()
                    else np.zeros((0, 2), np.int64), *self.window_ns)

    def scan_ns(self) -> int:
        """Device time inside the slot scans' `while` spans, summed over
        the chips."""
        return sum(length(self._loops(d)) for d in self.devices)

    def predraw_ns(self) -> int:
        """Device time of the scan programs outside their `while` spans:
        the op time of every program execution that holds a loop, less
        the loop itself, summed over the chips."""
        total = 0
        for d in self.devices:
            loops = self._loops(d)
            if not len(loops):
                continue
            mods = union(d.modules)
            with_loop = np.array(
                [len(intersect(loops, m[None])) > 0 for m in mods], bool)
            busy = intersect(self._busy(d), mods[with_loop])
            total += length(busy) - length(intersect(busy, loops))
        return total

    def top_ops(self, k: int = 10) -> list[list]:
        """The k leaf ops (no `while`) with the most device time, summed
        over their executions in the window, in seconds."""
        tot: dict[str, int] = {}
        for d in self.devices:
            lo, hi = self.window_ns
            for (s, e), n in zip(d.ops, d.names):
                if n.startswith("%while") or e <= lo or s >= hi:
                    continue
                key = op_label(n)
                tot[key] = tot.get(key, 0) + int(min(e, hi) - max(s, lo))
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / 1e9] for n, v in best]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The k longest stretches of the window in which the first chip
        ran no op, each named by the innermost harness span that covers
        its middle (what the host was doing), in seconds."""
        if not self.devices:
            return []
        lo, hi = self.window_ns
        busy = self._busy(self.devices[0])
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        out = []
        for s, e in gaps:
            mid = (s + e) // 2
            cover = [(ss, n) for n, ss, ee in self.spans
                     if ss <= mid < ee and n != WINDOW]
            label = max(cover)[1] if cover else "outside any span"
            out.append([label, (e - s) / 1e9])
        return sorted(out, key=lambda x: -x[1])[:k]


def _events(line):
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def reduce_trace(path: str) -> TraceSummary:
    """Read one `.xplane.pb` into a `TraceSummary` over the harness's
    `chipbench.window` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(n, s, e) for s, e, n in _events(line)
                          if n.startswith(SPAN_PREFIX)]
        elif plane.name.startswith("/device:TPU:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            ops = lines.get("XLA Ops", [])
            mods = lines.get("XLA Modules", [])
            devices.append(Device(
                ops=np.array([(s, e) for s, e, _ in ops],
                             np.int64).reshape(-1, 2),
                names=[n for _, _, n in ops],
                modules=np.array([(s, e) for s, e, _ in mods],
                                 np.int64).reshape(-1, 2)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW} span, "
                         f"found {len(windows)}")
    return TraceSummary(window_ns=windows[0], spans=spans, devices=devices)
