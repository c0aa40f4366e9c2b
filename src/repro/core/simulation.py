"""Cycle-level interconnection-network simulator (paper §6.2), JAX-vectorised.

Reproduces the INSEE experiments comparing 4D-FCC(8) vs T(16,8,8,8) and
4D-BCC(4) vs T(8,8,8,4) under uniform / antipodal / central-symmetric /
random-pairings traffic.

Router model (simplifications vs INSEE noted in DESIGN.md §10):
  * packet = 16 phits; a link moves one packet per 16-cycle slot
    (virtual cut-through at packet granularity),
  * per-input-port queues of `queue` packets (paper Table 3: 4),
  * DOR over minimal routing records (Algorithms 1–4) with random
    tie-breaking between the two equal-norm records r and −route(−v)
    (Remark 30),
  * bubble flow control: entering a dimension ring (injection or turn)
    requires 2 free slots in the target queue, continuing in-dimension
    requires 1 — the paper's deadlock-avoidance rule,
  * output-link arbitration with a per-slot rotating queue-slot priority;
    in-transit traffic beats injection (the BlueGene congestion-control
    behaviour noted in §6.2).

Three implementations of the slot update share the state layout:

  * ``impl="batched"`` (default) — all per-link quantities (winners,
    records-after-hop, delivery flags, bubble requirements) are computed
    in one vectorised pass over all 2n ports, with no Python loop over
    ports and no scatters; the per-(node, out-port) winner is a segmented
    min over N·2nQ encoded priority keys (segment id = node·2n +
    requested port — realized as 2n fused masked column-mins, so no
    (N, 2nQ, 2n) candidate tensor is ever materialized); only the
    same-slot space-reuse fixed point (a packet moving into a slot
    vacated in this very slot) runs as a cheap `lax.scan` over the 2n
    port levels on an (N, 2n) carry, reproducing the reference sweep's
    acceptance exactly.  A whole run is one `lax.scan` over slots, and a
    whole load curve is one vmapped device program (`simulate_sweep`).
  * ``impl="fused"`` — the same slot update as a Pallas kernel
    (`repro.kernels.sim_step`): winner segmented-min, acceptance fixed
    point and the one-hot clears/transit/injection writes fused into ONE
    kernel pass over VMEM node tiles.  It runs in interpret mode on the
    CPU and is bitwise-equal to ``batched`` given the same pre-drawn
    traffic.  It does not lower for TPU (Mosaic refuses its in-kernel
    gathers — see `kernels/sim_step.py`), so on a TPU backend it raises
    rather than fall back; the chip runs ``batched``.
  * ``impl="reference"`` — the pre-batching per-port Python loop, kept as
    the semantic oracle: tests validate both other implementations
    statistically against it (same load curves within stochastic
    tolerance), and `benchmarks/sim_throughput.py` measures the speedup.

Scenario fault masks are TRACED inputs of the compiled batched/fused
programs (the pristine scenario keeps its own static specialization, so
baselines stay bitwise-identical): K fault patterns of one structure
(policy × dead-node-ness) share a single trace/compile, and
`simulate_scenario_sweep` vmaps the whole scenario axis through one
device program (see docs/simulator.md).

Arbitration detail: the reference breaks queue-slot contention for an
output link with i.i.d. uniform scores drawn inside the slot update; the
batched pass pre-draws 8-bit seeded priorities for the whole run in one
bulk threefry call and resolves priority collisions with a per-slot
rotating (hence unbiased) tie-break — statistically equivalent, one
min-reduction per slot.  Both keep every *semantic* randomness source —
Bernoulli injection, uniform destinations, and the Remark-30 record
coin.

**Transient faults.**  A `repro.core.fault_schedule.FaultSchedule`
(ordered fault/repair events) threads a TIME axis through the same
mask machinery: the schedule compiles to per-epoch mask stacks ``(E, …)``
plus a slot→epoch map, all of which ride in the state as traced inputs —
the batched and fused paths gather the current epoch's masks inside the
existing `lax.scan` carry (one dynamic index per slot; no per-epoch
retrace, and the pristine path keeps its static specialization), while
the reference oracle bakes the stacks and stays the per-slot semantic
authority.  Timeline semantics (tests/test_transient_sim.py):

  * packets enqueued at a node that dies are DROPPED that slot and
    counted, so ``delivered + in_flight + dropped == injected`` holds at
    *every* slot (with warmup=0), not just at run end — scheduled runs
    emit a per-slot `SimTimeline` asserting exactly that;
  * injection at currently-dead sources is masked per-epoch, and fixed
    patterns drop packets aimed at a currently-dead destination;
  * adaptive/escape re-consult `routing_engine.policy_ports` against the
    current epoch's masks every slot (a carried port can go stale when
    the world changes under a waiting packet); DOR ports are
    liveness-independent and keep the carried-port fast path;
  * a degenerate single-epoch schedule (E = 1) is BITWISE-equal to the
    static `Scenario` run — the whole static engine is the E = 1 special
    case of the timeline engine.

`simulate_schedule_sweep(g, pattern, schedules, loads, seeds)` runs K
timelines × loads × seeds through ONE compiled program (schedules pad
their epoch stacks to a common E; the slot→epoch maps are per-lane
traced inputs, so padding is free).

Throughput is reported in phits/cycle/node = packets/slot/node.

**Latency telemetry.**  Every delivery knows its packet's birth slot, so
latency statistics are *measured-window* statistics: a delivery counts
toward the latency mean (and, with ``hist_bins > 0``, the bucketed
histogram) only when the packet was BORN at or after `warmup` — packets
born during warmup carry queue-buildup ages that are not steady-state
samples (pre-PR-6 they silently inflated the mean).  `lat_cnt` tracks
how many deliveries were measured; with zero measured deliveries the
mean is NaN, never 0.0.  ``hist_bins=B`` threads a fixed-width ``(B,)``
age histogram through the scan carry of all three implementations
(bucket ``i < B-1`` = deliveries aged exactly ``i`` slots; bucket
``B-1`` = overflow, ages ``>= B-1``), accumulated with one
`segment_sum` per slot — no per-packet host transfer, no shape change
across loads, and bitwise-zero effect on every pre-existing counter.
`SimResult.latency_percentile` / `latency_p50/p99/p999` recover EXACT
nearest-rank percentiles from the histogram (validated cycle-exactly
against the per-packet `reference_latency_samples` oracle whenever no
mass reaches the overflow bucket); `SweepStats` pools seed histograms
into percentile-vs-load curves, and scheduled runs carry a per-slot
cumulative histogram in `SimTimeline` from which
`SimTimeline.recovery_slots` measures slots-until-p99-returns-to-
baseline after a repair event (see docs/simulator.md).

**Scenario engine.**  Both implementations accept a `repro.core.scenario.
Scenario` (dead links, dead nodes, routing policy ∈ {dor, adaptive,
escape}).  Faults and policies enter the compiled slot update purely as
masks and tables — a `link_ok` (N, 2n) mask excludes dead channels from
arbitration, dead nodes are masked out of injection and destination
sampling, and the per-packet output port comes from
`routing_engine.policy_ports` — so a scenario run is still ONE device
program and `simulate_sweep` can vmap it over loads AND seeds.  The
trivial scenario (no faults, DOR) takes the exact pre-scenario code
paths, so baseline results stay bitwise-identical.  Invariants (enforced
by tests/test_scenarios.py): no packet ever crosses a dead channel
(`SimResult.link_use` audits every crossing), and — with warmup=0, so
every slot is counted — `delivered + in_flight + dropped == injected`
exactly (a packet is *dropped* only at injection, when a fixed pattern
targets a dead node; with a warmup, packets injected before measurement
starts are excluded from the counters but still occupy queue slots).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from .fault_schedule import CompiledSchedule, FaultSchedule, ensure_compiled
from .lattice import LatticeGraph
from .link_spec import LinkSpec
from .routing import make_router
from .routing_engine import canonical_reduce, credit_vc_select, policy_ports
from .scenario import Scenario
from .sim_config import SimConfig, validate_feature_combo

PACKET_PHITS = 16


# ---------------------------------------------------------------------------
# static tables
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SimTables:
    n: int
    N: int
    neighbors: np.ndarray        # (N, 2n) — col 2i: +e_i, 2i+1: −e_i
    records_a: np.ndarray        # (N, n) minimal record per delta index
    records_b: np.ndarray        # (N, n) alternate minimal record (= −route(−v))
    labels: np.ndarray           # (N, n)
    hermite: np.ndarray          # (n, n)
    strides: np.ndarray          # (n,)


def build_tables(g: LatticeGraph, seed: int = 0,
                 backend: str = "auto") -> SimTables:
    """All-pairs record tables via the batched routing engine (the numpy
    oracle remains available with backend='numpy')."""
    router = make_router(g.matrix, backend)
    labels = g.labels
    rec_a = np.asarray(router(labels))
    # −route(−v) is also minimal for v and picks the *other* option on every
    # direction tie (half-ring hops, twin cycle intersections) — per-packet
    # coin between the two implements Remark 30's randomized tie-breaking.
    rec_b = -router(-labels)
    return SimTables(
        n=g.n, N=g.order, neighbors=g.neighbor_indices.astype(np.int32),
        records_a=rec_a.astype(np.int32), records_b=rec_b.astype(np.int32),
        labels=labels.astype(np.int32),
        hermite=g.hermite.astype(np.int32),
        strides=g.strides.astype(np.int32))


def _delta_idx(labels_src, labels_dst, hermite, strides):
    """Vectorised canonical reduction of (dst − src) into a node index."""
    v = canonical_reduce(labels_dst - labels_src, hermite)
    return (v * strides).sum(axis=-1)


# ---------------------------------------------------------------------------
# traffic patterns
# ---------------------------------------------------------------------------

def pattern_table(g: LatticeGraph, pattern: str, seed: int = 0) -> np.ndarray | None:
    """Fixed destination table (N,) for deterministic patterns; None for
    uniform (destination sampled per packet)."""
    N = g.order
    if pattern == "uniform":
        return None
    if pattern == "antipodal":
        d = g.distances_from_origin
        far = g.labels[int(np.argmax(d))]
        dst = g.label_to_index(g.labels + far)
        return dst.astype(np.int32)
    if pattern == "centralsymmetric":
        dst = g.label_to_index(-g.labels)
        return dst.astype(np.int32)
    if pattern == "randompairings":
        rng = np.random.default_rng(seed)
        perm = rng.permutation(N)
        dst = np.empty(N, dtype=np.int32)
        dst[perm[0::2]] = perm[1::2]
        dst[perm[1::2]] = perm[0::2]
        return dst
    raise ValueError(pattern)


# ---------------------------------------------------------------------------
# the simulator
# ---------------------------------------------------------------------------

def _hist_percentile(hist: np.ndarray, q: float) -> float:
    """EXACT nearest-rank percentile of a (B,) latency histogram, in
    CYCLES (bucket i = latency of exactly i slots = 16·i cycles for
    i < B−1).  NaN with no mass; +inf when the rank lands in the
    overflow bucket B−1 (the true value is only lower-bounded there —
    pick `hist_bins` above the worst age for exact tails)."""
    hist = np.asarray(hist)
    total = int(hist.sum())
    if total == 0:
        return float("nan")
    if not (0.0 < q <= 1.0):
        raise ValueError(f"percentile q must be in (0, 1], got {q}")
    rank = min(total, max(1, int(np.ceil(q * total))))
    idx = int(np.searchsorted(np.cumsum(hist), rank, side="left"))
    if idx >= hist.size - 1:
        return float("inf")
    return float(PACKET_PHITS * idx)


def _bucket_counts(age, meas, bins: int):
    """(B,) bucketed delivery counts of one slot: clip ages into the
    fixed-width buckets and reduce the measured-delivery mask through a
    one-hot matvec (ages of unmeasured lanes are clipped garbage with
    weight 0).  Deliberately NOT `segment_sum`: XLA CPU serializes its
    scatter-add lowering — a dense (NP, B) dot is ~3× cheaper per slot
    at bench shapes (same trick as the segmented-min arbitration
    rewrite).  The dot packs TWO buckets per int32 column (bucket 2c in
    the low half-word, 2c+1 in the high), halving the one-hot
    intermediate — another ~2×.  A per-slot per-bucket count is at most
    the N·P lane count, so 16-bit halves cannot overflow while
    N·P ≤ 65535; beyond that (or for odd `bins`) fall back to the plain
    one-column-per-bucket dot."""
    b = jnp.clip(age.astype(jnp.int32), 0, bins - 1).ravel()
    m = meas.astype(jnp.int32).ravel()
    if bins % 2 or b.size > 0xFFFF:
        onehot = (b[:, None] == jnp.arange(bins, dtype=jnp.int32)[None, :]
                  ).astype(jnp.int32)
        return m @ onehot
    cols = jnp.arange(bins // 2, dtype=jnp.int32)
    packed = jnp.where((b[:, None] >> 1) == cols[None, :],
                       jnp.int32(1) << (16 * (b[:, None] & 1)), 0)
    # unpack via uint32: the high half-word may set bit 31 (count 2^15)
    r = (m @ packed).astype(jnp.uint32)
    lo = (r & 0xFFFF).astype(jnp.int32)
    hi = (r >> 16).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=1).ravel()


@dataclass(frozen=True)
class SimTimeline:
    """Per-slot counter trace of a scheduled (transient-fault) run: each
    array has shape (slots,) — cumulative counted totals AFTER each slot,
    plus the instantaneous queue occupancy and the per-slot count of
    dead-channel crossings (an exact audit: always zero).  With warmup=0
    conservation holds at EVERY slot, not just at run end.

    With ``hist_bins > 0`` the trace also carries `lat_hist`, the
    CUMULATIVE (slots, B) latency histogram after each slot — windowed
    differences of it give per-slot tail-latency estimates without any
    per-packet storage (`latency_percentile_trace`, `recovery_slots`)."""

    delivered: np.ndarray
    injected: np.ndarray
    dropped: np.ndarray
    in_flight: np.ndarray
    dead_crossings: np.ndarray
    lat_hist: np.ndarray | None = None

    def conservation_violations(self) -> np.ndarray:
        """Slots where delivered + in_flight + dropped != injected."""
        return np.flatnonzero(
            self.injected != self.delivered + self.dropped + self.in_flight)

    def conservation_ok(self) -> bool:
        return self.conservation_violations().size == 0

    # -- tail-latency telemetry (hist_bins runs only) -----------------------
    def _require_hist(self):
        if self.lat_hist is None:
            raise ValueError(
                "timeline has no latency histogram — run with hist_bins>0")

    def latency_window_hist(self, end_slot: int, window: int) -> np.ndarray:
        """(B,) histogram of deliveries measured in the `window` slots
        ending AT `end_slot` (inclusive) — a cumulative difference."""
        self._require_hist()
        if end_slot < 0:
            return np.zeros(self.lat_hist.shape[1], self.lat_hist.dtype)
        hi = self.lat_hist[end_slot]
        if end_slot - window >= 0:
            return hi - self.lat_hist[end_slot - window]
        return hi.copy()

    def latency_percentile_trace(self, q: float = 0.99,
                                 window: int = 64) -> np.ndarray:
        """(slots,) windowed nearest-rank percentile (cycles) after each
        slot — NaN where the window saw no measured delivery."""
        self._require_hist()
        return np.array([
            _hist_percentile(self.latency_window_hist(s, window), q)
            for s in range(self.lat_hist.shape[0])])

    def recovery_slots(self, fault_slot: int, repair_slot: int, *,
                       q: float = 0.99, window: int = 64,
                       slack_cycles: float = 0.0) -> int | None:
        """Slots from the repair event until the windowed percentile-q
        latency first returns to its pre-fault baseline (the same-width
        window ending just before `fault_slot`), or None if it never
        does within the run.  `slack_cycles` loosens the baseline for
        stochastic traffic (windows are finite samples)."""
        self._require_hist()
        if not 0 < fault_slot <= repair_slot < self.lat_hist.shape[0]:
            raise ValueError(
                f"need 0 < fault_slot <= repair_slot < slots, got "
                f"fault={fault_slot} repair={repair_slot} "
                f"slots={self.lat_hist.shape[0]}")
        base = _hist_percentile(
            self.latency_window_hist(fault_slot - 1, window), q)
        if np.isnan(base):
            raise ValueError(
                "no measured deliveries in the pre-fault window — widen "
                "`window` or shorten the warmup")
        for s in range(repair_slot, self.lat_hist.shape[0]):
            p = _hist_percentile(self.latency_window_hist(s, window), q)
            if not np.isnan(p) and p <= base + slack_cycles:
                return s - repair_slot
        return None


@dataclass(frozen=True)
class SimResult:
    accepted_load: float      # phits / cycle / node
    avg_latency_cycles: float  # NaN when lat_count == 0 (no measured pkt)
    delivered: int
    injected: int
    slots: int
    dropped: int = 0          # refused at injection (dead destination)
    in_flight: int = 0        # occupied queue slots at run end
    # deliveries the latency stats measured: born AND delivered at or
    # after warmup (== delivered when warmup=0; the mean and histogram
    # are taken over exactly these packets)
    lat_count: int = 0
    # (hist_bins,) age histogram of the measured deliveries — bucket i
    # counts latency of exactly i slots (i < B−1), bucket B−1 overflows;
    # None unless the run asked for hist_bins > 0
    latency_hist: np.ndarray | None = field(default=None, compare=False)
    # (N, 2n) per-channel packet crossings, counted over ALL slots; only
    # tracked for non-trivial scenarios (the dead-link audit)
    link_use: np.ndarray | None = field(default=None, compare=False)
    # per-slot counter trace, only emitted by FaultSchedule runs
    timeline: SimTimeline | None = field(default=None, compare=False)
    # per-VC telemetry of the credit-flow router (vcs > 1 runs only):
    # (V,) deliveries attributed to the winner's SOURCE lane, (V,)
    # injections by the lane the packet was admitted into, and (V,)
    # occupied queue slots at run end.  Packets may switch lanes at each
    # hop, so only the V-SUMS obey conservation:
    # sum(vc_injected) == injected, sum(vc_delivered) == delivered,
    # sum(vc_in_flight) == in_flight.  None for vcs=1.
    vc_delivered: np.ndarray | None = field(default=None, compare=False)
    vc_injected: np.ndarray | None = field(default=None, compare=False)
    vc_in_flight: np.ndarray | None = field(default=None, compare=False)

    def latency_percentile(self, q: float) -> float:
        """EXACT nearest-rank percentile-q latency in cycles from the
        bucketed histogram (requires a hist_bins>0 run); NaN with no
        measured delivery, +inf if the rank overflows the last bucket."""
        if self.latency_hist is None:
            raise ValueError(
                "result has no latency histogram — run with hist_bins>0")
        return _hist_percentile(self.latency_hist, q)

    @property
    def latency_p50(self) -> float:
        return self.latency_percentile(0.50)

    @property
    def latency_p99(self) -> float:
        return self.latency_percentile(0.99)

    @property
    def latency_p999(self) -> float:
        return self.latency_percentile(0.999)


_RUNNER_CACHE: dict = {}


def _next_port(rec):
    """DOR: first nonzero dimension of the record → output port."""
    nz = jnp.abs(rec) > 0
    dim = jnp.argmax(nz, axis=-1)
    sgn = jnp.take_along_axis(rec, dim[..., None], -1)[..., 0]
    return 2 * dim + (sgn < 0), dim, sgn


def _next_port_ext(rec, pdim, psgn, pspan):
    """Greedy weighted DOR over an express-extended port set: among the
    ports of the record's first nonzero dimension whose sign matches and
    whose span FITS the remaining offset (no overshoot — the minimal-
    record invariant survives), take the largest span.  With no express
    entries this selects exactly `_next_port`'s 2·dim + (sgn<0)."""
    nz = jnp.abs(rec) > 0
    dim = jnp.argmax(nz, axis=-1)
    val = jnp.take_along_axis(rec, dim[..., None], -1)[..., 0]
    val = val.astype(jnp.int32)
    ok = ((pdim == dim[..., None]) & (psgn * val[..., None] > 0)
          & (pspan <= jnp.abs(val)[..., None]))
    return jnp.argmax(jnp.where(ok, pspan, -1), axis=-1)


def _next_port_ext_ok(rec, pdim, psgn, pspan, link_ok):
    """`_next_port_ext` under faults: among the fitting ports of the
    record's first nonzero dimension, prefer the largest-span LIVE one —
    live beats span, so a dead express hop degrades onto the base span-1
    port (which always fits) instead of wedging the packet.  Only a dead
    BASE channel leaves the packet requesting a dead port, where it
    blocks in place exactly like DOR through a fault.  `link_ok`
    broadcasts to ``rec.shape[:-1] + (P,)``; with all-live masks this
    selects exactly `_next_port_ext`."""
    nz = jnp.abs(rec) > 0
    dim = jnp.argmax(nz, axis=-1)
    val = jnp.take_along_axis(rec, dim[..., None], -1)[..., 0]
    val = val.astype(jnp.int32)
    ok = ((pdim == dim[..., None]) & (psgn * val[..., None] > 0)
          & (pspan <= jnp.abs(val)[..., None]))
    lok = jnp.broadcast_to(link_ok, ok.shape)
    key = jnp.where(ok, lok.astype(jnp.int32) * 4096 + pspan, -1)
    return jnp.argmax(key, axis=-1)


def _inject(state, key, new_dst, new_rec, new_birth, ctx, masks=None):
    """Reference injection stage (per-slot PRNG draws + scatter writes,
    bitwise-stable vs the pre-batching simulator for trivial scenarios).
    Runs after transit so in-flight traffic has priority; entering a ring
    costs 2 free slots (bubble rule).  Under a non-trivial scenario dead
    sources never want, destinations are sampled over live nodes, packets
    of fixed patterns aimed at a dead node are *dropped*, and the
    injection port follows the scenario policy.  `masks` overrides the
    scenario mask entries with the CURRENT EPOCH's slices when the run
    follows a `FaultSchedule` (the reference path resolves the epoch once
    per slot and hands the static-shaped masks down here)."""
    N, P = ctx["N"], ctx["P"]
    m = ctx if masks is None else {**ctx, **masks}
    fixed_dst = ctx["fixed_dst"]
    trivial = ctx["trivial"]
    labels, hermite, strides = ctx["labels"], ctx["hermite"], ctx["strides"]
    rec_a, rec_b = ctx["rec_a"], ctx["rec_b"]
    slot = state["slot"]
    k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 2), 3)
    want_new = jax.random.uniform(k1, (N,)) < state["load"]
    if not trivial:
        want_new = want_new & m["inj_ok"]
    want = want_new | (state["backlog"] > 0)
    if fixed_dst:
        d = state["dst_table"]
    elif not trivial and ctx["has_dead_nodes"]:
        # uniform over *live* destinations (self-draws carry di == 0 and
        # simply back-log, exactly like a fixed self-pattern)
        d = m["live_tbl"][jax.random.randint(k2, (N,), 0, m["n_live"])]
    else:
        d = jax.random.randint(k2, (N,), 0, N - 1)
        d = jnp.where(d >= jnp.arange(N), d + 1, d)
    di = _delta_idx(labels, labels[d], hermite, strides)
    coin = jax.random.uniform(k3, (N,)) < 0.5
    r = jnp.where(coin[:, None], rec_a[di], rec_b[di])
    if trivial:
        if ctx.get("express"):
            inj_port = _next_port_ext(r, ctx["pdim"], ctx["psgn"],
                                      ctx["pspan"])
        else:
            inj_port, _, _ = _next_port(r[:, None, :])
            inj_port = inj_port[:, 0]
        drop = None
        ipc = inj_port
    else:
        if ctx.get("express"):
            # greedy weighted DOR over the extended ports, liveness-aware
            # (express at V=1 is dor-only; see validate_feature_combo)
            inj_port = _next_port_ext_ok(r, ctx["pdim"], ctx["psgn"],
                                         ctx["pspan"], m["link_ok"])
        else:
            inj_port = policy_ports(r, m["link_ok"], ctx["policy"])
        drop = want & ~m["dst_ok"][d]
        ipc = jnp.minimum(inj_port, P - 1)        # clamp the P sentinel
    freeq = jnp.take_along_axis(
        (new_dst < 0).sum(axis=2), ipc[:, None], axis=1)[:, 0]
    can = want & (freeq >= 2) & (jnp.abs(r).sum(-1) > 0)
    if not trivial:
        can = can & ~drop & (inj_port < P)
    r_ = jnp.arange(N)
    r = r.astype(new_rec.dtype)
    slot_idx = jnp.argmax(new_dst[r_, ipc] < 0, axis=1)
    new_dst = new_dst.at[r_, ipc, slot_idx].set(
        jnp.where(can, d, new_dst[r_, ipc, slot_idx]))
    new_rec = new_rec.at[r_, ipc, slot_idx].set(
        jnp.where(can[:, None], r, new_rec[r_, ipc, slot_idx]))
    new_birth = new_birth.at[r_, ipc, slot_idx].set(
        jnp.where(can, slot, new_birth[r_, ipc, slot_idx]))
    backlog = state["backlog"] + want_new - can
    if drop is not None:
        backlog = backlog - drop
    backlog = jnp.clip(backlog, 0, 1 << 30)
    return new_dst, new_rec, new_birth, backlog, can, drop


def _make_traffic(ctx, state, key, slots: int):
    """Pre-draw the whole run's injection randomness in a handful of large
    batched PRNG calls (per-slot threefry + routing-table lookups inside
    the scan cost ~45% of a run): per (slot, node) a uniform injection
    draw and the Remark-30 record coin, plus — for uniform traffic — the
    destination as a *delta index* drawn directly (dst uniform over the
    N−1 other nodes ⟺ delta uniform over the nonzero canonical labels),
    reduced to the record and its first DOR port via the `rec_ab` /
    `port_ab` tables.

    Under a `FaultSchedule` (ctx["scheduled"]) the mask state entries
    carry a leading epoch axis and `state["slot2epoch"]` maps each slot
    to its epoch: live-destination sampling and non-DOR injection ports
    gather the CURRENT epoch's masks per slot.  With E = 1 every gather
    reproduces the static values bitwise."""
    N, P, Q = ctx["N"], ctx["P"], ctx["Q"]
    V = ctx.get("V", 1)
    scheduled = ctx.get("scheduled", False)
    ku, kd, kc, kp = jax.random.split(jax.random.fold_in(key, 2), 4)
    u = jax.random.uniform(ku, (slots, N))
    coin = (jax.random.uniform(kc, (slots, N)) < 0.5).astype(jnp.int32)
    if ctx["fixed_dst"]:
        # read from the state so one compiled runner serves every fixed
        # pattern on this topology (the cache key only carries fixed-ness)
        di = state["di_fixed"][None, :]                    # (1, N), broadcast
    elif not ctx["trivial"] and ctx["has_dead_nodes"]:
        # uniform over *live* destinations: draw the node, reduce the
        # delta on device (self-draws carry di == 0 and back-log).  The
        # live table is a traced state input padded to N entries; the
        # traced n_live bound keeps the draw exactly uniform over them.
        if scheduled:
            s2e = state["slot2epoch"]
            lt = state["live_tbl"][s2e]                    # (slots, N)
            idx = jax.random.randint(
                kd, (slots, N), 0, state["n_live"][s2e][:, None])
            dstn = jnp.take_along_axis(lt, idx, axis=1)
        else:
            dstn = state["live_tbl"][
                jax.random.randint(kd, (slots, N), 0, state["n_live"])]
        di = _delta_idx(ctx["labels"][None, :, :], ctx["labels"][dstn],
                        ctx["hermite"], ctx["strides"])
    else:
        di = jax.random.randint(kd, (slots, N), 1, N)
    r = ctx["rec_ab"][di, coin]                            # (slots, N, n)
    if V > 1 or ctx["trivial"] or ctx["policy"] == "dor":
        # DOR ignores liveness, so the precomputed port table stays valid.
        # The VC router also takes this branch for EVERY policy: its
        # injection (port, VC) choice depends on the per-slot credit
        # counters, so it is recomputed inside the scan
        # (`credit_vc_select`) and tr["p"] only seeds the DOR fallback.
        p = ctx["port_ab"][di, coin]
    elif scheduled:
        p = policy_ports(r, state["link_ok"][state["slot2epoch"]],
                         ctx["policy"]).astype(jnp.int8)
    else:
        p = policy_ports(r, state["link_ok"][None, :, :],
                         ctx["policy"]).astype(jnp.int8)
    return dict(
        u=u,
        r=r,
        p=p,
        v=jnp.broadcast_to(di != 0, (slots, N)),
        # arbitration priorities for every queue slot of every slot time,
        # one bulk threefry draw (~5× cheaper than hashing in the scan);
        # the VC router draws per (port, VC, slot) — V=1 is the exact
        # pre-VC shape
        prio=jax.random.bits(kp, (slots, N, P * V * Q), jnp.uint8))


def _finish_slot(state, counted_from, delivered, lat_sum, lat_cnt, can,
                 drop=None, qdrop=None, **updates):
    slot = state["slot"]
    counted = slot >= counted_from
    # dropped packets count as injected so that conservation stays exact:
    # injected == delivered + in_flight + dropped.  Queue drops (packets
    # already in flight when their node dies, `qdrop`) were counted
    # injected at injection time, so they increment ONLY `dropped`.
    inj = can.sum() if drop is None else can.sum() + drop.sum()
    # lat_sum / lat_cnt arrive already filtered to measured deliveries
    # (birth >= warmup) — a packet born at or after warmup can only be
    # delivered at a counted slot, so no extra `counted` gate is needed
    # (and with warmup=0 the filter is the old behaviour bitwise)
    out = dict(
        state, **updates, slot=slot + 1,
        delivered=state["delivered"] + jnp.where(counted, delivered, 0),
        lat_sum=state["lat_sum"] + lat_sum,
        lat_cnt=state["lat_cnt"] + lat_cnt,
        injected=state["injected"] + jnp.where(counted, inj, 0))
    if drop is not None:
        d = drop.sum() if qdrop is None else drop.sum() + qdrop
        out["dropped"] = state["dropped"] + jnp.where(counted, d, 0)
    return out


def _port_lookup(per_port, fill, port_flat):
    """(N, P) per-port values → (N, S) per-slot values through each queue
    slot's requested port; sentinel port P reads `fill`.  A where-chain
    over the P static ports, elementwise, so it fuses into one loop
    instead of an element gather per slot; bitwise equal to a
    `take_along_axis` over the table padded with a `fill` column."""
    out = jnp.full(port_flat.shape, fill, per_port.dtype)
    for p in range(per_port.shape[1]):
        out = jnp.where(port_flat == p, per_port[:, p:p + 1], out)
    return out


def _make_slot_step_batched(ctx, warmup: int):
    """One simulated slot with NO Python loop over ports and NO scatters
    (XLA CPU serializes scatter updates; everything here is node-axis
    gathers, per-port select chains, one-hot masks and small reductions;
    no element gather runs at every queue slot):

      * winner per (node, out-port): a segmented min over the N·2nQ
        encoded priority keys (segment id = node·2n + requested port,
        realized as 2n fused masked column-mins — nothing bigger than
        O(N·2nQ) is ever materialized) — 8-bit seeded threefry
        priorities pre-drawn for the whole run (`_make_traffic`) plus a
        per-slot rotating tie-break, standing in for the reference's
        i.i.d. uniform arbitration scores,
      * link acceptance for all 2n ports at once; the same-slot space
        reuse fixed point runs as a `lax.scan` over port levels on a tiny
        (N, 2n) carry (exactly the reference sweep's acceptance),
      * queue updates through one-hot write masks (each in-queue receives
        at most one packet per slot, so masks never collide),
      * each packet's DOR output port is carried in the state and updated
        only when the packet moves, so no per-slot argmax over the full
        (N, 2n, Q, n) record tensor.

    Scenario faults and policies enter as masks/tables only: dead channels
    are excluded from the winner min-reduce (`link_ok` where-mask), the
    carried port comes from `policy_ports`, and dropped/audit counters are
    extra fused reductions — the trivial scenario compiles to the exact
    pre-scenario program.  The masks are TRACED inputs (they travel in the
    state, like `di_fixed`), so one compiled runner serves every fault
    pattern of the same structure (policy × dead-node-ness) and
    `simulate_scenario_sweep` can vmap a whole scenario axis through it.

    NOTE: `kernels.sim_step._slot_step_kernel` mirrors this update phase
    for phase and must stay bitwise-equal — change both together
    (tests/test_fused_impl.py enforces the parity in CI)."""
    n, N, P, Q = ctx["n"], ctx["N"], ctx["P"], ctx["Q"]
    nbr = ctx["nbr"]
    rec_dtype = ctx["rec_dtype"]
    trivial = ctx["trivial"]
    weighted = ctx.get("weighted", False)
    express = ctx.get("express", False)
    if weighted:
        wgt = ctx["wgt"]                           # (P,) int32 slot costs
    PQ = P * Q
    # arbitration key = prio(8 bit)·PQ + rot(<PQ): int16 fits exactly up
    # to PQ=127 (256·PQ − 1 < 0x7FFF); wider queues fall back to int32
    key_dtype = jnp.int16 if PQ <= 127 else jnp.int32
    BIG = key_dtype(np.iinfo(np.dtype(key_dtype)).max)
    ports = jnp.arange(P)
    opp = jnp.arange(P) ^ 1                        # paired ±e_i ports
    sender = nbr[:, opp]                           # (N, P): src of in-port p
    receiver = nbr                                 # (N, P): dst of out-port p
    if express:
        # overlay ports hop span·e_dim; the table already carries signs
        hop = ctx["hop_tab"].astype(rec_dtype)
    else:
        dim_p = ports // 2
        sgn_p = 1 - 2 * (ports % 2)
        # hop of out-port p subtracted from the record: sgn_p · e_{dim_p}
        hop = np.zeros((P, n), np.int64)
        hop[np.arange(P), np.asarray(dim_p)] = np.asarray(sgn_p)
        hop = jnp.asarray(hop, rec_dtype)
    pq32 = jnp.arange(PQ, dtype=jnp.int32)
    ports8 = jnp.arange(P, dtype=jnp.int8)
    NO_PORT = jnp.int8(P)

    scheduled = ctx.get("scheduled", False)

    def slot_step(state, tr):
        # birth doubles as the occupancy marker (−1 = free slot): the
        # destination index itself is never consulted in transit — delivery
        # is decided by the record reaching zero — so the batched state
        # carries no dst array at all.
        rec, birth, port = state["rec"], state["birth"], state["port"]
        if scheduled:
            # resolve the current epoch INSIDE the scan carry: one dynamic
            # gather per (E, …) mask stack, no per-epoch retrace.  Packets
            # enqueued at a node that just died are dropped HERE (counted
            # into `dropped` below), so per-slot conservation holds; its
            # injection BACKLOG dies with it too (pending demand is not a
            # packet — clearing it keeps a dead node from injecting while
            # dead, and is a no-op at E=1 where dead nodes never backlog)
            with jax.named_scope("sim.epoch"):
                e = tr["epoch"]
                link_ok = state["link_ok"][e]
                inj_ok_e = state["inj_ok"][e]
                deadq = (birth >= 0) & ~inj_ok_e[:, None, None]
                qdrop = deadq.sum()
                birth = jnp.where(deadq, -1, birth)
                backlog0 = jnp.where(inj_ok_e, state["backlog"], 0)
        else:
            link_ok = None if trivial else state["link_ok"]
            qdrop = None
            backlog0 = state["backlog"]
        with jax.named_scope("sim.arbitrate"):
            slot = state["slot"]
            occ = birth >= 0                                   # (N, P, Q)
            if weighted:
                # a packet still paying a multi-slot crossing (wait > 0) sits
                # in its queue slot — occupying space and in_flight — but is
                # not yet eligible to request an output port
                busy, wait = state["busy"], state["wait"]
                elig = occ & (wait == 0)
            else:
                elig = occ
            if express and not trivial:
                # liveness-aware greedy weighted DOR: a carried express port
                # goes stale when its channel dies (and becomes preferable
                # again when it repairs) — re-consult against the current
                # masks every slot.  All-live masks reproduce the carried
                # port (same greedy argmax), keeping forced-mask/pristine
                # lanes equivalent.
                port = jnp.where(
                    occ,
                    _next_port_ext_ok(rec, ctx["pdim"], ctx["psgn"],
                                      ctx["pspan"],
                                      link_ok[:, None, None, :]
                                      ).astype(jnp.int8), NO_PORT)
            elif scheduled and ctx["policy"] != "dor":
                # adaptive/escape re-consult policy_ports against the CURRENT
                # epoch's masks: a carried port can go stale when the world
                # changes under a waiting packet.  With E = 1 the recompute is
                # the identity (the carried port was this very function of the
                # same rec/link_ok), keeping the static run bitwise-equal.
                port = jnp.where(
                    occ,
                    policy_ports(rec, link_ok[:, None, None, :],
                                 ctx["policy"]).astype(jnp.int8), NO_PORT)
            else:
                port = jnp.where(occ, port, NO_PORT)
            if weighted:
                # the state-carried port survives the wait (the packet still
                # wants the same hop once eligible); only the ARBITRATION view
                # hides waiting packets
                port_flat = jnp.where(elig, port, NO_PORT).reshape(N, PQ)
            else:
                port_flat = port.reshape(N, PQ)

            # ---- winner per (node, out-port): segmented min over encoded keys --
            # segment id = node·2n + requested_port, key = prio·PQ + rot —
            # pre-drawn 8-bit threefry priorities (tr["prio"]) + a per-slot
            # rotating tie-break keep the key narrow; priority collisions land
            # on the rotating tie-break, so they carry no systematic
            # queue-slot bias.  The segmented reduction is realized as one
            # fused masked column-min per port bucket (2n static buckets)
            # rather than jax.ops.segment_min, whose scatter-min lowering XLA
            # CPU serializes (~17× slower at N=4096); either way every
            # per-slot intermediate stays O(N·2nQ) — the (N, 2nQ, 2n) one-hot
            # candidate tensor this replaces was the largest tensor of the
            # whole slot program.  Winners are bitwise-identical to the
            # one-hot min-reduce: same keys, same min, per segment
            # (tests/test_sim_memory.py pins the absence of the blowup).
            rot = (pq32[None, :] + jnp.int32(slot)) % PQ       # tie-break perm
            enc = tr["prio"].astype(key_dtype) * key_dtype(PQ) \
                + rot.astype(key_dtype)                        # (N, PQ) < BIG
            w_enc = jnp.stack(
                [jnp.min(jnp.where(port_flat == ports8[p], enc, BIG), axis=1)
                 for p in range(P)], axis=1)                   # (N, P)
            if link_ok is not None:
                # a dead channel moves nothing: mask its winner away (packets
                # requesting it — DOR through a fault — block in place)
                w_enc = jnp.where(link_ok, w_enc, BIG)
            if weighted:
                # a weight-w channel stays held for w slots after a crossing:
                # mask it out of arbitration exactly like a dead link while
                # its busy countdown runs
                w_enc = jnp.where(busy == 0, w_enc, BIG)
            whas = w_enc < BIG
            widx = jnp.where(
                whas, (w_enc.astype(jnp.int32) % PQ - jnp.int32(slot)) % PQ, 0)
            w_srcq = widx // Q                                 # queue it occupies

        with jax.named_scope("sim.link_view"):
            flat_rec = rec.reshape(N, PQ, n)
            flat_birth = birth.reshape(N, PQ)

            # ---- per-link view at the receiver of in-port p ----
            # (gathers composed: winner fields are read once, directly through
            # the sender's winner index)
            in_has = whas[sender, ports]                       # (N, P)
            in_widx = widx[sender, ports]
            in_rec = flat_rec[sender, in_widx]                 # (N, P, n)
            in_birth = flat_birth[sender, in_widx]
            in_srcq = in_widx // Q
            rec_after = in_rec - hop[None]
            done = jnp.abs(rec_after.astype(jnp.int32)).sum(-1) == 0
            deliver = in_has & done
            turning = in_srcq != ports[None]                   # entering this ring
            need = jnp.where(turning, 2, 1)                    # bubble rule
            free0 = Q - occ.sum(axis=2)                        # (N, P) per queue

        # ---- acceptance: exact sequential-sweep fixed point ----
        with jax.named_scope("sim.accept"):
            # The reference resolves same-slot space reuse by sweeping ports in
            # index order: in-port p sees slots vacated by winners that left
            # through ports p' < p.  That recurrence needs only an (N, P)
            # carry — per-queue vacancy counts and acceptance flags — so the
            # heavy per-link quantities above stay one batched pass and the
            # fixed point itself is a cheap `lax.scan` over the 2n port levels
            # (bitwise-equal acceptance to the reference sweep given the same
            # winners).
            lvl_xs = dict(h=in_has.T, dn=done.T, f=free0.T, nd=need.T,
                          dl=deliver.T, rx=receiver.T, wq=w_srcq.T, wh=whas.T,
                          p=ports)

            def level(vac, x):
                acc_p = x["h"] & ~x["dn"] & (
                    x["f"] + jnp.take(vac, x["p"], axis=1) >= x["nd"])
                # my port-p winner departs iff the packet moved at its receiver
                dep_w = (x["dl"] | acc_p)[x["rx"]] & x["wh"]
                vac = vac + jnp.where(
                    dep_w[:, None] & (x["wq"][:, None] == ports[None, :]), 1, 0)
                return vac, acc_p

            _, accT = jax.lax.scan(level, jnp.zeros((N, P), jnp.int32), lvl_xs)
            acc = accT.T                                       # (N, P)
            moved = deliver | acc

        with jax.named_scope("sim.finish"):
            delivered = deliver.sum()
            # latency telemetry measures only packets BORN in the measured
            # window: warmup-era births carry queue-buildup ages that are not
            # steady-state samples (the PR-6 warmup-bias fix).  birth >= warmup
            # implies delivery slot > warmup, so these sums need no extra
            # counted gate.
            age = slot + 1 - in_birth                          # (N, P)
            if weighted:
                # delivery is counted at the win slot, but the packet still
                # pays the final crossing: its true arrival is wgt[p]−1
                # slots later (weight-1 adds 0 — identical arithmetic)
                age = age + (wgt - 1)[None, :]
            meas = deliver & (in_birth >= warmup)
            lat_sum = jnp.where(meas, age, 0).sum()
            lat_cnt = meas.sum()

        # ---- apply: clear departed slots + fused transit/injection write --
        with jax.named_scope("sim.apply"):
            # Transit fills the FIRST free slot of the in-queue, injection the
            # LAST free slot of its ring's queue; when both fire on the same
            # queue the bubble rule guarantees ≥3 free post-clear slots, so
            # the two one-hot masks never collide and every state array takes
            # a single fused where-chain.
            dep_port = moved[receiver, ports] & whas
            # a queue slot departs iff it IS its port's winner and the link
            # moves: every enc < BIG, so a sentinel slot and a port that
            # moves nothing (both read BIG) never match
            dep_slot = _port_lookup(jnp.where(dep_port, w_enc, BIG), BIG,
                                    port_flat) == enc          # (N, PQ)
            birth_cleared = jnp.where(dep_slot, -1, flat_birth).reshape(N, P, Q)
            free_mask = birth_cleared < 0
            qi = jnp.arange(Q)[None, None, :]
            slot_f = jnp.argmax(free_mask, axis=2)             # (N, P) first free
            slot_l = (Q - 1) - jnp.argmax(free_mask[:, :, ::-1], axis=2)
            wmask = acc[:, :, None] & (qi == slot_f[:, :, None])
            if express and not trivial:
                port_in = _next_port_ext_ok(rec_after, ctx["pdim"],
                                            ctx["psgn"], ctx["pspan"],
                                            link_ok[:, None, :])
            elif express:
                port_in = _next_port_ext(rec_after, ctx["pdim"], ctx["psgn"],
                                         ctx["pspan"])         # (N, P) next hop
            elif trivial:
                port_in, _, _ = _next_port(rec_after)          # (N, P) next hop
            else:
                port_in = policy_ports(rec_after, link_ok[:, None, :],
                                       ctx["policy"])

            # injection from pre-drawn traffic (after transit: in-flight
            # traffic has priority; entering a ring costs 2 free slots)
            want_new = tr["u"] < state["load"]
            if scheduled:
                want_new = want_new & inj_ok_e
            elif not trivial:
                want_new = want_new & state["inj_ok"]
            want = want_new | (backlog0 > 0)
            depcnt = dep_slot.reshape(N, P, Q).sum(axis=2)
            freeq_post = free0 + depcnt - acc                  # after transit
            inj_p = tr["p"]
            if express and not trivial:
                # the pre-drawn port table is liveness-ignorant; recompute
                # the greedy weighted-DOR port against the current masks so
                # a new packet never queues behind a dead express channel
                # while its base port is live
                inj_p = _next_port_ext_ok(tr["r"], ctx["pdim"], ctx["psgn"],
                                          ctx["pspan"],
                                          link_ok).astype(jnp.int8)
            inj_port = inj_p.astype(jnp.int32)
            if trivial:
                drop = None
                can = want & (jnp.take_along_axis(
                    freeq_post, inj_port[:, None], axis=1)[:, 0] >= 2) & tr["v"]
            else:
                # the drop mask is pattern-specific, so — like di_fixed — it
                # lives in the STATE: the compiled runner stays shared across
                # fixed patterns (the cache key only carries fixed-ness)
                drop = want & ~(state["dst_live_fixed"][e] if scheduled
                                else state["dst_live_fixed"])
                ipc = jnp.minimum(inj_port, P - 1)             # clamp P sentinel
                can = (want & ~drop & (jnp.take_along_axis(
                    freeq_post, ipc[:, None], axis=1)[:, 0] >= 2)
                    & tr["v"] & (inj_port < P))
            imask = (can[:, None, None]
                     & (ports8[None, :, None] == inj_p[:, None, None])
                     & (qi == slot_l[:, :, None]))
            backlog = backlog0 + want_new - can
            if drop is not None:
                backlog = backlog - drop
            backlog = jnp.clip(backlog, 0, 1 << 30)

            new_rec = jnp.where(
                imask[..., None], tr["r"][:, None, None, :],
                jnp.where(wmask[..., None], rec_after[:, :, None, :], rec))
            new_birth = jnp.where(
                imask, slot.astype(birth.dtype),
                jnp.where(wmask, in_birth[:, :, None], birth_cleared))
            new_port = jnp.where(
                imask, inj_p[:, None, None],
                jnp.where(wmask, port_in[:, :, None].astype(jnp.int8), port))

            updates = dict(rec=new_rec, birth=new_birth, port=new_port,
                           backlog=backlog)
            if weighted:
                # countdown bookkeeping: a departed slot's wait clears with
                # it, an arriving packet starts at wgt[in-port]−1 (the write
                # masks never collide with injection, which starts at 0 —
                # crossing no link costs nothing), and the crossed channel's
                # busy restarts at wgt−1 (blocked for the w−1 FOLLOWING slots)
                wait_dec = jnp.where(dep_slot.reshape(N, P, Q), 0,
                                     jnp.maximum(wait - 1, 0))
                updates["wait"] = jnp.where(
                    imask, 0,
                    jnp.where(wmask, (wgt - 1)[None, :, None], wait_dec))
                updates["busy"] = jnp.where(dep_port, wgt[None, :] - 1,
                                            jnp.maximum(busy - 1, 0))
        if ctx["hist_bins"]:
            with jax.named_scope("sim.histogram"):
                updates["lat_hist"] = state["lat_hist"] + _bucket_counts(
                    age, meas, ctx["hist_bins"])
        with jax.named_scope("sim.finish"):
            if not trivial:
                # dead-channel audit: count every crossing (all slots, not just
                # measured ones — "never" means never)
                updates["link_use"] = state["link_use"] + dep_port.astype(jnp.int32)
            out = _finish_slot(state, warmup, delivered, lat_sum, lat_cnt, can,
                               drop, qdrop=qdrop, **updates)
            return out, (_timeline_y(out, new_birth, dep_port, link_ok)
                         if scheduled else None)

    return slot_step


def _timeline_y(out, occupancy, dep_port, link_ok):
    """One per-slot `SimTimeline` sample: post-slot cumulative counters,
    current queue occupancy, and the dead-channel-crossing audit (crossing
    a channel while it is dead is impossible by construction — arbitration
    masks it — so this is an exact always-zero regression tripwire)."""
    crossed = dep_port if dep_port.dtype == jnp.bool_ else dep_port != 0
    y = dict(delivered=out["delivered"], injected=out["injected"],
             dropped=out["dropped"],
             in_flight=(occupancy >= 0).sum(),
             dead_crossings=(crossed & ~link_ok).sum())
    if "lat_hist" in out:
        # cumulative post-slot histogram: windowed differences on the host
        # give per-slot tail-latency traces (SimTimeline.recovery_slots)
        y["lat_hist"] = out["lat_hist"]
    return y


def _make_slot_step_fused(ctx, warmup: int):
    """The batched slot update routed through the Pallas kernel
    (`repro.kernels.sim_step.fused_slot_step`): winner segmented-min +
    acceptance fixed point + one-hot clears/transit/injection writes run
    as ONE kernel pass over VMEM node tiles.  Same state layout and
    pre-drawn traffic as `_make_slot_step_batched`, and bitwise-equal
    results; off-TPU the kernel runs in interpret mode (validated by the
    differential suite at quick shapes).  On a TPU backend it raises:
    Mosaic refuses the kernel's in-kernel gathers (see kernels/sim_step.py),
    and running it anywhere else would hide that the chip never ran it."""
    from ..kernels.ops import _on_tpu
    from ..kernels.sim_step import fused_slot_step
    if _on_tpu():
        raise NotImplementedError(
            'impl="fused" does not lower for TPU: Mosaic refuses the '
            "kernel's in-kernel gather `sender = nbr[:, opp]` (ValueError: "
            "Shape mismatch in input, indices and output, from "
            '_gather_lowering_rule). Use impl="batched" on the chip.')
    N = ctx["N"]
    nbr = ctx["nbr"]
    trivial = ctx["trivial"]
    scheduled = ctx.get("scheduled", False)

    def slot_step(state, tr):
        slot = state["slot"]
        rec, birth, port = state["rec"], state["birth"], state["port"]
        if scheduled:
            # epoch resolution + dead-node queue kill + the stale-port
            # policy re-consult all happen HERE, in the scan carry — the
            # kernel itself stays epoch-oblivious (it sees one slot's
            # static-shaped masks) and bitwise-mirrors the batched step.
            e = tr["epoch"]
            link_ok = state["link_ok"][e]
            inj_ok_e = state["inj_ok"][e]
            dst_live = state["dst_live_fixed"][e]
            deadq = (birth >= 0) & ~inj_ok_e[:, None, None]
            qdrop = deadq.sum()
            birth = jnp.where(deadq, -1, birth)
            # a dead node's injection backlog dies with it (see batched)
            backlog0 = jnp.where(inj_ok_e, state["backlog"], 0)
            if ctx["policy"] != "dor":
                port = policy_ports(rec, link_ok[:, None, None, :],
                                    ctx["policy"]).astype(jnp.int8)
        else:
            link_ok = None if trivial else state["link_ok"]
            dst_live = None if trivial else state["dst_live_fixed"]
            qdrop = None
            backlog0 = state["backlog"]
        want_new = tr["u"] < state["load"]
        if scheduled:
            want_new = want_new & inj_ok_e
        elif not trivial:
            want_new = want_new & state["inj_ok"]
        want = want_new | (backlog0 > 0)
        (new_rec, new_birth, new_port, deliver, lat, can8, drop8,
         dep_port) = fused_slot_step(
            rec, birth, port, tr["prio"], slot,
            want, tr["r"], tr["p"], tr["v"], nbr,
            link_ok=link_ok,
            dst_live_fixed=dst_live,
            policy="dor" if trivial else ctx["policy"],
            interpret=True)
        can = can8 != 0
        drop = None if trivial else (drop8 != 0)
        backlog = backlog0 + want_new - can
        if drop is not None:
            backlog = backlog - drop
        backlog = jnp.clip(backlog, 0, 1 << 30)
        # the kernel's `lat` output is slot+1−birth where delivered (0
        # elsewhere), so birth = slot+1−lat: the measured-window filter and
        # histogram run OUTSIDE the kernel on its existing outputs — the
        # kernel body stays untouched and the batched bitwise-parity
        # contract is preserved counter for counter
        delivered_m = deliver != 0
        meas = delivered_m & (slot + 1 - lat >= warmup)
        lat_sum = jnp.where(meas, lat, 0).sum()
        lat_cnt = meas.sum()
        updates = dict(rec=new_rec, birth=new_birth, port=new_port,
                       backlog=backlog)
        if ctx["hist_bins"]:
            updates["lat_hist"] = state["lat_hist"] + _bucket_counts(
                lat, meas, ctx["hist_bins"])
        if not trivial:
            updates["link_use"] = state["link_use"] + dep_port.astype(jnp.int32)
        out = _finish_slot(state, warmup, delivered_m.sum(), lat_sum,
                           lat_cnt, can, drop, qdrop=qdrop, **updates)
        return out, (_timeline_y(out, new_birth, dep_port, link_ok)
                     if scheduled else None)

    return slot_step


def _make_slot_step_reference(ctx, warmup: int):
    """The pre-batching per-port sweep (semantic oracle for the batched
    implementation; random output-link arbitration, sequential same-slot
    space reuse in port order).  Under a `FaultSchedule` the per-epoch
    mask stacks stay BAKED constants (full-fingerprint cache key) and the
    step resolves the current epoch from the slot counter — the oracle
    defines the per-slot semantics the traced implementations must
    match."""
    n, N, P, Q = ctx["n"], ctx["N"], ctx["P"], ctx["Q"]
    nbr = ctx["nbr"]
    opp = [p ^ 1 for p in range(P)]
    trivial = ctx["trivial"]
    scheduled = ctx.get("scheduled", False)
    weighted = ctx.get("weighted", False)
    express = ctx.get("express", False)
    if express:
        dim_of = np.asarray(ctx["pdim"]).tolist()
        sgn_of = np.asarray(ctx["psgn"]).tolist()
        span_of = np.asarray(ctx["pspan"]).tolist()
    else:
        dim_of = [p // 2 for p in range(P)]
        sgn_of = [1 - 2 * (p % 2) for p in range(P)]
        span_of = [1] * P
    wgt_of = (np.asarray(ctx["wgt"]).tolist() if weighted else [1] * P)

    def slot_step(state, key):
        dst, rec, birth = state["dst"], state["rec"], state["birth"]
        slot = state["slot"]
        if scheduled:
            e = ctx["slot2epoch"][slot]
            link_ok = ctx["link_ok"][e]
            node_ok = ctx["inj_ok"][e]
            masks = dict(link_ok=link_ok, inj_ok=node_ok, dst_ok=node_ok,
                         live_tbl=ctx["live_tbl"][e],
                         n_live=ctx["n_live"][e])
            deadq = (dst >= 0) & ~node_ok[:, None, None]
            qdrop = deadq.sum()
            dst = jnp.where(deadq, -1, dst)
            # a dead node's injection backlog dies with it (see batched):
            # _inject reads the cleared value, so a dead source never
            # injects from stale demand while dead
            state = dict(state,
                         backlog=jnp.where(node_ok, state["backlog"], 0))
        else:
            link_ok = None if trivial else ctx["link_ok"]
            masks, qdrop = None, None
        occ = dst >= 0                                     # (N, P, Q)
        if express and not trivial:
            # liveness-aware greedy weighted DOR (see the batched step)
            port = _next_port_ext_ok(rec, ctx["pdim"], ctx["psgn"],
                                     ctx["pspan"],
                                     link_ok[:, None, None, :])
        elif express:
            port = _next_port_ext(rec, ctx["pdim"], ctx["psgn"],
                                  ctx["pspan"])             # (N, P, Q)
        elif trivial:
            port, _, _ = _next_port(rec)                   # (N, P, Q)
        else:
            port = policy_ports(rec, link_ok[:, None, None, :],
                                ctx["policy"])
        if weighted:
            # packets still paying a multi-slot crossing are ineligible
            busy, wait = state["busy"], state["wait"]
            port = jnp.where(occ & (wait == 0), port, -1)
        else:
            port = jnp.where(occ, port, -1)

        # ---- arbitration: one winner packet per (node, out-port) ----
        rand = jax.random.uniform(jax.random.fold_in(key, 1), (N, P, Q))
        requested = port[..., None] == jnp.arange(P)
        if not trivial:
            # dead channels never arbitrate: packets aimed at them block
            requested = requested & link_ok[:, None, None, :]
        if weighted:
            # a busy (multi-slot-held) channel moves nothing this slot
            requested = requested & (busy == 0)[:, None, None, :]
        flatscore = jnp.where(requested, rand[..., None], -1.0)
        flat = flatscore.reshape(N, P * Q, P)
        widx = jnp.argmax(flat, axis=1)                    # (N, P) flat pq index
        whas = jnp.take_along_axis(flat, widx[:, None, :], axis=1)[:, 0, :] >= 0.0

        flat_dst = dst.reshape(N, P * Q)
        flat_rec = rec.reshape(N, P * Q, n)
        flat_birth = birth.reshape(N, P * Q)
        rows = jnp.arange(N)[:, None]
        w_dst = flat_dst[rows, widx]                       # (N, P)
        w_rec = flat_rec[rows, widx]                       # (N, P, n)
        w_birth = flat_birth[rows, widx]
        w_src_port = widx // Q                             # (N, P)

        # ---- per-link acceptance (each in-queue receives ≤ 1 packet) ----
        delivered = jnp.int32(0)
        lat_sum = jnp.int32(0)
        lat_cnt = jnp.int32(0)
        dead_crossings = jnp.int32(0)
        age_l, meas_l, del_l = [], [], []
        new_dst, new_rec, new_birth = dst, rec, birth
        if weighted:
            # countdowns tick once per slot; crossings below re-arm them
            new_busy = jnp.maximum(busy - 1, 0)
            new_wait = jnp.maximum(wait - 1, 0)
        link_use = None if trivial else state["link_use"]
        for p in range(P):
            d_p = dim_of[p]
            s_p = sgn_of[p] * span_of[p]                   # signed hop span
            w_p = wgt_of[p]                                # slot cost
            u = nbr[:, opp[p]]                             # sender for recv w
            has = whas[u, p]
            pk_dst = w_dst[u, p]
            pk_rec = w_rec[u, p]
            pk_birth = w_birth[u, p]
            pk_src_port = w_src_port[u, p]
            rec_after = pk_rec.at[:, d_p].add(-s_p)
            done = jnp.abs(rec_after.astype(jnp.int32)).sum(-1) == 0
            will_deliver = has & done
            turning = pk_src_port != p                     # entering this ring
            freeq = (new_dst[:, p] < 0).sum(axis=1)
            ok = has & ~done & (freeq >= jnp.where(turning, 2, 1))
            moved = will_deliver | ok
            # stats — latency over measured deliveries only (birth >=
            # warmup, the PR-6 warmup-bias fix; identical to the batched
            # step's filter).  Weighted channels add their final-crossing
            # cost: delivery is counted at the win slot, arrival is w−1
            # slots later.
            age_p = slot + 1 - pk_birth
            if weighted:
                age_p = age_p + (w_p - 1)
            meas_p = will_deliver & (pk_birth >= warmup)
            delivered += will_deliver.sum()
            lat_sum += jnp.where(meas_p, age_p, 0).sum()
            lat_cnt += meas_p.sum()
            if ctx["hist_bins"] or ctx.get("lat_trace"):
                age_l.append(age_p)
                meas_l.append(meas_p)
                del_l.append(will_deliver)
            if scheduled:
                dead_crossings += (moved & ~link_ok[u, p]).sum()
            if link_use is not None:
                # crossing of channel (u, p); u ↔ receiver is a bijection,
                # so the scatter-add never collides
                link_use = link_use.at[u, p].add(moved.astype(jnp.int32))
            # clear winner slot at sender
            sel = widx[:, p]
            fd = new_dst.reshape(N, P * Q)
            fd = fd.at[u, sel[u]].set(jnp.where(moved, -1, fd[u, sel[u]]))
            new_dst = fd.reshape(N, P, Q)
            # write into receiver queue p (first free slot)
            slot_idx = jnp.argmax(new_dst[:, p] < 0, axis=1)
            r_ = jnp.arange(N)
            new_dst = new_dst.at[r_, p, slot_idx].set(
                jnp.where(ok, pk_dst, new_dst[r_, p, slot_idx]))
            new_rec = new_rec.at[r_, p, slot_idx].set(
                jnp.where(ok[:, None], rec_after, new_rec[r_, p, slot_idx]))
            new_birth = new_birth.at[r_, p, slot_idx].set(
                jnp.where(ok, pk_birth, new_birth[r_, p, slot_idx]))
            if weighted:
                # crossed channel (u, p) re-arms its hold; the accepted
                # packet starts its own eligibility countdown at w−1
                new_busy = new_busy.at[u, p].set(
                    jnp.where(moved, w_p - 1, new_busy[u, p]))
                new_wait = new_wait.at[r_, p, slot_idx].set(
                    jnp.where(ok, w_p - 1, new_wait[r_, p, slot_idx]))

        if weighted:
            # free slots carry no countdown: zero them so injection (which
            # crosses no link) always starts eligible
            new_wait = jnp.where(new_dst >= 0, new_wait, 0)
        new_dst, new_rec, new_birth, backlog, can, drop = _inject(
            state, key, new_dst, new_rec, new_birth, ctx, masks)
        updates = dict(dst=new_dst, rec=new_rec, birth=new_birth,
                       backlog=backlog)
        if weighted:
            updates["busy"] = new_busy
            updates["wait"] = new_wait
        if ctx["hist_bins"]:
            updates["lat_hist"] = state["lat_hist"] + _bucket_counts(
                jnp.stack(age_l, 1), jnp.stack(meas_l, 1),
                ctx["hist_bins"])
        if link_use is not None:
            updates["link_use"] = link_use
        out = _finish_slot(state, warmup, delivered, lat_sum, lat_cnt, can,
                           drop, qdrop=qdrop, **updates)
        y = None
        if scheduled:
            y = dict(delivered=out["delivered"], injected=out["injected"],
                     dropped=out["dropped"],
                     in_flight=(new_dst >= 0).sum(),
                     dead_crossings=dead_crossings)
            if ctx["hist_bins"]:
                y["lat_hist"] = out["lat_hist"]
        elif ctx.get("lat_trace"):
            # the per-packet oracle: every delivery's age + flags, per slot
            # (test-scale only — slots×N×P device→host traffic).  The meas
            # flag travels too: weighted ages carry the +w−1 final-crossing
            # term, so the host cannot reconstruct birth from slot+1−age.
            y = dict(age=jnp.stack(age_l, 1), deliv=jnp.stack(del_l, 1),
                     meas=jnp.stack(meas_l, 1))
        return out, y

    return slot_step


def _make_slot_step_vc_batched(ctx, warmup: int):
    """The credit-flow virtual-channel router (vcs > 1), vectorised with
    the same no-scatter discipline as `_make_slot_step_batched`:

      * state generalizes the per-port FIFO to (N, 2n, V, Q) lanes plus a
        carried (N, 2n, V) CREDIT array — `credit[w, p, v]` is the
        advertised free window of queue (w, p, v), initialized to
        `credits` (or Q) and kept exact incrementally (+1 per departure,
        −1 per acceptance/injection into the lane),
      * every occupied slot re-evaluates its (out-port, lane) request
        per slot via `routing_engine.credit_vc_select`: lanes 1..V−1 are
        credit-gated minimal-adaptive (max downstream credits, rotating
        tie-break), lane 0 is the restricted-DOR ESCAPE lane with bubble
        flow control — the Duato construction, so the router is
        deadlock-free by the escape-CDG acyclicity argument
        (tests/test_vc_router.py enumerates it).  No per-packet port is
        carried: the choice depends on the live credit state,
      * winner per (node, out-port) is the same segmented min, now over
        N·2nVQ encoded keys (lanes share the physical link — one packet
        per channel per slot),
      * acceptance needs: escape-lane entry (turn/injection) 2 free
        credits, in-lane continuation 1 (the bubble rule per lane-ring);
        adaptive lanes need 1 — their eligibility is already credit>0 at
        selection, and deadlock recovery is the escape lane's job.  Under
        policy "dor" every lane runs the bubble rule (no credit gate in
        selection), which keeps plain DOR deadlock-free per lane-ring.

    V=1 never reaches this builder — `_get_runner` dispatches to the
    pre-VC `_make_slot_step_batched`, keeping the vcs=1 program bitwise
    identical.  `FaultSchedule` timelines compose: the per-epoch mask
    stacks are gathered in the scan carry exactly like the V=1 step, a
    killed node's enqueued phits drop across all lanes with the freed
    credits restored in the same slot, and a degenerate E=1 schedule is
    bitwise-equal to the static `Scenario` run.  Express overlays extend
    the port axis (geometry flows through `credit_vc_select`'s
    port_geom); only the fused kernel stays V=1 (rejected in
    `SimConfig`)."""
    n, N, P, Q, V = ctx["n"], ctx["N"], ctx["P"], ctx["Q"], ctx["V"]
    nbr = ctx["nbr"]
    rec_dtype = ctx["rec_dtype"]
    trivial = ctx["trivial"]
    policy = ctx["policy"]
    adaptive = policy in ("adaptive", "escape")
    PV, PVQ = P * V, P * V * Q
    key_dtype = jnp.int16 if PVQ <= 127 else jnp.int32
    BIG = key_dtype(np.iinfo(np.dtype(key_dtype)).max)
    ports = jnp.arange(P)
    opp = jnp.arange(P) ^ 1
    sender = nbr[:, opp]                           # (N, P): src of in-port p
    receiver = nbr                                 # (N, P): dst of out-port p
    express = ctx.get("express", False)
    if express:
        # overlay ports hop span·e_dim; the table already carries signs,
        # and `credit_vc_select` scores the extended axis via port_geom
        hop = ctx["hop_tab"].astype(rec_dtype)
        port_geom = (ctx["pdim"], ctx["psgn"], ctx["pspan"])
    else:
        dim_p = ports // 2
        sgn_p = 1 - 2 * (ports % 2)
        hop = np.zeros((P, n), np.int64)
        hop[np.arange(P), np.asarray(dim_p)] = np.asarray(sgn_p)
        hop = jnp.asarray(hop, rec_dtype)
        port_geom = None
    # fault-aware escape: only the "escape" policy opts into the PR 3
    # misroute when VC0's DOR port is dead ("adaptive" keeps the packet
    # blocking, like V=1 DOR through a fault); inert on live ports, so
    # all-live masks select identically either way
    esc_fb = policy == "escape" and not trivial
    scheduled = ctx.get("scheduled", False)
    pvq32 = jnp.arange(PVQ, dtype=jnp.int32)
    qids = jnp.arange(PV, dtype=jnp.int32)
    varange = jnp.arange(V, dtype=jnp.int32)
    weighted = ctx.get("weighted", False)
    if weighted:
        wgt = ctx["wgt"]                    # (P,) int32 slot costs

    def take_q(arr_flat, qidx):
        """(N, PV) per-lane values gathered at a (N,) queue id each."""
        return jnp.take_along_axis(arr_flat, qidx[:, None], axis=1)[:, 0]

    def slot_step(state, tr):
        rec, birth, credit = state["rec"], state["birth"], state["credit"]
        slot = state["slot"]
        if scheduled:
            # resolve the current epoch INSIDE the scan carry (one gather
            # per mask stack, no per-epoch retrace).  A killed node's
            # enqueued phits drop across ALL lanes; the dropped occupancy
            # frees its queue space, so the lane's advertised credits are
            # restored HERE — `credit == credit_init − occupancy` holds
            # at every slot.  At E = 1 dead nodes never hold occupants
            # (their channels are dead and their injection is masked from
            # slot 0), so deadq ≡ False and the restore adds zero: the
            # static Scenario run stays bitwise-equal.
            with jax.named_scope("sim.epoch"):
                e = tr["epoch"]
                link_ok = state["link_ok"][e]
                inj_ok_e = state["inj_ok"][e]
                deadq = (birth >= 0) & ~inj_ok_e[:, None, None, None]
                qdrop = deadq.sum()
                birth = jnp.where(deadq, -1, birth)
                credit = credit + deadq.sum(axis=3)
                backlog0 = jnp.where(inj_ok_e, state["backlog"], 0)
        else:
            link_ok = None if trivial else state["link_ok"]
            qdrop = None
            backlog0 = state["backlog"]
        with jax.named_scope("sim.vc_select"):
            occ = birth >= 0                                   # (N, P, V, Q)

            # ---- per-packet (out-port, lane) request, credit-aware ----
            # downstream credit view: what u sees for out-port p is the
            # credit of ITS OWN queue at the receiver, (nbr[u,p], p, ·)
            cd = credit[nbr, ports[None, :]]                   # (N, P, V)
            lok = (jnp.ones((N, P), bool) if trivial else link_ok)
            sel_port, sel_vc = credit_vc_select(
                rec, lok[:, None, None, None, :],
                cd[:, None, None, None, :, :], policy, rot=slot,
                port_geom=port_geom, escape_fallback=esc_fb)
            if weighted:
                # multi-slot crossings: waiting packets are ineligible
                busy, wait = state["busy"], state["wait"]
                sel_port = jnp.where(occ & (wait == 0), sel_port, P)
            else:
                sel_port = jnp.where(occ, sel_port, P)         # sentinel if free
            port_flat = sel_port.reshape(N, PVQ)
            vc_flat = sel_vc.reshape(N, PVQ)

        # ---- winner per (node, out-port): segmented min over lanes ----
        with jax.named_scope("sim.arbitrate"):
            rot = (pvq32[None, :] + jnp.int32(slot)) % PVQ
            enc = tr["prio"].astype(key_dtype) * key_dtype(PVQ) \
                + rot.astype(key_dtype)                        # (N, PVQ)
            w_enc = jnp.stack(
                [jnp.min(jnp.where(port_flat == p, enc, BIG), axis=1)
                 for p in range(P)], axis=1)                   # (N, P)
            if link_ok is not None:
                w_enc = jnp.where(link_ok, w_enc, BIG)
            if weighted:
                # a held (busy) physical channel arbitrates nothing this slot
                w_enc = jnp.where(busy == 0, w_enc, BIG)
            whas = w_enc < BIG
            widx = jnp.where(
                whas, (w_enc.astype(jnp.int32) % PVQ - jnp.int32(slot)) % PVQ,
                0)
            w_srcq = widx // Q                                 # queue id p·V+v

            flat_rec = rec.reshape(N, PVQ, n)
            flat_birth = birth.reshape(N, PVQ)
            rows = jnp.arange(N)[:, None]
            w_vc = jnp.take_along_axis(vc_flat, widx, axis=1)  # target lane

        # ---- per-link view at the receiver of in-port p ----
        with jax.named_scope("sim.link_view"):
            in_has = whas[sender, ports]                       # (N, P)
            in_widx = widx[sender, ports]
            in_rec = flat_rec[sender, in_widx]                 # (N, P, n)
            in_birth = flat_birth[sender, in_widx]
            in_srcq = w_srcq[sender, ports]                    # source queue id
            in_vc = w_vc[sender, ports]                        # target lane
            rec_after = in_rec - hop[None]
            done = jnp.abs(rec_after.astype(jnp.int32)).sum(-1) == 0
            deliver = in_has & done
            tgt_q = ports[None, :] * V + in_vc                 # target queue id
            # bubble rule per lane-ring: continuing in the SAME (port, lane)
            # needs 1 free credit, entering (turn, lane switch) needs 2;
            # credit-gated adaptive lanes need only 1 (Duato)
            need = jnp.where(in_srcq == tgt_q, 1, 2)
            if adaptive:
                need = jnp.where(in_vc > 0, 1, need)

        # ---- acceptance: sequential-sweep fixed point over channels ----
        with jax.named_scope("sim.accept"):
            # same recurrence as V=1, with a queue-granular (N, P·V) vacancy
            # carry: each channel p writes only queue (w, p, lane), so lanes
            # never collide and the carry stays tiny
            credit_flat = credit.reshape(N, PV)
            lvl_xs = dict(h=in_has.T, dn=done.T, nd=need.T, dl=deliver.T,
                          rx=receiver.T, wq=w_srcq.T, wh=whas.T, tq=tgt_q.T)

            def level(vac, x):
                freeq = take_q(credit_flat, x["tq"]) + take_q(vac, x["tq"])
                acc_p = x["h"] & ~x["dn"] & (freeq >= x["nd"])
                dep_w = (x["dl"] | acc_p)[x["rx"]] & x["wh"]
                vac = vac + jnp.where(
                    dep_w[:, None] & (x["wq"][:, None] == qids[None, :]), 1, 0)
                return vac, acc_p

            _, accT = jax.lax.scan(level, jnp.zeros((N, PV), jnp.int32), lvl_xs)
            acc = accT.T                                       # (N, P)
            moved = deliver | acc

        with jax.named_scope("sim.finish"):
            delivered = deliver.sum()
            age = slot + 1 - in_birth
            if weighted:
                # final-crossing cost: arrival is wgt[p]−1 slots after the win
                age = age + (wgt - 1)[None, :]
            meas = deliver & (in_birth >= warmup)
            lat_sum = jnp.where(meas, age, 0).sum()
            lat_cnt = meas.sum()

        # ---- apply: clears + one-hot transit/injection writes ----
        with jax.named_scope("sim.apply"):
            dep_port = moved[receiver, ports] & whas
            # departs iff winner and moved (see the V=1 step)
            dep_slot = _port_lookup(jnp.where(dep_port, w_enc, BIG), BIG,
                                    port_flat) == enc          # (N, PVQ)
            birth_cleared = jnp.where(dep_slot, -1,
                                      flat_birth).reshape(N, P, V, Q)
            free_mask = birth_cleared < 0
            qi = jnp.arange(Q)[None, None, None, :]
            slot_f = jnp.argmax(free_mask, axis=3)             # (N, P, V)
            slot_l = (Q - 1) - jnp.argmax(free_mask[..., ::-1], axis=3)
            accv = acc[:, :, None] & (varange[None, None, :] == in_vc[:, :, None])
            wmask = accv[..., None] & (qi == slot_f[..., None])

            # ---- injection (after transit; local credits gate admission) --
            want_new = tr["u"] < state["load"]
            if scheduled:
                want_new = want_new & inj_ok_e
            elif not trivial:
                want_new = want_new & state["inj_ok"]
            want = want_new | (backlog0 > 0)
            depcnt = dep_slot.reshape(N, P, V, Q).sum(axis=3)  # (N, P, V)
            credit_post = credit + depcnt - accv.astype(jnp.int32)
            inj_port, inj_vc = credit_vc_select(tr["r"], lok, credit_post,
                                                policy, rot=slot,
                                                port_geom=port_geom,
                                                escape_fallback=esc_fb)
            ipc = jnp.minimum(inj_port, P - 1)                 # clamp P sentinel
            freesel = take_q(credit_post.reshape(N, PV), ipc * V + inj_vc)
            can = want & (freesel >= 2) & tr["v"] & (inj_port < P)
            if trivial:
                drop = None
            else:
                drop = want & ~(state["dst_live_fixed"][e] if scheduled
                                else state["dst_live_fixed"])
                can = can & ~drop
            imask = (can[:, None, None, None]
                     & (ports[None, :, None, None] == ipc[:, None, None, None])
                     & (varange[None, None, :, None]
                        == inj_vc[:, None, None, None])
                     & (qi == slot_l[..., None]))
            backlog = backlog0 + want_new - can
            if drop is not None:
                backlog = backlog - drop
            backlog = jnp.clip(backlog, 0, 1 << 30)

            new_rec = jnp.where(
                imask[..., None], tr["r"][:, None, None, None, :],
                jnp.where(wmask[..., None], rec_after[:, :, None, None, :],
                          rec))
            new_birth = jnp.where(
                imask, slot.astype(birth.dtype),
                jnp.where(wmask, in_birth[:, :, None, None], birth_cleared))
            new_credit = credit_post - imask.sum(axis=3)

        with jax.named_scope("sim.finish"):
            # per-lane telemetry: deliveries by the winner's SOURCE lane,
            # injections (incl. drops — they count as injected) by the
            # admitted lane; warmup-gated like the scalar counters
            counted = slot >= warmup
            src_vc = in_srcq % V
            vc_del = (deliver[..., None]
                      & (src_vc[..., None] == varange)).sum((0, 1))
            injm = can if drop is None else (can | drop)
            vc_inj = (injm[:, None] & (inj_vc[:, None] == varange)).sum(0)

            updates = dict(
                rec=new_rec, birth=new_birth, credit=new_credit,
                backlog=backlog,
                vc_delivered=state["vc_delivered"] + jnp.where(counted, vc_del,
                                                               0),
                vc_injected=state["vc_injected"] + jnp.where(counted, vc_inj,
                                                             0))
        if weighted:
            with jax.named_scope("sim.apply"):
                wait_dec = jnp.where(dep_slot.reshape(N, P, V, Q), 0,
                                     jnp.maximum(wait - 1, 0))
                updates["wait"] = jnp.where(
                    imask, 0, jnp.where(wmask, (wgt - 1)[None, :, None, None],
                                        wait_dec))
                updates["busy"] = jnp.where(dep_port, wgt[None, :] - 1,
                                            jnp.maximum(busy - 1, 0))
        if ctx["hist_bins"]:
            with jax.named_scope("sim.histogram"):
                updates["lat_hist"] = state["lat_hist"] + _bucket_counts(
                    age, meas, ctx["hist_bins"])
        with jax.named_scope("sim.finish"):
            if not trivial:
                updates["link_use"] = state["link_use"] + dep_port.astype(
                    jnp.int32)
            out = _finish_slot(state, warmup, delivered, lat_sum, lat_cnt, can,
                               drop, qdrop=qdrop, **updates)
            return out, (_timeline_y(out, new_birth, dep_port, link_ok)
                         if scheduled else None)

    return slot_step


def _make_slot_step_vc_reference(ctx, warmup: int):
    """Per-(port, lane) sweep oracle of the VC credit-flow router: the
    same macro-semantics as `_make_slot_step_vc_batched` (credit-gated
    `credit_vc_select` requests, one winner per physical channel, the
    per-lane bubble/credit acceptance rule, exact incremental credit
    bookkeeping) with the reference arbitration style — i.i.d. uniform
    per-slot scores and scatter writes in channel order.  Validated
    statistically against the batched VC path, like the V=1 oracle."""
    n, N, P, Q, V = ctx["n"], ctx["N"], ctx["P"], ctx["Q"], ctx["V"]
    nbr = ctx["nbr"]
    opp = [p ^ 1 for p in range(P)]
    trivial = ctx["trivial"]
    policy = ctx["policy"]
    adaptive = policy in ("adaptive", "escape")
    PV, PVQ = P * V, P * V * Q
    varange = jnp.arange(V, dtype=jnp.int32)
    scheduled = ctx.get("scheduled", False)
    weighted = ctx.get("weighted", False)
    express = ctx.get("express", False)
    wgt_of = (np.asarray(ctx["wgt"]).tolist() if weighted else [1] * P)
    if express:
        dim_of = np.asarray(ctx["pdim"]).tolist()
        sgn_of = np.asarray(ctx["psgn"]).tolist()
        span_of = np.asarray(ctx["pspan"]).tolist()
        port_geom = (ctx["pdim"], ctx["psgn"], ctx["pspan"])
    else:
        dim_of = [p // 2 for p in range(P)]
        sgn_of = [1 - 2 * (p % 2) for p in range(P)]
        span_of = [1] * P
        port_geom = None
    esc_fb = policy == "escape" and not trivial

    def slot_step(state, key):
        dst, rec, birth = state["dst"], state["rec"], state["birth"]
        credit = state["credit"]
        slot = state["slot"]
        if scheduled:
            # epoch resolution from the slot counter (masks stay BAKED);
            # dead-node drops mirror the batched VC step: occupancy at a
            # killed node clears across all lanes and the freed queue
            # space restores the lane's credits in the same slot
            e = ctx["slot2epoch"][slot]
            link_ok = ctx["link_ok"][e]
            node_ok = ctx["inj_ok"][e]
            masks = dict(link_ok=link_ok, inj_ok=node_ok, dst_ok=node_ok,
                         live_tbl=ctx["live_tbl"][e],
                         n_live=ctx["n_live"][e])
            deadq = (dst >= 0) & ~node_ok[:, None, None, None]
            qdrop = deadq.sum()
            dst = jnp.where(deadq, -1, dst)
            credit = credit + deadq.sum(axis=3)
            state = dict(state,
                         backlog=jnp.where(node_ok, state["backlog"], 0))
        else:
            link_ok = None if trivial else ctx["link_ok"]
            masks, qdrop = None, None
        occ = dst >= 0                                     # (N, P, V, Q)
        lok = jnp.ones((N, P), bool) if trivial else link_ok
        cd = credit[nbr, jnp.arange(P)[None, :]]           # (N, P, V)
        sel_port, sel_vc = credit_vc_select(
            rec, lok[:, None, None, None, :],
            cd[:, None, None, None, :, :], policy, rot=slot,
            port_geom=port_geom, escape_fallback=esc_fb)
        if weighted:
            busy, wait = state["busy"], state["wait"]
            sel_port = jnp.where(occ & (wait == 0), sel_port, -1)
        else:
            sel_port = jnp.where(occ, sel_port, -1)

        # ---- arbitration: one winner per (node, out-port) ----
        rand = jax.random.uniform(jax.random.fold_in(key, 1), (N, P, V, Q))
        requested = sel_port[..., None] == jnp.arange(P)
        if not trivial:
            requested = requested & link_ok[:, None, None, None, :]
        if weighted:
            requested = requested & (busy == 0)[:, None, None, None, :]
        flat = jnp.where(requested, rand[..., None], -1.0).reshape(
            N, PVQ, P)
        widx = jnp.argmax(flat, axis=1)                    # (N, P)
        whas = jnp.take_along_axis(flat, widx[:, None, :],
                                   axis=1)[:, 0, :] >= 0.0
        rows = jnp.arange(N)[:, None]
        flat_dst = dst.reshape(N, PVQ)
        flat_rec = rec.reshape(N, PVQ, n)
        flat_birth = birth.reshape(N, PVQ)
        w_dst = flat_dst[rows, widx]
        w_rec = flat_rec[rows, widx]
        w_birth = flat_birth[rows, widx]
        w_srcq = widx // Q                                 # queue id p·V+v
        w_vc = jnp.take_along_axis(sel_vc.reshape(N, PVQ), widx, axis=1)

        delivered = jnp.int32(0)
        lat_sum = jnp.int32(0)
        lat_cnt = jnp.int32(0)
        dead_crossings = jnp.int32(0)
        vc_del = jnp.zeros((V,), jnp.int32)
        age_l, meas_l, del_l = [], [], []
        new_dst, new_rec, new_birth = dst, rec, birth
        if weighted:
            new_busy = jnp.maximum(busy - 1, 0)
            new_wait = jnp.maximum(wait - 1, 0)
        credit_work = credit                               # (N, P, V)
        link_use = None if trivial else state["link_use"]
        r_ = jnp.arange(N)
        for p in range(P):
            d_p = dim_of[p]
            s_p = sgn_of[p] * span_of[p]                   # signed hop span
            w_p = wgt_of[p]
            u = nbr[:, opp[p]]                             # sender for recv w
            has = whas[u, p]
            pk_dst = w_dst[u, p]
            pk_rec = w_rec[u, p]
            pk_birth = w_birth[u, p]
            pk_srcq = w_srcq[u, p]
            pk_vc = w_vc[u, p]                             # target lane
            rec_after = pk_rec.at[:, d_p].add(-s_p)
            done = jnp.abs(rec_after.astype(jnp.int32)).sum(-1) == 0
            will_deliver = has & done
            need = jnp.where(pk_srcq == p * V + pk_vc, 1, 2)
            if adaptive:
                need = jnp.where(pk_vc > 0, 1, need)
            freeq = jnp.take_along_axis(credit_work[:, p], pk_vc[:, None],
                                        axis=1)[:, 0]
            ok = has & ~done & (freeq >= need)
            moved = will_deliver | ok
            age_p = slot + 1 - pk_birth
            if weighted:
                age_p = age_p + (w_p - 1)
            meas_p = will_deliver & (pk_birth >= warmup)
            delivered += will_deliver.sum()
            lat_sum += jnp.where(meas_p, age_p, 0).sum()
            lat_cnt += meas_p.sum()
            vc_del = vc_del + (will_deliver[:, None]
                               & ((pk_srcq % V)[:, None] == varange)).sum(0)
            if ctx["hist_bins"] or ctx.get("lat_trace"):
                age_l.append(age_p)
                meas_l.append(meas_p)
                del_l.append(will_deliver)
            if scheduled:
                dead_crossings += (moved & ~link_ok[u, p]).sum()
            if link_use is not None:
                link_use = link_use.at[u, p].add(moved.astype(jnp.int32))
            # clear the winner slot at the sender; its lane regains a credit
            sel = widx[:, p]
            fd = new_dst.reshape(N, PVQ)
            fd = fd.at[u, sel[u]].set(jnp.where(moved, -1, fd[u, sel[u]]))
            new_dst = fd.reshape(N, P, V, Q)
            credit_work = credit_work.reshape(N, PV).at[u, pk_srcq].add(
                moved.astype(jnp.int32)).reshape(N, P, V)
            # write into receiver queue (w, p, lane), first free slot
            lane_dst = new_dst[r_, p, pk_vc]               # (N, Q)
            slot_idx = jnp.argmax(lane_dst < 0, axis=1)
            new_dst = new_dst.at[r_, p, pk_vc, slot_idx].set(
                jnp.where(ok, pk_dst, new_dst[r_, p, pk_vc, slot_idx]))
            new_rec = new_rec.at[r_, p, pk_vc, slot_idx].set(
                jnp.where(ok[:, None], rec_after,
                          new_rec[r_, p, pk_vc, slot_idx]))
            new_birth = new_birth.at[r_, p, pk_vc, slot_idx].set(
                jnp.where(ok, pk_birth, new_birth[r_, p, pk_vc, slot_idx]))
            credit_work = credit_work.at[r_, p, pk_vc].add(
                -ok.astype(jnp.int32))
            if weighted:
                new_busy = new_busy.at[u, p].set(
                    jnp.where(moved, w_p - 1, new_busy[u, p]))
                new_wait = new_wait.at[r_, p, pk_vc, slot_idx].set(
                    jnp.where(ok, w_p - 1,
                              new_wait[r_, p, pk_vc, slot_idx]))

        if weighted:
            # free slots carry no countdown (injection crosses no link)
            new_wait = jnp.where(new_dst >= 0, new_wait, 0)

        # ---- injection: credit-aware lane admission (bubble cost 2) ----
        m = ctx if masks is None else {**ctx, **masks}
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 2), 3)
        want_new = jax.random.uniform(k1, (N,)) < state["load"]
        if not trivial:
            want_new = want_new & m["inj_ok"]
        want = want_new | (state["backlog"] > 0)
        if ctx["fixed_dst"]:
            d = state["dst_table"]
        elif not trivial and ctx["has_dead_nodes"]:
            d = m["live_tbl"][jax.random.randint(k2, (N,), 0, m["n_live"])]
        else:
            d = jax.random.randint(k2, (N,), 0, N - 1)
            d = jnp.where(d >= jnp.arange(N), d + 1, d)
        di = _delta_idx(ctx["labels"], ctx["labels"][d], ctx["hermite"],
                        ctx["strides"])
        coin = jax.random.uniform(k3, (N,)) < 0.5
        r = jnp.where(coin[:, None], ctx["rec_a"][di], ctx["rec_b"][di])
        inj_port, inj_vc = credit_vc_select(r, lok, credit_work, policy,
                                            rot=slot, port_geom=port_geom,
                                            escape_fallback=esc_fb)
        ipc = jnp.minimum(inj_port, P - 1)
        freesel = jnp.take_along_axis(
            credit_work.reshape(N, PV), (ipc * V + inj_vc)[:, None],
            axis=1)[:, 0]
        can = (want & (freesel >= 2) & (jnp.abs(r).sum(-1) > 0)
               & (inj_port < P))
        if trivial:
            drop = None
        else:
            drop = want & ~m["dst_ok"][d]
            can = can & ~drop
        r = r.astype(new_rec.dtype)
        lane_dst = new_dst[r_, ipc, inj_vc]
        slot_idx = jnp.argmax(lane_dst < 0, axis=1)
        new_dst = new_dst.at[r_, ipc, inj_vc, slot_idx].set(
            jnp.where(can, d, new_dst[r_, ipc, inj_vc, slot_idx]))
        new_rec = new_rec.at[r_, ipc, inj_vc, slot_idx].set(
            jnp.where(can[:, None], r, new_rec[r_, ipc, inj_vc, slot_idx]))
        new_birth = new_birth.at[r_, ipc, inj_vc, slot_idx].set(
            jnp.where(can, slot, new_birth[r_, ipc, inj_vc, slot_idx]))
        credit_work = credit_work.reshape(N, PV).at[
            r_, ipc * V + inj_vc].add(-can.astype(jnp.int32)).reshape(
                N, P, V)
        backlog = state["backlog"] + want_new - can
        if drop is not None:
            backlog = backlog - drop
        backlog = jnp.clip(backlog, 0, 1 << 30)

        counted = slot >= warmup
        injm = can if drop is None else (can | drop)
        vc_inj = (injm[:, None] & (inj_vc[:, None] == varange)).sum(0)
        updates = dict(
            dst=new_dst, rec=new_rec, birth=new_birth, backlog=backlog,
            credit=credit_work,
            vc_delivered=state["vc_delivered"] + jnp.where(counted, vc_del,
                                                           0),
            vc_injected=state["vc_injected"] + jnp.where(counted, vc_inj,
                                                         0))
        if weighted:
            updates["busy"] = new_busy
            updates["wait"] = new_wait
        if ctx["hist_bins"]:
            updates["lat_hist"] = state["lat_hist"] + _bucket_counts(
                jnp.stack(age_l, 1), jnp.stack(meas_l, 1),
                ctx["hist_bins"])
        if link_use is not None:
            updates["link_use"] = link_use
        out = _finish_slot(state, warmup, delivered, lat_sum, lat_cnt, can,
                           drop, qdrop=qdrop, **updates)
        y = None
        if scheduled:
            y = dict(delivered=out["delivered"], injected=out["injected"],
                     dropped=out["dropped"],
                     in_flight=(new_dst >= 0).sum(),
                     dead_crossings=dead_crossings)
            if ctx["hist_bins"]:
                y["lat_hist"] = out["lat_hist"]
        elif ctx.get("lat_trace"):
            # the per-packet oracle, VC flavour: ages/flags per physical
            # in-port — same (slots, N, P) trace shape as the V=1 oracle
            y = dict(age=jnp.stack(age_l, 1), deliv=jnp.stack(del_l, 1),
                     meas=jnp.stack(meas_l, 1))
        return out, y

    return slot_step


def _scenario_mask_fields(scenario: Scenario, g: LatticeGraph, N: int,
                          dst_np, force_dead_nodes: bool = False,
                          link_spec=None) -> dict:
    """The scenario-DEPENDENT traced arrays of a mask-threaded context —
    factored out so a K-scenario sweep derives per-scenario masks without
    rebuilding the scenario-independent routing/label tables K times.
    `link_spec` extends the link_ok axis over express overlay ports
    (2n+2X), so express channels die and repair like any link."""
    link_ok = scenario.link_ok(g, link_spec)
    node_ok = scenario.node_ok(g)
    live = np.flatnonzero(node_ok).astype(np.int32)
    if live.size == 0:
        raise ValueError("scenario kills every node")
    # pad the live table to N entries so it has a scenario-independent
    # shape (a traced input must not change shape across patterns);
    # entries past n_live repeat live[0] and are never drawn
    live_pad = np.full(N, live[0], np.int32)
    live_pad[:live.size] = live
    return dict(
        link_ok=jnp.asarray(link_ok),
        inj_ok=jnp.asarray(node_ok),
        dst_ok=jnp.asarray(node_ok),
        has_dead_nodes=bool(scenario.dead_nodes) or force_dead_nodes,
        live_tbl=jnp.asarray(live_pad),
        n_live=int(live.size),
        # fixed-pattern packets aimed at a dead node are dropped at
        # injection (uniform traffic samples live nodes, never drops)
        dst_live_fixed=jnp.asarray(
            node_ok[dst_np] if dst_np is not None else np.ones(N, bool)))


def _schedule_mask_fields(compiled: CompiledSchedule, g: LatticeGraph,
                          N: int, dst_np, force_dead_nodes: bool = False,
                          pad_to: int | None = None,
                          link_spec=None) -> dict:
    """Per-EPOCH stacks of the scenario mask fields, plus the slot→epoch
    map — the traced time axis of a scheduled run.  `pad_to` repeats the
    final epoch so K schedules of differing epoch counts can share one
    compiled program (padded epochs are unreachable: the slot→epoch map
    never points at them)."""
    per = [_scenario_mask_fields(s, g, N, dst_np, force_dead_nodes,
                                 link_spec)
           for s in compiled.epochs]
    E = pad_to if pad_to is not None else len(per)
    if E < len(per):
        raise ValueError(
            f"pad_to={E} is smaller than the schedule's {len(per)} epochs")
    per = per + [per[-1]] * (E - len(per))
    out = {k: jnp.stack([m[k] for m in per])
           for k in ("link_ok", "inj_ok", "dst_ok", "live_tbl",
                     "dst_live_fixed")}
    out["n_live"] = jnp.asarray([m["n_live"] for m in per], jnp.int32)
    out["has_dead_nodes"] = (any(m["has_dead_nodes"] for m in per)
                             or force_dead_nodes)
    out["slot2epoch"] = jnp.asarray(compiled.slot2epoch, jnp.int32)
    return out


def _make_ctx(t: SimTables, g: LatticeGraph, pattern: str, seed: int,
              queue: int, scenario: Scenario | None = None,
              force_masks: bool = False, force_dead_nodes: bool = False,
              schedule: CompiledSchedule | None = None,
              pad_epochs: int | None = None, *, hist_bins: int = 0,
              lat_trace: bool = False, vcs: int = 1,
              credits: int | None = None, links: LinkSpec | None = None):
    """`force_masks=True` builds the mask-threaded (non-trivial) context
    even for the pristine scenario — used by `simulate_scenario_sweep`,
    where a pristine pattern may ride the traced-mask program alongside
    faulted ones (all-live masks reproduce the trivial results);
    `force_dead_nodes=True` additionally gives a dead-node-free pattern
    the dead-node program STRUCTURE (live-table destination sampling over
    all N nodes), so it can share a sweep with dead-node patterns.
    `schedule` (a `CompiledSchedule`) builds the TIME-INDEXED context:
    per-epoch mask stacks (padded to `pad_epochs` when sweeping K
    schedules of differing epoch counts) plus the slot→epoch map, all
    traced inputs of the batched/fused programs.  `hist_bins=B` turns on
    the in-carry latency histogram (age buckets 0..B−2 exact, B−1
    overflow); `lat_trace=True` makes the REFERENCE runner additionally
    emit per-slot delivery traces (the per-packet latency oracle —
    test-scale only, exclusive with `schedule`).  `links` (a `LinkSpec`)
    adds heterogeneous-link semantics: per-port slot weights (a weight-w
    channel is held for w slots), a pillar structural mask AND-ed into
    the link_ok masks, and express overlay ports extending P past 2n; a
    trivial/None spec compiles the identical pre-heterogeneous program."""
    scenario = scenario or Scenario()
    if lat_trace and schedule is not None:
        raise ValueError("lat_trace is exclusive with schedule=")
    if hist_bins < 0:
        raise ValueError(f"hist_bins must be >= 0, got {hist_bins}")
    policy = schedule.policy if schedule is not None else scenario.policy
    ls = links if links is not None and not links.is_trivial else None
    if ls is not None:
        ls.validate(t.n)
        # SimConfig raises these with friendlier context; the shared
        # validator keeps direct _make_ctx callers honest too
        validate_feature_combo(vcs=vcs, links_trivial=False,
                               express=bool(ls.express), policy=policy)
        # a pillar spec removes links: even a pristine Scenario must ride
        # the mask-threaded program so the structural mask is enforced
        force_masks = force_masks or ls.has_pillar
    trivial = (schedule is None and scenario.is_trivial
               and not force_masks)
    dst_np = pattern_table(g, pattern, seed)
    fixed_dst = dst_np is not None
    # records are tiny for every pod-sized lattice — int8 state quarters the
    # memory traffic of the biggest per-slot tensors (int32 kept as a
    # fallback for enormous single-dimension graphs; escape misrouting can
    # grow records past the minimal bound, so it gets the wide dtype —
    # at V=1 directly, and at V>1 via the VC0 escape-fallback misroute
    # that kicks in when DOR's escape port is dead)
    rec_max = max(int(np.abs(t.records_a).max(initial=0)),
                  int(np.abs(t.records_b).max(initial=0)))
    rec_dtype = (jnp.int32
                 if policy == "escape" or rec_max > 120
                 else jnp.int8)
    # per-delta-index injection tables: record (Remark-30 pair) + its first
    # DOR port, so traffic generation is two gathers instead of routing work
    rec_ab = np.stack([t.records_a, t.records_b], axis=1)  # (N, 2, n)
    nz = np.abs(rec_ab) > 0
    dim = np.argmax(nz, axis=-1)
    sgn = np.take_along_axis(rec_ab, dim[..., None], axis=-1)[..., 0]
    if ls is not None and ls.express:
        # greedy weighted-DOR first hop over the extended port set: among
        # ports of the record's first nonzero dimension whose sign matches
        # and whose span fits the remaining offset, take the largest span
        pdim_np = ls.port_dims(t.n)
        psgn_np = ls.port_signs(t.n)
        pspan_np = ls.port_spans(t.n)
        ok = ((pdim_np == dim[..., None]) & (psgn_np * sgn[..., None] > 0)
              & (pspan_np <= np.abs(sgn)[..., None]))
        port_ab = np.argmax(np.where(ok, pspan_np, -1), axis=-1)  # (N, 2)
    else:
        port_ab = 2 * dim + (sgn < 0)                      # (N, 2)
    if fixed_dst:
        g_strides = t.strides.astype(np.int64)
        lab = t.labels.astype(np.int64)
        delta = lab[dst_np] - lab
        # reduce into the Hermite box on host (exact integer arithmetic)
        from . import intmat
        di_fixed = (intmat.canonical_label(delta, t.hermite)
                    * g_strides).sum(axis=-1).astype(np.int32)
    else:
        di_fixed = np.zeros(t.N, np.int32)
    # the batched/fused cache key carries only the scenario STRUCTURE
    # (policy × dead-node-ness — plus the epoch count for schedules, a
    # shape): masks are traced state inputs, so every fault pattern of the
    # same structure reuses one compiled runner.  The reference oracle
    # keeps masks baked (full fingerprint key).
    if schedule is not None:
        fields = _schedule_mask_fields(
            schedule, g, t.N, dst_np if fixed_dst else None,
            force_dead_nodes, pad_to=pad_epochs, link_spec=ls)
        E = int(fields["link_ok"].shape[0])
        scen: dict = dict(trivial=False, scheduled=True, policy=policy,
                          scen_fp=schedule.fingerprint(g),
                          scen_structure=("schedule", policy,
                                          fields["has_dead_nodes"], E))
        scen.update(fields)
    else:
        hdn = bool(scenario.dead_nodes) or force_dead_nodes
        scen = dict(trivial=trivial, scheduled=False, policy=policy,
                    scen_fp=scenario.fingerprint(g),
                    scen_structure=(("trivial",) if trivial else
                                    ("traced", policy, hdn)))
        if not trivial:
            scen.update(_scenario_mask_fields(
                scenario, g, t.N, dst_np if fixed_dst else None,
                force_dead_nodes, ls))
    # heterogeneous-link context: per-port weights, pillar structural
    # mask (AND-ed into every link_ok, so the dead-channel audit covers
    # missing pillars), express-extended neighbour/port-geometry tables
    if ls is not None:
        nbr_np = ls.extended_neighbors(g)
        wgt_np = ls.port_weights(t.n)
        structural_np = ls.structural_mask(g)
        if structural_np is not None:
            scen["link_ok"] = scen["link_ok"] & jnp.asarray(structural_np)
        link = dict(
            link_fp=ls.fingerprint(),
            weighted=bool((wgt_np > 1).any()),
            express=bool(ls.express),
            wgt=jnp.asarray(wgt_np),
            structural=(None if structural_np is None
                        else jnp.asarray(structural_np)),
            pdim=jnp.asarray(ls.port_dims(t.n)),
            psgn=jnp.asarray(ls.port_signs(t.n)),
            pspan=jnp.asarray(ls.port_spans(t.n)),
            hop_tab=jnp.asarray(ls.hop_table(t.n)))
        P = ls.num_ports(t.n)
    else:
        nbr_np = t.neighbors
        link = dict(link_fp=None, weighted=False, express=False,
                    structural=None)
        P = 2 * t.n
    return dict(
        n=t.n, N=t.N, P=P, Q=queue, rec_dtype=rec_dtype,
        V=int(vcs), credit_init=int(queue if credits is None else credits),
        hist_bins=int(hist_bins), lat_trace=bool(lat_trace),
        **scen, **link,
        nbr=jnp.asarray(nbr_np),
        rec_a=jnp.asarray(t.records_a),
        rec_b=jnp.asarray(t.records_b),
        rec_ab=jnp.asarray(rec_ab.astype(np.int64), rec_dtype),
        port_ab=jnp.asarray(port_ab, jnp.int8),
        di_fixed=jnp.asarray(di_fixed),
        labels=jnp.asarray(t.labels),
        hermite=jnp.asarray(t.hermite),
        strides=jnp.asarray(t.strides),
        fixed_dst=fixed_dst,
        dst_table=jnp.asarray(
            dst_np if fixed_dst else np.zeros(t.N, np.int32)))


def _init_state(ctx, load: float, impl: str, slots: int = 1 << 14):
    n, N, P, Q = ctx["n"], ctx["N"], ctx["P"], ctx["Q"]
    V = ctx.get("V", 1)
    birth_dtype = jnp.int16 if slots < (1 << 15) - 1 else jnp.int32
    # the VC router (V > 1) widens every per-port queue to V lanes and
    # carries the (N, P, V) credit array + per-lane counters in the scan
    # state; V = 1 keeps the exact pre-VC layout (no credit, no lane axis)
    qshape = (N, P, V, Q) if V > 1 else (N, P, Q)
    state = dict(
        load=jnp.float32(load),
        dst_table=ctx["dst_table"],
        rec=jnp.zeros(qshape + (n,), dtype=ctx["rec_dtype"]),
        birth=jnp.full(qshape, -1, dtype=birth_dtype),
        backlog=jnp.zeros((N,), dtype=jnp.int32),
        slot=jnp.int32(0),
        delivered=jnp.int32(0),
        lat_sum=jnp.int32(0),
        lat_cnt=jnp.int32(0),
        injected=jnp.int32(0),
        dropped=jnp.int32(0))
    if V > 1:
        state["credit"] = jnp.full((N, P, V), ctx["credit_init"],
                                   jnp.int32)
        state["vc_delivered"] = jnp.zeros((V,), jnp.int32)
        state["vc_injected"] = jnp.zeros((V,), jnp.int32)
    if ctx.get("weighted"):
        # heterogeneous links: `busy` counts down the remaining slots a
        # weight-w channel stays held after a crossing; `wait` counts
        # down the slots before an in-queue packet becomes eligible (it
        # occupies buffer space — and in_flight — the whole time)
        state["busy"] = jnp.zeros((N, P), dtype=jnp.int32)
        state["wait"] = jnp.zeros(qshape, dtype=jnp.int32)
    if ctx["hist_bins"]:
        state["lat_hist"] = jnp.zeros((ctx["hist_bins"],), jnp.int32)
    if not ctx["trivial"]:
        state["link_use"] = jnp.zeros((N, P), dtype=jnp.int32)
    if impl in ("batched", "fused"):
        if V == 1:
            # birth < 0 marks free slots; each packet carries its next
            # DOR port (the VC router re-selects per slot instead — its
            # choice depends on the live credit counters)
            state["port"] = jnp.zeros((N, P, Q), dtype=jnp.int8)
        state["di_fixed"] = ctx["di_fixed"]
        if not ctx["trivial"]:
            # scenario masks are TRACED inputs: they ride in the state so
            # one compiled runner serves every fault pattern of the same
            # structure, and scenario sweeps can vmap over them.  Under a
            # schedule they carry a leading (E,) epoch axis, n_live is an
            # (E,) vector, and the slot→epoch map joins them.
            state["dst_live_fixed"] = ctx["dst_live_fixed"]
            state["link_ok"] = ctx["link_ok"]
            state["inj_ok"] = ctx["inj_ok"]
            if ctx.get("scheduled"):
                state["slot2epoch"] = ctx["slot2epoch"]
                if ctx["has_dead_nodes"]:
                    state["live_tbl"] = ctx["live_tbl"]
                    state["n_live"] = ctx["n_live"]
            elif ctx["has_dead_nodes"]:
                state["live_tbl"] = ctx["live_tbl"]
                state["n_live"] = jnp.int32(ctx["n_live"])
        del state["dst_table"]
    else:
        # the reference keeps the original dst-as-occupancy layout
        state["dst"] = jnp.full(qshape, -1, dtype=jnp.int32)
        state["birth"] = jnp.zeros(qshape, dtype=jnp.int32)
    return state


# scenario-dependent traced state inputs (vmapped by the scenario axis of
# `simulate_scenario_sweep` / the schedule axis of
# `simulate_schedule_sweep`, shared across the load/seed axes);
# slot2epoch only exists in scheduled states
_SCEN_STATE = ("link_ok", "inj_ok", "live_tbl", "n_live", "dst_live_fixed",
               "slot2epoch")
# state entries shared across the load AND seed sweep axes
_SHARED_STATE = ("dst_table", "di_fixed") + _SCEN_STATE

# traces per impl, incremented when a runner's Python body runs (i.e. at
# jit-trace time) — the recompile-count tests read this to prove that K
# fault patterns of one structure share a single trace/compile
TRACE_COUNTS: dict = {"batched": 0, "reference": 0, "fused": 0}

# host spans of the public entries (`sim.*`, docs/simulator.md "Profiling
# a run"): recorded only while a profiler trace is being taken
_span = jax.profiler.TraceAnnotation


def _get_runner(t: SimTables, ctx, *, slots: int, warmup: int, impl: str,
                n_loads: int, n_seeds: int = 1, n_scen: int = 1):
    """One compiled `lax.scan` per (topology, pattern kind, scenario
    STRUCTURE, run shape); sweeps vmap the same program over the load axis
    and, nested inside it, the seed axis — and `simulate_scenario_sweep`
    over an outermost scenario axis.  The batched/fused runners take
    per-run PRNG keys and pre-draw all traffic (`_make_traffic`); the
    reference runner splits its key into per-slot keys and draws inside
    the scan.  Scenario masks are traced state inputs for batched/fused
    (cache key = structure only: policy × dead-node-ness), and baked
    constants for the reference oracle (cache key = full fingerprint)."""
    scen_key = (ctx["scen_fp"] if impl == "reference"
                else ctx["scen_structure"])
    scheduled = ctx.get("scheduled", False)
    tracing = ctx["lat_trace"] and impl == "reference"
    V = ctx.get("V", 1)
    validate_feature_combo(
        impl=impl, vcs=V, links_trivial=ctx.get("link_fp") is None,
        express=ctx.get("express", False), policy=ctx["policy"])
    key = (t.neighbors.tobytes(), ctx["fixed_dst"], slots, warmup,
           ctx["Q"], impl, n_loads, n_seeds, n_scen, scen_key,
           ctx["hist_bins"], tracing, V, ctx.get("credit_init"),
           ctx.get("link_fp"))
    if key not in _RUNNER_CACHE:
        if impl == "reference":
            step = (_make_slot_step_vc_reference(ctx, warmup) if V > 1
                    else _make_slot_step_reference(ctx, warmup))

            def runner(st, key):
                TRACE_COUNTS[impl] += 1
                ks = jax.random.split(key, slots)
                final, ys = jax.lax.scan(step, st, ks)
                if scheduled:
                    return dict(final, timeline=ys)
                if tracing:
                    return dict(final, lat_trace=ys)
                return final
        else:
            step = (_make_slot_step_vc_batched(ctx, warmup) if V > 1
                    else _make_slot_step_batched(ctx, warmup)
                    if impl == "batched"
                    else _make_slot_step_fused(ctx, warmup))

            def runner(st, key):
                TRACE_COUNTS[impl] += 1
                with jax.named_scope("sim.predraw"):
                    tr = _make_traffic(ctx, st, key, slots)
                if scheduled:
                    # the slot→epoch map is scanned alongside the traffic
                    # so each step sees its epoch as a scalar
                    tr["epoch"] = st["slot2epoch"]
                final, ys = jax.lax.scan(step, st, tr)
                return dict(final, timeline=ys) if scheduled else final
        # dst_table / di_fixed / scenario masks are shared across both
        # sweep axes, so fixed-pattern traffic is derived once, not once
        # per run
        state_keys = list(_init_state(ctx, 0.0, impl))
        axes = {k: (None if k in _SHARED_STATE else 0) for k in state_keys}
        # the per-slot timeline ys only exist in scheduled outputs and are
        # always batched along the vmapped axes (ditto the oracle trace)
        out_ax = dict(axes, timeline=0) if scheduled else (
            dict(axes, lat_trace=0) if tracing else axes)
        if n_seeds > 1:
            # seed axis: same initial state, one key per seed
            runner = jax.vmap(runner, in_axes=(None, 0), out_axes=out_ax)
        if n_loads > 1:
            # load axis: per-load state (the offered load lives in it) and
            # per-load fold of the key (decorrelates sweep points)
            runner = jax.vmap(runner, in_axes=(axes, 0), out_axes=out_ax)
        if n_scen > 1:
            # outermost scenario axis: only the masks vary; the PRNG key
            # is shared (common random numbers — scenario differences in
            # the results are fault effects, not sampling noise)
            in_sc = {k: (0 if k in _SCEN_STATE else None)
                     for k in state_keys}
            out_sc = {k: (None if k in ("dst_table", "di_fixed") else 0)
                      for k in state_keys}
            if scheduled:
                out_sc = dict(out_sc, timeline=0)
            runner = jax.vmap(runner, in_axes=(in_sc, None), out_axes=out_sc)
        _RUNNER_CACHE[key] = jax.jit(runner)
    return _RUNNER_CACHE[key]


def _result(out, *, slots: int, warmup: int, N: int) -> SimResult:
    measured = slots - warmup
    delivered = int(out["delivered"])
    lat_cnt = int(out["lat_cnt"])
    # occupancy at run end: the reference keeps dst-as-occupancy, the
    # batched state marks free slots with birth < 0
    occ = out.get("dst", out.get("birth"))
    lu = out.get("link_use")
    tl = out.get("timeline")
    lh = out.get("lat_hist")
    vcd = out.get("vc_delivered")
    return SimResult(
        accepted_load=delivered / max(measured * N, 1),
        # mean over MEASURED deliveries (born at/after warmup); NaN — not
        # a fake 0.0 — when nothing qualified
        avg_latency_cycles=(PACKET_PHITS * float(out["lat_sum"]) / lat_cnt
                            if lat_cnt else float("nan")),
        delivered=delivered,
        injected=int(out["injected"]),
        slots=slots,
        dropped=int(out.get("dropped", 0)),
        in_flight=0 if occ is None else int((np.asarray(occ) >= 0).sum()),
        lat_count=lat_cnt,
        latency_hist=None if lh is None else np.asarray(lh),
        link_use=None if lu is None else np.asarray(lu),
        timeline=None if tl is None else SimTimeline(
            **{k: np.asarray(v) for k, v in tl.items()}),
        # per-lane telemetry only exists for vcs>1 runs; occupancy is
        # (N, P, V, Q) there, so the lane axis is axis 2
        vc_delivered=None if vcd is None else np.asarray(vcd),
        vc_injected=(None if vcd is None
                     else np.asarray(out["vc_injected"])),
        vc_in_flight=(None if vcd is None
                      else (np.asarray(occ) >= 0).sum(axis=(0, 1, 3))))


def _result_grid(out, axes_sizes: tuple, impl: str, *, slots: int,
                 warmup: int, N: int) -> np.ndarray:
    """Slice a (possibly vmapped) runner output into one `SimResult` per
    grid cell.  `axes_sizes` is the full leading batch shape (e.g.
    (L, S) or (K, L, S)); size-1 axes are absent from the raw output and
    re-inserted here.  Shared by `simulate_sweep` and
    `simulate_scenario_sweep` so the kept-counter set and axis
    normalization cannot drift between them."""
    occ_key = "dst" if impl == "reference" else "birth"
    keep = ("delivered", "lat_sum", "lat_cnt", "lat_hist", "injected",
            "dropped", "link_use", "vc_delivered", "vc_injected", occ_key)
    out_np = {k: np.asarray(v) for k, v in out.items() if k in keep}
    tl = out.get("timeline")
    tl_np = (None if tl is None
             else {k: np.asarray(v) for k, v in tl.items()})
    for i, size in enumerate(axes_sizes):
        if size == 1:
            out_np = {k: np.expand_dims(v, i) for k, v in out_np.items()}
            if tl_np is not None:
                tl_np = {k: np.expand_dims(v, i) for k, v in tl_np.items()}
    res = np.empty(axes_sizes, dtype=object)
    for idx in np.ndindex(*axes_sizes):
        cell = {k: v[idx] for k, v in out_np.items()}
        if tl_np is not None:
            cell["timeline"] = {k: v[idx] for k, v in tl_np.items()}
        res[idx] = _result(cell, slots=slots, warmup=warmup, N=N)
    return res


@dataclass(frozen=True)
class SweepStats:
    """Multi-seed sweep: `results[load][seed]` plus the mean ± CI reducers
    the Figs 5–8 error bars are drawn from."""
    loads: tuple[float, ...]
    seeds: tuple[int, ...]
    results: tuple[tuple[SimResult, ...], ...]

    def field(self, name: str) -> np.ndarray:
        """(L, S) array of one SimResult field."""
        return np.array([[getattr(r, name) for r in row]
                         for row in self.results], dtype=np.float64)

    def accepted(self) -> np.ndarray:
        return self.field("accepted_load")

    def accepted_mean(self) -> np.ndarray:
        return self.accepted().mean(axis=1)

    def accepted_ci(self, z: float = 1.96) -> np.ndarray:
        """Per-load CI half-width z·s/√k over the seed axis (0 for k=1)."""
        a = self.accepted()
        k = a.shape[1]
        if k < 2:
            return np.zeros(a.shape[0])
        return z * a.std(axis=1, ddof=1) / np.sqrt(k)

    def latency_mean(self) -> np.ndarray:
        """Per-load latency mean pooled over seeds, weighted by each
        seed's MEASURED delivery count (an unweighted per-seed mean
        over-represents starved seeds); seeds that measured nothing
        (NaN mean, zero weight) drop out, and a load point where no seed
        measured anything is NaN."""
        m = self.field("avg_latency_cycles")               # (L, S)
        w = self.field("lat_count")
        w = np.where(np.isnan(m), 0.0, w)
        tot = w.sum(axis=1)
        num = np.where(w > 0, m, 0.0) * w
        return np.where(tot > 0, num.sum(axis=1) / np.maximum(tot, 1.0),
                        np.nan)

    def latency_hist(self) -> np.ndarray:
        """(L, B) histogram pooled (summed) over the seed axis — the
        exact multi-seed distribution, not an average of averages."""
        rows = []
        for row in self.results:
            hs = [r.latency_hist for r in row]
            if any(h is None for h in hs):
                raise ValueError(
                    "sweep ran without hist_bins; pass hist_bins= to the "
                    "sweep call to collect latency histograms")
            rows.append(np.sum(hs, axis=0))
        return np.asarray(rows)

    def latency_percentile(self, q: float) -> np.ndarray:
        """(L,) exact q-th latency percentile (cycles) of the pooled
        per-load histogram; NaN where nothing was measured, +inf where
        the percentile falls in the overflow bucket."""
        return np.array([_hist_percentile(h, q)
                         for h in self.latency_hist()])

    def latency_p50(self) -> np.ndarray:
        return self.latency_percentile(0.50)

    def latency_p99(self) -> np.ndarray:
        return self.latency_percentile(0.99)

    def latency_p999(self) -> np.ndarray:
        return self.latency_percentile(0.999)


def _seed_list(seed: int, seeds) -> list[int] | None:
    if seeds is None:
        return None
    if isinstance(seeds, (int, np.integer)):
        return [seed + i for i in range(int(seeds))]
    return [int(s) for s in seeds]


def _sweep_plan(g: LatticeGraph, pattern: str, loads, *, slots, warmup,
                queue, seed, seed_list, tables, impl, scenario,
                scenarios=None, schedules=None, hist_bins=0, vcs=1,
                credits=None, links=None):
    """Build (runner, broadcast initial state, (L[, S]) key grid) for one
    sweep device program.  Key derivation: run (ℓ, s) of a multi-load
    sweep uses `fold_in(PRNGKey(seeds[s] + 17), ℓ)` — every load point
    gets its own fold (pre-PR-3 all points of a sweep shared one key and
    were perfectly correlated), and every seed its own base key.  A
    single-load sweep uses the unfolded base keys, so its seed-axis
    slices stay bitwise-equal to plain `simulate(..., seed=seeds[s])`.
    With `scenarios` (a list of K fault patterns) the state's traced mask
    entries are stacked on an outermost scenario axis and the runner is
    vmapped over it — K patterns, one trace, one compile.  The
    scenario-independent tables are built ONCE (only the mask fields are
    derived per scenario, via `_scenario_mask_fields`);
    `force_dead_nodes` gives every lane the dead-node program structure
    when any pattern in the sweep kills nodes.  `schedules` (a list of K
    schedules, each compiled here against `slots` unless it already is)
    is the transient analogue: per-schedule epoch stacks are padded to a
    common E and stacked on the same outermost axis — K timelines, one
    trace, one compile."""
    t = tables or build_tables(g, seed)
    ls = links if links is not None and not links.is_trivial else None
    if schedules is not None:
        schedules = [ensure_compiled(c, g, slots, links) for c in schedules]
        E = max(c.E for c in schedules)
        fdn = any(c.has_dead_nodes for c in schedules)
        ctx = _make_ctx(t, g, pattern, seed, queue, schedule=schedules[0],
                        pad_epochs=E, force_dead_nodes=fdn,
                        hist_bins=hist_bins, vcs=vcs, credits=credits,
                        links=links)
        dst_np = (np.asarray(ctx["dst_table"]) if ctx["fixed_dst"]
                  else None)
        sched_keys = ["link_ok", "inj_ok", "dst_live_fixed", "slot2epoch"]
        if ctx["has_dead_nodes"]:
            sched_keys += ["live_tbl", "n_live"]
        masks = [{k: ctx[k] for k in sched_keys}] + [
            _schedule_mask_fields(c, g, t.N, dst_np, fdn, pad_to=E,
                                  link_spec=ls)
            for c in schedules[1:]]
    elif scenarios is None:
        ctx = _make_ctx(t, g, pattern, seed, queue, scenario,
                        hist_bins=hist_bins, vcs=vcs, credits=credits,
                        links=links)
        masks = None
    else:
        fdn = any(s.dead_nodes for s in scenarios)
        ctx = _make_ctx(t, g, pattern, seed, queue, scenarios[0],
                        force_masks=True, force_dead_nodes=fdn,
                        hist_bins=hist_bins, vcs=vcs, credits=credits,
                        links=links)
        dst_np = (np.asarray(ctx["dst_table"]) if ctx["fixed_dst"]
                  else None)
        masks = [{k: ctx[k] for k in ("link_ok", "inj_ok", "live_tbl",
                                      "n_live", "dst_live_fixed")}] + [
            _scenario_mask_fields(s, g, t.N, dst_np, fdn, ls)
            for s in scenarios[1:]]
    if masks is not None and ctx.get("structural") is not None:
        # pillar structural mask: ctx lane 0 already has it AND-ed in
        # (_make_ctx); compose it into every other sweep lane's link_ok
        # (broadcasts over the (E, ...) epoch axis of schedule stacks)
        for m in masks[1:]:
            m["link_ok"] = m["link_ok"] & ctx["structural"]
    sl = seed_list if seed_list is not None else [seed]
    L, S = len(loads), len(sl)
    with _span("sim.runner"):
        runner = _get_runner(t, ctx, slots=slots, warmup=warmup, impl=impl,
                             n_loads=L, n_seeds=S,
                             n_scen=1 if masks is None else len(masks))
    state = _init_state(ctx, 0.0, impl, slots)
    if L > 1:
        state = {
            k: (v if k in _SHARED_STATE
                else jnp.broadcast_to(v, (L,) + v.shape))
            for k, v in state.items()}
    if masks is not None and len(masks) > 1:
        # stack the per-scenario traced masks on the scenario axis (a
        # K=1 sweep has no scenario vmap — ctx's masks are already in
        # the state)
        scheduled = ctx.get("scheduled", False)
        stack = ["link_ok", "inj_ok", "dst_live_fixed"]
        if scheduled:
            stack.append("slot2epoch")
        if ctx["has_dead_nodes"]:
            stack.append("live_tbl")
            if scheduled:
                stack.append("n_live")
        for k in stack:
            state[k] = jnp.stack([m[k] for m in masks])
        if ctx["has_dead_nodes"] and not scheduled:
            state["n_live"] = jnp.asarray([m["n_live"] for m in masks],
                                          jnp.int32)
    state = dict(state, load=jnp.asarray(loads, jnp.float32) if L > 1
                 else jnp.float32(loads[0]))
    def run_key(s, li):
        base = jax.random.PRNGKey(s + 17)
        return np.asarray(jax.random.fold_in(base, li) if L > 1 else base)

    with _span("sim.keys"):
        keys = np.stack([
            np.stack([run_key(s, li) for s in sl])
            for li in range(L)])                           # (L, S, 2)
        if S == 1:
            keys = keys[:, 0]
        if L == 1:
            keys = keys[0]
        keys = jnp.asarray(keys)
    return runner, state, keys, t, ctx


def _run(runner, state, keys):
    """Dispatch one device program and wait for its outputs (`sim.run`);
    the host conversion that follows would wait at the same point."""
    with _span("sim.run"):
        return jax.block_until_ready(runner(state, keys))


def _sweep_grid(entry: str, g: LatticeGraph, pattern: str, loads, cfg,
                seed_list, axes_sizes: tuple, **plan) -> np.ndarray:
    """Plan, run and fetch one sweep device program under the entry's
    host span: the shared body of the three sweep entries.  Returns the
    `_result_grid` over `axes_sizes`; `plan` carries the entry's own
    `_sweep_plan` arguments (scenario, scenarios or schedules)."""
    with _span(entry, nodes=g.order, slots=cfg.slots,
               lanes=int(np.prod(axes_sizes))):
        with _span("sim.plan"):
            runner, state, keys, t, _ = _sweep_plan(
                g, pattern, loads, slots=cfg.slots, warmup=cfg.warmup,
                queue=cfg.queue, seed=cfg.seed, seed_list=seed_list,
                tables=cfg.tables, impl=cfg.impl, hist_bins=cfg.hist_bins,
                vcs=cfg.vcs, credits=cfg.credits, links=cfg.links, **plan)
        out = _run(runner, state, keys)
        with _span("sim.fetch"):
            return _result_grid(out, axes_sizes, cfg.impl, slots=cfg.slots,
                                warmup=cfg.warmup, N=t.N)


def simulate(g: LatticeGraph, pattern: str, load: float, *,
             config: SimConfig | None = None,
             slots: int | None = None, warmup: int | None = None,
             queue: int | None = None, seed: int | None = None,
             tables: SimTables | None = None, impl: str | None = None,
             scenario: Scenario | None = None, fold: int | None = None,
             schedule: FaultSchedule | None = None,
             hist_bins: int | None = None, vcs: int | None = None,
             credits: int | None = None,
             links: LinkSpec | None = None) -> SimResult:
    """Run `slots` packet-slots (16 cycles each) at offered load `load`
    (phits/cycle/node) and measure accepted throughput + latency.

    Every run-shaping parameter can arrive EITHER as a `SimConfig` via
    `config=` or as the historical kwargs (a thin shim over
    `SimConfig.from_kwargs`; mixing both raises).  `fold` stays a
    per-call argument — it names *which* sweep point to reproduce, not
    how to run: `simulate_sweep(loads)[i]` equals
    `simulate(loads[i], fold=i)`.

    impl="batched" is the port-batched single-pass simulator;
    impl="reference" is the per-port-sweep oracle it is validated against.
    `scenario` injects faults / selects the routing policy (see
    `repro.core.scenario.Scenario`); None is the pristine DOR baseline and
    compiles to the exact pre-scenario program.  `schedule` (a
    `repro.core.fault_schedule.FaultSchedule`, exclusive with `scenario`)
    runs a TRANSIENT-fault timeline: per-epoch mask stacks ride the state
    as traced inputs, the result carries a per-slot `SimTimeline`, and a
    single-epoch schedule is bitwise-equal to the static scenario run.

    impl="fused" routes the slot update through the Pallas kernel
    (`repro.kernels.sim_step`): same state layout and pre-drawn traffic as
    the batched path, winner/acceptance/apply fused into one kernel pass
    — results are bitwise-equal to batched.  It runs in interpret mode on
    the CPU and raises NotImplementedError on a TPU backend, where Mosaic
    refuses its gathers.

    `hist_bins=B` additionally collects the (B,)-bucket latency histogram
    in the scan carry (`SimResult.latency_hist` /
    `latency_p50/p99/p999`); 0 (the default) compiles the exact
    histogram-free program.

    `vcs=V` (> 1) switches to the credit-flow VIRTUAL-CHANNEL router:
    (N, 2n, V, queue) lanes per port, downstream credit counters in the
    scan carry, lanes 1..V−1 credit-gated minimal-adaptive and lane 0
    the restricted-DOR escape lane (deadlock-free by CDG acyclicity —
    see docs/simulator.md).  `credits` caps the per-lane window (None =
    full queue depth).  vcs=1 (default) compiles the EXACT pre-VC
    program; vcs>1 requires impl in (batched | reference) and composes
    with scenario= AND schedule= (a degenerate single-epoch schedule
    stays bitwise-equal to the static scenario VC run).

    `links` (a `repro.core.LinkSpec`) turns on heterogeneous-link
    semantics — per-dimension slot weights, pillar Z-masks, express
    overlay channels (docs/simulator.md "Heterogeneous links"); a
    trivial/None spec compiles the identical pre-heterogeneous
    program."""
    cfg = SimConfig.from_kwargs(
        config, slots=slots, warmup=warmup, queue=queue, seed=seed,
        tables=tables, impl=impl, scenario=scenario, schedule=schedule,
        hist_bins=hist_bins, vcs=vcs, credits=credits, links=links)
    with _span("sim.simulate", nodes=g.order, slots=cfg.slots, lanes=1):
        with _span("sim.plan"):
            t = cfg.tables or build_tables(g, cfg.seed)
            if cfg.schedule is not None:
                ctx = _make_ctx(t, g, pattern, cfg.seed, cfg.queue,
                                schedule=ensure_compiled(cfg.schedule, g,
                                                         cfg.slots,
                                                         cfg.links),
                                hist_bins=cfg.hist_bins, vcs=cfg.vcs,
                                credits=cfg.credits, links=cfg.links)
            else:
                ctx = _make_ctx(t, g, pattern, cfg.seed, cfg.queue,
                                cfg.scenario, hist_bins=cfg.hist_bins,
                                vcs=cfg.vcs, credits=cfg.credits,
                                links=cfg.links)
            with _span("sim.runner"):
                runner = _get_runner(t, ctx, slots=cfg.slots,
                                     warmup=cfg.warmup, impl=cfg.impl,
                                     n_loads=1)
            with _span("sim.keys"):
                key = jax.random.PRNGKey(cfg.seed + 17)
                if fold is not None:
                    key = jax.random.fold_in(key, fold)
            state = _init_state(ctx, load, cfg.impl, cfg.slots)
        out = _run(runner, state, key)
        with _span("sim.fetch"):
            return _result(out, slots=cfg.slots, warmup=cfg.warmup, N=t.N)


def simulate_sweep(g: LatticeGraph, pattern: str, loads, *,
                   config: SimConfig | None = None,
                   slots: int | None = None, warmup: int | None = None,
                   queue: int | None = None, seed: int | None = None,
                   seeds=None, tables: SimTables | None = None,
                   impl: str | None = None,
                   scenario: Scenario | None = None,
                   schedule: FaultSchedule | None = None,
                   hist_bins: int | None = None, vcs: int | None = None,
                   credits: int | None = None,
                   links: LinkSpec | None = None):
    """An entire offered-load curve (Figs. 5–8) as ONE device program: the
    per-slot update is vmapped over the load axis and — when `seeds` is
    given — over a nested seed axis, so the whole sweep JITs once and runs
    without host round-trips between runs.  Run-shaping parameters come
    from `config=` (a `SimConfig`) or the legacy kwargs (not both —
    `SimConfig.from_kwargs` raises on conflicts); `seeds` stays a
    per-call argument (it names the replication axis, not the router).

    seeds=None returns list[SimResult] (one per load; run ℓ uses
    `fold_in(PRNGKey(seed+17), ℓ)`, so distinct sweep points are
    decorrelated).  seeds=k (int) uses base seeds [seed, …, seed+k−1],
    seeds=[…] uses them verbatim; both return a `SweepStats` whose
    seed-axis slice s is bitwise-identical to the single-seed sweep with
    seed=seeds[s].  A single-load, single-seed sweep delegates to
    `simulate` (same key, pre-PR-3 compatible)."""
    cfg = SimConfig.from_kwargs(
        config, slots=slots, warmup=warmup, queue=queue, seed=seed,
        tables=tables, impl=impl, scenario=scenario, schedule=schedule,
        hist_bins=hist_bins, vcs=vcs, credits=credits, links=links)
    loads = [float(l) for l in np.asarray(loads).ravel()]
    sl = _seed_list(cfg.seed, seeds)
    if sl is None and len(loads) == 1:
        return [simulate(g, pattern, loads[0], config=cfg)]
    L, S = len(loads), len(sl or [cfg.seed])
    res = _sweep_grid(
        "sim.sweep", g, pattern, loads, cfg, sl, (L, S),
        scenario=cfg.scenario,
        schedules=None if cfg.schedule is None else [cfg.schedule])
    if sl is None:
        return [res[li, 0] for li in range(L)]
    return SweepStats(loads=tuple(loads), seeds=tuple(sl),
                      results=tuple(tuple(row) for row in res))


def simulate_scenario_sweep(g: LatticeGraph, pattern: str, scenarios,
                            loads=(0.6,), *,
                            config: SimConfig | None = None,
                            slots: int | None = None,
                            warmup: int | None = None,
                            queue: int | None = None,
                            seed: int | None = None, seeds=None,
                            tables: SimTables | None = None,
                            impl: str | None = None,
                            hist_bins: int | None = None,
                            vcs: int | None = None,
                            credits: int | None = None,
                            links: LinkSpec | None = None):
    """K fault patterns × (loads × seeds) as ONE device program: the
    scenario masks are traced state inputs, so the compiled slot update is
    vmapped over an outermost scenario axis — K patterns cost one trace
    and one compile (pre-PR-4 each pattern was baked into its own
    program and re-compiled).

    All *faulted* scenarios must share the routing policy and
    dead-node-ness (both shape the compiled program); `None`/pristine
    entries mean the fault-free baseline — they adopt the sweep's policy
    (with all channels live every policy routes the DOR minimal port, so
    the baseline lane is policy-independent) and, in a dead-node sweep,
    the dead-node program structure (live-table sampling over all N
    nodes), riding the same traced-mask program with all-live masks.  The PRNG key grid is
    shared across scenarios (common random numbers: result differences
    between patterns are fault effects, not sampling noise), so scenario
    k's results are bitwise-equal to the single-scenario sweep with the
    same loads/seeds.

    Returns a list of length K mirroring `simulate_sweep`'s return for
    each scenario: list[SimResult] per load when `seeds is None`, else a
    `SweepStats`."""
    cfg = SimConfig.from_kwargs(
        config, slots=slots, warmup=warmup, queue=queue, seed=seed,
        tables=tables, impl=impl, hist_bins=hist_bins, vcs=vcs,
        credits=credits, links=links)
    if cfg.scenario is not None or cfg.schedule is not None:
        raise ValueError(
            "simulate_scenario_sweep takes its fault patterns from the "
            "`scenarios` list; leave config.scenario/config.schedule unset")
    scenarios = [s if s is not None else Scenario() for s in scenarios]
    if not scenarios:
        raise ValueError("simulate_scenario_sweep needs >= 1 scenario")
    if cfg.impl not in ("batched", "fused"):
        raise ValueError(
            "simulate_scenario_sweep needs a traced-mask implementation "
            f"(batched | fused), got {cfg.impl!r}")
    policies = sorted({s.policy for s in scenarios if not s.is_trivial})
    if len(policies) > 1:
        raise ValueError(
            f"scenario sweep mixes routing policies {policies}; the policy "
            "shapes the compiled program — sweep each policy separately")
    if policies and policies[0] != "dor":
        # pristine lanes adopt the sweep policy (equivalent routing on an
        # all-live graph) so [None, faulted-adaptive, ...] just works
        scenarios = [s.with_policy(policies[0]) if s.is_trivial else s
                     for s in scenarios]
    faulted = [s for s in scenarios if s.dead_links or s.dead_nodes]
    if len({bool(s.dead_nodes) for s in faulted}) > 1:
        raise ValueError(
            "scenario sweep mixes dead-node and link-only fault patterns; "
            "destination sampling differs structurally — sweep separately")
    loads = [float(l) for l in np.asarray(loads).ravel()]
    sl = _seed_list(cfg.seed, seeds)
    K, L, S = len(scenarios), len(loads), len(sl or [cfg.seed])
    res = _sweep_grid("sim.scenario_sweep", g, pattern, loads, cfg, sl,
                      (K, L, S), scenario=None, scenarios=scenarios)
    results = []
    for ki in range(K):
        if sl is None:
            results.append([res[ki, li, 0] for li in range(L)])
        else:
            results.append(SweepStats(
                loads=tuple(loads), seeds=tuple(sl),
                results=tuple(tuple(row) for row in res[ki])))
    return results


def simulate_schedule_sweep(g: LatticeGraph, pattern: str, schedules,
                            loads=(0.6,), *,
                            config: SimConfig | None = None,
                            slots: int | None = None,
                            warmup: int | None = None,
                            queue: int | None = None,
                            seed: int | None = None, seeds=None,
                            tables: SimTables | None = None,
                            impl: str | None = None,
                            hist_bins: int | None = None,
                            vcs: int | None = None,
                            credits: int | None = None,
                            links: LinkSpec | None = None):
    """K transient-fault TIMELINES × (loads × seeds) as ONE device
    program — `simulate_scenario_sweep` generalized along the time axis.
    Each schedule compiles to per-epoch mask stacks + a slot→epoch map;
    stacks are padded to the sweep-wide maximum epoch count (padded
    epochs are unreachable) so all K lanes share one trace and one
    compile, and the slot→epoch maps ride the outermost vmap axis as
    traced inputs.

    Entries may be `FaultSchedule`s, static `Scenario`s (wrapped as
    degenerate single-epoch schedules) or `None` (the pristine baseline
    lane).  All lanes must share the routing policy (pristine/static-DOR
    lanes adopt the sweep's policy, which routes identically on an
    all-live graph); dead-node-ness is unified structurally — any lane
    with a node death anywhere in its timeline switches the whole sweep
    to live-table destination sampling.

    The PRNG key grid is shared across lanes (common random numbers), so
    lane k is bitwise-equal to the single-schedule sweep with the same
    loads/seeds, and a lane whose schedule is a degenerate single-epoch
    timeline is bitwise-equal to the STATIC `Scenario` run.  Returns a
    list of length K mirroring `simulate_sweep`'s return; every
    `SimResult` carries its per-slot `SimTimeline`."""
    cfg = SimConfig.from_kwargs(
        config, slots=slots, warmup=warmup, queue=queue, seed=seed,
        tables=tables, impl=impl, hist_bins=hist_bins, vcs=vcs,
        credits=credits, links=links)
    if cfg.scenario is not None or cfg.schedule is not None:
        raise ValueError(
            "simulate_schedule_sweep takes its timelines from the "
            "`schedules` list; leave config.scenario/config.schedule unset")
    schedules = [s if isinstance(s, FaultSchedule)
                 else FaultSchedule.from_scenario(s) for s in schedules]
    if not schedules:
        raise ValueError("simulate_schedule_sweep needs >= 1 schedule")
    if cfg.impl not in ("batched", "fused"):
        raise ValueError(
            "simulate_schedule_sweep needs a traced-mask implementation "
            f"(batched | fused), got {cfg.impl!r}")
    policies = sorted({s.policy for s in schedules
                       if not (s.is_static and s.base.is_trivial)})
    if len(policies) > 1:
        raise ValueError(
            f"schedule sweep mixes routing policies {policies}; the policy "
            "shapes the compiled program — sweep each policy separately")
    if policies and policies[0] != "dor":
        schedules = [s.with_policy(policies[0])
                     if s.is_static and s.base.is_trivial else s
                     for s in schedules]
    loads = [float(l) for l in np.asarray(loads).ravel()]
    sl = _seed_list(cfg.seed, seeds)
    K, L, S = len(schedules), len(loads), len(sl or [cfg.seed])
    res = _sweep_grid("sim.schedule_sweep", g, pattern, loads, cfg, sl,
                      (K, L, S), scenario=None, schedules=schedules)
    results = []
    for ki in range(K):
        if sl is None:
            results.append([res[ki, li, 0] for li in range(L)])
        else:
            results.append(SweepStats(
                loads=tuple(loads), seeds=tuple(sl),
                results=tuple(tuple(row) for row in res[ki])))
    return results


def simulate_load_sweep(g: LatticeGraph, pattern: str, loads, **kw):
    """DEPRECATED pre-PR-3 alias of `simulate_sweep` — identical
    signature and return; new code should call `simulate_sweep` (or pass
    a `SimConfig` via `config=`) directly."""
    warnings.warn(
        "simulate_load_sweep is deprecated; call simulate_sweep (same "
        "arguments) or pass a SimConfig via config=",
        DeprecationWarning, stacklevel=2)
    return simulate_sweep(g, pattern, loads, **kw)


# backwards-compatible name (pre-sweep API); deprecated like the alias
throughput_curve = simulate_load_sweep


def peak_throughput(g: LatticeGraph, pattern: str, loads=None, **kw):
    """Max accepted load over an offered-load sweep (the paper's
    'throughput peak')."""
    loads = loads if loads is not None else np.linspace(0.1, 1.0, 10)
    res = simulate_sweep(g, pattern, loads, **kw)
    best = max(res, key=lambda r: r.accepted_load)
    return best, res


def reference_latency_samples(g: LatticeGraph, pattern: str, load: float,
                              *, slots: int = 512, warmup: int = 128,
                              queue: int = 4, seed: int = 0,
                              tables: SimTables | None = None,
                              scenario: Scenario | None = None,
                              hist_bins: int = 0, vcs: int = 1,
                              credits: int | None = None,
                              links: LinkSpec | None = None):
    """The per-packet latency ORACLE: one reference-impl run that, on top
    of the usual counters (and histogram, when `hist_bins` is given),
    records every delivery's exact age in slots.  Returns
    ``(SimResult, samples)`` where ``samples`` holds two sorted int
    arrays of per-packet ages:

      * ``measured`` — deliveries of packets born at/after warmup (the
        population `lat_sum`/`lat_cnt`/`latency_hist` count), and
      * ``window``  — deliveries at slots ≥ warmup regardless of birth
        (the pre-fix biased population, kept so the warmup-bias
        regression test can demonstrate the difference).

    The run uses the same PRNG key derivation as `simulate(...,
    impl="reference")`, so the samples describe exactly that run —
    percentile accessors are validated cycle-exactly against them.
    Test-scale only: the trace is a (slots, N, 2n) device→host transfer.
    """
    t = tables or build_tables(g, seed)
    ctx = _make_ctx(t, g, pattern, seed, queue, scenario,
                    hist_bins=hist_bins, lat_trace=True, vcs=vcs,
                    credits=credits, links=links)
    runner = _get_runner(t, ctx, slots=slots, warmup=warmup,
                         impl="reference", n_loads=1)
    out = dict(runner(_init_state(ctx, load, "reference", slots),
                      jax.random.PRNGKey(seed + 17)))
    tr = out.pop("lat_trace")
    res = _result(out, slots=slots, warmup=warmup, N=t.N)
    age = np.asarray(tr["age"])                        # (slots, N, P)
    deliv = np.asarray(tr["deliv"]).astype(bool)
    # `meas` is the counted flag from the slot step itself (birth >= warmup
    # at delivery).  It can't be reconstructed host-side as slot+1−age:
    # weighted links fold their +w−1 crossing cost into the age, which
    # would shift reconstructed births across the warmup boundary.
    meas = np.asarray(tr["meas"]).astype(bool)
    slot_idx = np.arange(slots)[:, None, None]
    samples = dict(
        measured=np.sort(age[meas]),
        window=np.sort(age[deliv & (slot_idx >= warmup)]))
    return res, samples


def schedule_recovery_slots(result: SimResult, schedule: FaultSchedule,
                            *, q: float = 0.99, window: int = 64,
                            slack_cycles: float = 0.0) -> int | None:
    """Recovery time of a transient-fault run: slots from the schedule's
    LAST repair event until the windowed q-th latency percentile returns
    to its pre-fault baseline (see `SimTimeline.recovery_slots`).  The
    fault onset is the schedule's first ``*_down`` event, the repair its
    last ``*_up`` event; `result` must come from a `schedule=` run with
    `hist_bins` enabled.  Returns None when the tail never recovers
    inside the run."""
    downs = [s for s, kind, _ in schedule.events if kind.endswith("_down")]
    ups = [s for s, kind, _ in schedule.events if kind.endswith("_up")]
    if not downs or not ups:
        raise ValueError(
            "schedule needs at least one *_down and one *_up event to "
            f"define a fault/repair pair, got events={schedule.events!r}")
    if result.timeline is None:
        raise ValueError("result has no timeline — run with schedule=")
    return result.timeline.recovery_slots(
        min(downs), max(ups), q=q, window=window,
        slack_cycles=slack_cycles)
