"""Distance properties of cubic crystal graphs — closed forms of Table 1 and
BFS-based measurement utilities.

Average-distance convention (matches Table 1): k̄ = Σ_v d(0, v) / (N − 1).

Degraded/weighted summaries route through ONE facade,
`distance_stats(g, condition=...)` — a `repro.core.NetworkCondition`
names the fabric state (static scenario, fault timeline, heterogeneous
links) and the facade dispatches to the matching engine.  The historical
per-combination names (`faulted_average_distance`, `weighted_diameter`,
`faulted_schedule_stats`, ...) remain as `DeprecationWarning` shims.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass

import numpy as np

from .condition import NetworkCondition
from .lattice import InfeasibleNetwork, LatticeGraph


def _warn_deprecated(old: str, new: str) -> None:
    """One shared DeprecationWarning voice for the analytic shims (see
    docs/simulator.md, 'Unified analytic surface')."""
    warnings.warn(
        f"{old} is deprecated; use {new} (docs/simulator.md, "
        f"'Unified analytic surface')",
        DeprecationWarning, stacklevel=3)


# ---------------------------------------------------------------------------
# Table 1 closed forms
# ---------------------------------------------------------------------------

def pc_diameter(a: int) -> int:
    return 3 * (a // 2)


def fcc_diameter(a: int) -> int:
    return (3 * a) // 2


def bcc_diameter(a: int) -> int:
    return (3 * a) // 2


def mixed_torus_diameter(*sides: int) -> int:
    return sum(s // 2 for s in sides)


def pc_average_distance(a: int) -> float:
    if a % 2 == 0:
        return 3 * a**4 / (4 * (a**3 - 1))
    return (3 * a**4 - 3 * a**2) / (4 * (a**3 - 1))


def fcc_average_distance(a: int) -> float:
    if a % 2 == 0:
        return (7 * a**4 - 2 * a**2) / (4 * (2 * a**3 - 1))
    return (7 * a**4 - 2 * a**2 - 1) / (4 * (2 * a**3 - 1))


def bcc_average_distance(a: int, as_printed: bool = False) -> float:
    """BCC(a) average distance.

    The paper's odd-a numerator reads `35a⁴ − 14a² + 30`; exhaustive BFS at
    a ∈ {3, 5, 7} shows the constant is a typo for `+3` (measured 8·Σd equals
    35a⁴ − 14a² + 3 exactly).  Pass as_printed=True for the printed form."""
    if a % 2 == 0:
        return (35 * a**4 - 8 * a**2) / (8 * (4 * a**3 - 1))
    c = 30 if as_printed else 3
    return (35 * a**4 - 14 * a**2 + c) / (8 * (4 * a**3 - 1))


def torus_average_distance(*sides: int) -> float:
    """Exact k̄ of a mixed-radix torus: sum of per-dimension ring averages.

    Ring of size s has Σ d = s²/4 (even) or (s²−1)/4 (odd) over all nodes;
    per-dimension averages add because distance is separable."""
    N = int(np.prod(sides))
    total = 0
    for s in sides:
        ring_sum = s * s // 4 if s % 2 == 0 else (s * s - 1) // 4
        total += ring_sum * (N // s)  # each ring value appears N/s times
    return total / (N - 1)


# ---------------------------------------------------------------------------
# measured summaries
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# routed distance profiles (minimal-routing engine instead of BFS)
# ---------------------------------------------------------------------------

def routed_distance_profile(g: LatticeGraph, backend: str = "auto",
                            router=None) -> np.ndarray:
    """hist[k] = #nodes at distance k from any fixed node, computed from the
    norms of minimal routing records (Theorem 29: |r|₁ = d_G(0, v)) instead
    of BFS.  One batched engine call over all N labels — the fast path for
    sweeping large graph families.  Pass a prebuilt `router` (from
    `make_router`) to amortize engine construction across calls."""
    from .routing import make_router, norm1
    if router is None:
        router = make_router(g.matrix, backend)
    return np.bincount(norm1(np.asarray(router(g.labels))))


def routed_diameter(g: LatticeGraph, backend: str = "auto",
                    profile: np.ndarray | None = None) -> int:
    hist = routed_distance_profile(g, backend) if profile is None else profile
    return int(len(hist) - 1)


def routed_average_distance(g: LatticeGraph, backend: str = "auto",
                            profile: np.ndarray | None = None) -> float:
    """k̄ = Σ_v d(0, v) / (N − 1) from routed records (Table 1 convention).
    Pass `profile` (from `routed_distance_profile`) to reuse one all-pairs
    pass for several summary statistics."""
    hist = routed_distance_profile(g, backend) if profile is None else profile
    ks = np.arange(len(hist))
    return float((hist * ks).sum()) / (g.order - 1)


# ---------------------------------------------------------------------------
# degraded-graph (scenario) distance profiles: fault-aware table rebuild
# ---------------------------------------------------------------------------

def faulted_distance_matrix(g: LatticeGraph, scenario,
                            backend: str = "auto") -> np.ndarray:
    """(N, N) live-path distances of the degraded graph (BFS rebuild via
    `routing.fault_aware_next_hop`; −1 = unreachable or dead endpoint).
    Faults break vertex transitivity, so unlike the pristine case a single
    origin profile is not enough — the whole matrix is rebuilt.

    backend: "device" uses the compiled multi-source min-plus BFS
    (`routing.fault_aware_next_hop_device` — same tables, scales past pod
    sizes), "host" the per-destination numpy BFS loop, "auto" the device
    path when JAX is importable."""
    from .routing import fault_aware_next_hop, fault_aware_next_hop_device
    link_ok, node_ok = scenario.link_ok(g), scenario.node_ok(g)
    if backend not in ("auto", "device", "host"):
        raise ValueError(f"unknown BFS backend {backend!r}")
    if backend != "host":
        try:
            return fault_aware_next_hop_device(g, link_ok, node_ok)[0]
        except ImportError:
            if backend == "device":
                raise
    return fault_aware_next_hop(g, link_ok, node_ok)[0]


def faulted_distance_sweep(g: LatticeGraph, scenarios) -> dict:
    """Degraded-distance statistics for K fault patterns as ONE compiled
    device program: the min-plus BFS relaxation runs under `lax.map` over
    the stacked liveness masks (sequential over scenarios, so the (N, N)
    distance front is resident once, not K times) and only the per-
    scenario reductions come back to host.

    Returns {"average_distance": (K,), "diameter": (K,),
    "reachable_pairs": (K,)} over ordered live reachable pairs (the
    `faulted_average_distance` / `faulted_diameter` conventions, with
    one batched-sweep deviation: a lane with ZERO reachable pairs —
    a totally disconnected fault pattern — reports
    average_distance=NaN / diameter=0 / reachable_pairs=0 instead of
    raising like `faulted_average_distance`, so one broken lane cannot
    kill the other K−1; check `reachable_pairs` or NaN before ranking).  This is
    the degraded-topology sweep the host N×BFS loop cannot sustain: at
    N=4096 one host rebuild is minutes of Python, while the whole K-
    scenario sweep here is one device program (`make bench` row
    `scenarios/bfs_sweep*`)."""
    import jax
    import jax.numpy as jnp

    from .routing import _get_fault_bfs            # shared relaxation

    scenarios = list(scenarios)
    N, P = g.order, 2 * g.n
    nbr = g.neighbor_indices.astype(np.int32)
    link = np.stack([s.link_ok(g) for s in scenarios])
    node = np.stack([s.node_ok(g) for s in scenarios])
    eff = link & node[:, :, None] & node[:, nbr]
    relax = _get_fault_bfs(N, P, with_next_hop=False)
    nbr_j = jnp.asarray(nbr)

    def stats(masks):
        eff_ok, link_ok, node_ok = masks
        dist = relax(nbr_j, eff_ok, link_ok, node_ok)
        reach = dist > 0
        pairs = reach.sum()
        d = jnp.where(reach, dist, 0)
        # float32 row-sum accumulation: exact for any realistic diameter
        # (row sums < 2^24), and the final mean is a float anyway
        total = d.sum(axis=0, dtype=jnp.float32).sum(dtype=jnp.float32)
        avg = jnp.where(pairs > 0, total / jnp.maximum(pairs, 1),
                        jnp.float32(jnp.nan))   # disconnected lane → NaN
        return (avg, d.max(), pairs)

    avg, diam, pairs = jax.lax.map(
        stats, (jnp.asarray(eff), jnp.asarray(link), jnp.asarray(node)))
    return {"average_distance": np.asarray(avg, np.float64),
            "diameter": np.asarray(diam, np.int64),
            "reachable_pairs": np.asarray(pairs, np.int64)}


def _faulted_schedule_stats(g: LatticeGraph, schedule, slots: int = 512
                            ) -> dict:
    """Per-EPOCH degraded-distance curves of a transient-fault timeline
    (`repro.core.fault_schedule.FaultSchedule`, or an already-compiled
    `CompiledSchedule`): the schedule's epochs are static scenarios, so
    the whole timeline reuses `faulted_distance_sweep`'s one-compile
    device BFS — K epochs of (N, N) relaxation in one program.

    Returns `faulted_distance_sweep`'s dict plus `epoch_start_slot`
    ((E,) — epoch e covers slots [start[e], start[e+1]))."""
    from .fault_schedule import ensure_compiled
    compiled = ensure_compiled(schedule, g, slots)
    out = faulted_distance_sweep(g, compiled.epochs)
    out["epoch_start_slot"] = np.asarray(compiled.starts, np.int64)
    return out


def faulted_distance_profile(g: LatticeGraph, scenario,
                             dist: np.ndarray | None = None) -> np.ndarray:
    """hist[k] = #ordered live reachable pairs at distance k ≥ 1 in the
    degraded graph (cf. `routed_distance_profile`, which counts from one
    origin of the vertex-transitive pristine graph)."""
    if dist is None:
        dist = faulted_distance_matrix(g, scenario)
    d = dist[dist > 0]
    return np.bincount(d) if d.size else np.zeros(1, dtype=np.int64)


def _faulted_average_distance(g: LatticeGraph, scenario,
                              dist: np.ndarray | None = None) -> float:
    """Mean distance over ordered live reachable pairs of the degraded
    graph — the k̄ entering the Δ/k̄-style saturation intuition once links
    or nodes die."""
    if dist is None:
        dist = faulted_distance_matrix(g, scenario)
    d = dist[dist > 0]
    if d.size == 0:
        raise InfeasibleNetwork("no reachable pairs under this scenario")
    return float(d.mean())


def _faulted_diameter(g: LatticeGraph, scenario,
                      dist: np.ndarray | None = None) -> int:
    """Max live-pair distance of the degraded graph."""
    if dist is None:
        dist = faulted_distance_matrix(g, scenario)
    return int(dist.max())


# -- heterogeneous-link (LinkSpec) metrics ----------------------------------

def weighted_distance_matrix(g: LatticeGraph, link_spec,
                             scenario=None) -> np.ndarray:
    """(N, N) weighted shortest-path COSTS (slots) of a heterogeneous
    fabric: per-dimension/express slot costs and the pillar mask of a
    `core.link_spec.LinkSpec`, optionally composed with a fault
    `Scenario`.  Runs the per-port-cost min-plus relaxation of
    `routing.fault_aware_next_hop_device` over the extended (base +
    express) port axis; −1 marks unreachable pairs (possible once
    pillars or faults cut the graph).  A trivial spec reproduces
    `faulted_distance_matrix` / the hop-count matrix exactly."""
    from .routing import fault_aware_next_hop_device
    if scenario is not None:
        link_ok, node_ok = scenario.link_ok(g), scenario.node_ok(g)
    else:
        link_ok = np.ones((g.order, 2 * g.n), dtype=bool)
        node_ok = None
    return fault_aware_next_hop_device(
        g, link_ok, node_ok, link_spec=link_spec)[0]


def _weighted_average_distance(g: LatticeGraph, link_spec,
                               dist: np.ndarray | None = None) -> float:
    """Mean weighted cost over ordered reachable pairs — the k̄ entering
    the Δ/k̄ saturation intuition once slot costs are non-uniform."""
    if dist is None:
        dist = weighted_distance_matrix(g, link_spec)
    d = dist[dist > 0]
    if d.size == 0:
        raise InfeasibleNetwork("no reachable pairs under this LinkSpec")
    return float(d.mean())


def _weighted_diameter(g: LatticeGraph, link_spec,
                       dist: np.ndarray | None = None) -> int:
    """Max weighted pair cost (slots) of the heterogeneous fabric."""
    if dist is None:
        dist = weighted_distance_matrix(g, link_spec)
    return int(dist.max())


# ---------------------------------------------------------------------------
# unified analytic surface: distance_stats facade + deprecation shims
# ---------------------------------------------------------------------------

def _matrix_stats(dist: np.ndarray) -> dict:
    """Reduce one (N, N) distance/cost matrix (−1 = unreachable) to the
    facade's summary dict, keeping the shim conventions exactly."""
    d = dist[dist > 0]
    if d.size == 0:
        raise InfeasibleNetwork("no reachable pairs under this condition")
    return {"average_distance": float(d.mean()),
            "diameter": int(dist.max()),
            "reachable_pairs": int(d.size)}


def distance_stats(g: LatticeGraph,
                   condition: NetworkCondition | None = None,
                   **kwargs) -> dict:
    """Distance summary of `g` under one `repro.core.NetworkCondition` —
    THE entry point for degraded/weighted distance metrics (the shimmed
    `faulted_*`/`weighted_*` names all dispatch through here).

    Returns {"average_distance", "diameter", "reachable_pairs"}:

      * pristine condition — the closed BFS values (`g.average_distance`,
        `g.diameter`) over all N·(N−1) ordered pairs;
      * static `scenario` — live-pair statistics of the degraded graph
        (fault-aware BFS rebuild, `condition.backend` selects the
        engine);
      * `links` (LinkSpec) — weighted shortest-path costs over the
        extended port axis, composable with a static `scenario`;
      * `schedule` (FaultSchedule) — per-EPOCH arrays plus
        `epoch_start_slot` ((E,) — epoch e covers slots
        [start[e], start[e+1])); lanes left totally disconnected report
        average_distance=NaN / diameter=0 / reachable_pairs=0 (the
        `faulted_distance_sweep` convention) instead of raising.

    Condition fields may also be passed as kwargs (`scenario=...`,
    `links=...`); passing both a `condition` and kwargs raises."""
    cond = NetworkCondition.from_kwargs(condition, **kwargs)
    links = cond.links if cond.links is not None else None
    if cond.schedule is not None:
        if links is not None and not links.is_trivial:
            # weighted × timeline: per-epoch min-plus relaxations (the
            # sweep engine is hop-count only, so this walks epochs on
            # host — E is small by construction)
            from .fault_schedule import ensure_compiled
            compiled = ensure_compiled(cond.schedule, g, cond.slots, links)
            avg, diam, pairs = [], [], []
            for scen in compiled.epochs:
                dist = weighted_distance_matrix(g, links, scenario=scen)
                d = dist[dist > 0]
                avg.append(float(d.mean()) if d.size else float("nan"))
                diam.append(int(dist.max()) if d.size else 0)
                pairs.append(int(d.size))
            return {"average_distance": np.asarray(avg, np.float64),
                    "diameter": np.asarray(diam, np.int64),
                    "reachable_pairs": np.asarray(pairs, np.int64),
                    "epoch_start_slot": np.asarray(compiled.starts,
                                                   np.int64)}
        return _faulted_schedule_stats(g, cond.schedule, cond.slots)
    if links is not None:
        return _matrix_stats(
            weighted_distance_matrix(g, links, scenario=cond.scenario))
    if cond.scenario is not None:
        return _matrix_stats(
            faulted_distance_matrix(g, cond.scenario, cond.backend))
    return {"average_distance": float(g.average_distance),
            "diameter": int(g.diameter),
            "reachable_pairs": g.order * (g.order - 1)}


def faulted_average_distance(g: LatticeGraph, scenario,
                             dist: np.ndarray | None = None) -> float:
    """Deprecated shim — `distance_stats(g, scenario=...)`."""
    _warn_deprecated(
        "faulted_average_distance",
        "distance_stats(g, scenario=...)['average_distance']")
    return _faulted_average_distance(g, scenario, dist)


def faulted_diameter(g: LatticeGraph, scenario,
                     dist: np.ndarray | None = None) -> int:
    """Deprecated shim — `distance_stats(g, scenario=...)`."""
    _warn_deprecated("faulted_diameter",
                     "distance_stats(g, scenario=...)['diameter']")
    return _faulted_diameter(g, scenario, dist)


def faulted_schedule_stats(g: LatticeGraph, schedule, slots: int = 512
                           ) -> dict:
    """Deprecated shim — `distance_stats(g, schedule=...)`."""
    _warn_deprecated("faulted_schedule_stats",
                     "distance_stats(g, schedule=..., slots=...)")
    return _faulted_schedule_stats(g, schedule, slots)


def weighted_average_distance(g: LatticeGraph, link_spec,
                              dist: np.ndarray | None = None) -> float:
    """Deprecated shim — `distance_stats(g, links=...)`."""
    _warn_deprecated(
        "weighted_average_distance",
        "distance_stats(g, links=...)['average_distance']")
    return _weighted_average_distance(g, link_spec, dist)


def weighted_diameter(g: LatticeGraph, link_spec,
                      dist: np.ndarray | None = None) -> int:
    """Deprecated shim — `distance_stats(g, links=...)`."""
    _warn_deprecated("weighted_diameter",
                     "distance_stats(g, links=...)['diameter']")
    return _weighted_diameter(g, link_spec, dist)


@dataclass(frozen=True)
class DistanceSummary:
    name: str
    n: int
    order: int
    degree: int
    diameter: int
    average_distance: float

    def row(self) -> str:
        return (f"{self.name:<24} n={self.n} N={self.order:<8} Δ={self.degree} "
                f"D={self.diameter:<4} k̄={self.average_distance:.5f}")


def summarize(name: str, g: LatticeGraph) -> DistanceSummary:
    return DistanceSummary(
        name=name, n=g.n, order=g.order, degree=g.degree,
        diameter=g.diameter, average_distance=g.average_distance)
