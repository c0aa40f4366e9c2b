"""Throughput bounds under uniform traffic (paper §3.4).

For edge-symmetric graphs the uniform-traffic throughput (phits/cycle/node)
is bounded by Δ/k̄.  For edge-asymmetric mixed-radix tori the binding
constraint is the most loaded dimension: Δ/(n·k̄_max), where k̄_max is the
largest per-dimension average distance.
"""
from __future__ import annotations

import numpy as np

from .condition import NetworkCondition
from .distances import (_warn_deprecated, bcc_average_distance,
                        fcc_average_distance, pc_average_distance)
from .lattice import InfeasibleNetwork, LatticeGraph


def symmetric_throughput_bound(g: LatticeGraph) -> float:
    """Δ/k̄ for edge-symmetric lattice graphs."""
    return g.degree / g.average_distance


def ring_average_distance(s: int) -> float:
    return (s * s // 4 if s % 2 == 0 else (s * s - 1) // 4) / s


def mixed_torus_throughput_bound(*sides: int) -> float:
    """Δ/(n·k̄_max) (inferred from [7] as quoted in §3.4)."""
    n = len(sides)
    k_max = max(ring_average_distance(s) for s in sides)
    return (2 * n) / (n * k_max)


def fcc_throughput_bound(a: int) -> float:
    """48/(7a) asymptotically (§3.4); exact via the closed-form k̄."""
    return 6.0 / fcc_average_distance(a)


def bcc_throughput_bound(a: int) -> float:
    """192/(35a) asymptotically (§3.4)."""
    return 6.0 / bcc_average_distance(a)


def pc_throughput_bound(a: int) -> float:
    return 6.0 / pc_average_distance(a)


def channel_load(g: LatticeGraph, records: np.ndarray,
                 seed: int = 0) -> np.ndarray:
    """Directional link loads (N, 2n) implied by a set of routing records under
    one-packet-per-node uniform traffic, assuming DOR traversal order.

    records: (P, n) minimal routing records for P source→dest pairs, sources
    drawn uniformly.  Returns expected phit-crossings per directional link per
    injected packet; max load determines saturation throughput 1/max."""
    n = g.n
    N = g.order
    P = records.shape[0]
    load = np.zeros((N, 2 * n), dtype=np.float64)
    # DOR: dimension 0 hops first, then 1, ...
    srcs = np.random.default_rng(seed).integers(0, N, size=P)
    pos = g.labels[srcs].astype(np.int64).copy()
    for dim in range(n):
        r = records[:, dim]
        sgn = np.sign(r).astype(np.int64)
        direction = (sgn < 0).astype(np.int64)
        for s in range(int(np.abs(r).max(initial=0))):
            active = np.abs(r) > s
            idx = g.label_to_index(pos[active])
            np.add.at(load, (idx, 2 * dim + direction[active]), 1.0)
            pos[active, dim] += sgn[active]
    return load * (N / P)


_DEVICE_WALK_CACHE: dict = {}


def channel_load_device(g: LatticeGraph, records: np.ndarray,
                        srcs: np.ndarray | None = None,
                        seed: int = 0) -> np.ndarray:
    """`channel_load` with the DOR link-crossing walk on device, as ONE
    segment-sum.  DOR positions are closed-form — after finishing
    dimensions d' < d the packet sits at src + Σ_{d'<d} r_{d'}·e_{d'} —
    so every crossing event (pair, dim, step) is enumerated by
    broadcasting, canonically reduced, flattened to a directional-link id
    and accumulated with a single `jax.ops.segment_sum` over N·2n
    segments.  No per-step scatter and no fori_loop (this closes the
    ROADMAP "device walk is scatter-serialized on CPU" frontier); same
    loads as the numpy walk for the same records/sources, which remains
    as `channel_load`."""
    import jax
    import jax.numpy as jnp

    from .routing_engine import canonical_reduce

    n, N = g.n, g.order
    records = np.asarray(records)
    P = records.shape[0]
    if srcs is None:
        srcs = np.random.default_rng(seed).integers(0, N, size=P)
    bounds = tuple(int(np.abs(records[:, d]).max(initial=0))
                   for d in range(n))
    hermite = g.hermite.astype(np.int32)
    key = (n, N, P, bounds, hermite.tobytes())
    if key not in _DEVICE_WALK_CACHE:
        H = jnp.asarray(hermite)
        strides = jnp.asarray(g.strides.astype(np.int32))
        diag = tuple(int(hermite[i, i]) for i in range(n))
        eye = np.eye(n, dtype=np.int32)
        # completed-dimension mask: prefix_d = src + rec ⊙ lower[d]
        lower = np.tril(np.ones((n, n), np.int32), -1)

        def walk(pos, rec):
            ids, weights = [], []
            for dim in range(n):            # static, tiny
                b = bounds[dim]
                if b == 0:
                    continue
                r = rec[:, dim]                             # (P,)
                sgn = jnp.sign(r)
                chan = 2 * dim + (r < 0)
                prefix = pos + rec * lower[dim]             # (P, n)
                t = jnp.arange(b, dtype=jnp.int32)
                steps = (prefix[:, None, :]
                         + t[None, :, None] * sgn[:, None, None]
                         * eye[dim][None, None, :])         # (P, b, n)
                w = canonical_reduce(steps, H, diag)
                idx = (w * strides).sum(axis=-1)            # (P, b)
                ids.append((idx * (2 * n) + chan[:, None]).ravel())
                weights.append(
                    (t[None, :] < jnp.abs(r)[:, None]).ravel())
            load = jax.ops.segment_sum(
                jnp.concatenate(weights).astype(jnp.float32),
                jnp.concatenate(ids), num_segments=N * 2 * n)
            return load.reshape(N, 2 * n) * (N / P)

        _DEVICE_WALK_CACHE[key] = jax.jit(walk)
    out = _DEVICE_WALK_CACHE[key](
        jnp.asarray(g.labels[srcs].astype(np.int32)),
        jnp.asarray(records.astype(np.int32)))
    return np.asarray(out, dtype=np.float64)


def channel_load_uniform(g: LatticeGraph, pairs: int = 20_000, seed: int = 0,
                         backend: str = "auto") -> np.ndarray:
    """Monte-Carlo channel loads under uniform traffic: sample `pairs`
    source→destination pairs, route them through the batched engine, and
    accumulate DOR link crossings — routing AND the crossing walk run on
    device unless `backend='numpy'`.  The empirical saturation throughput
    is `1 / channel_load_uniform(g).max()` phits/cycle/node — cross-check
    it against the analytic Δ/k̄ bound of §3.4."""
    from .routing import make_router
    rng = np.random.default_rng(seed)
    router = make_router(g.matrix, backend)
    srcs = rng.integers(0, g.order, pairs)
    v = g.labels[srcs] - g.labels[rng.integers(0, g.order, pairs)]
    records = np.asarray(router(v))
    if backend != "numpy":
        try:
            # channel_load re-draws `srcs` from the same seed (first draw
            # of the generator), so the device walk sees identical sources
            return channel_load_device(g, records, srcs=srcs)
        except ImportError:       # jax absent — numpy walk stands alone
            pass
    return channel_load(g, records, seed=seed)


def measured_saturation_throughput(g: LatticeGraph, pairs: int = 20_000,
                                   seed: int = 0,
                                   backend: str = "auto") -> float:
    """1/max-link-load under engine-routed uniform traffic (phits/cyc/node)."""
    return float(1.0 / channel_load_uniform(g, pairs, seed, backend).max())


def simulated_saturation_load(g: LatticeGraph, loads, *, pattern="uniform",
                              config=None, seeds: int = 1) -> float:
    """Dynamic counterpart of `measured_saturation_throughput`: sweep the
    slot-level simulator over `loads` offered phits/cycle/node and return
    the peak ACCEPTED load — saturation as the router actually realises it
    (queue contention, bubble rule, and with ``config.vcs > 1`` the VC
    credit-flow router) rather than the static 1/max-link-load proxy.
    `config` is a `repro.core.SimConfig`; None uses the defaults."""
    from .simulation import simulate_sweep
    if seeds == 1:
        seeds = None          # list[SimResult] path; no replication axis
    results = simulate_sweep(g, pattern, list(loads), seeds=seeds,
                             config=config)
    if isinstance(results, list):
        return max(float(r.accepted_load) for r in results)
    return float(results.accepted_mean().max())


# ---------------------------------------------------------------------------
# degraded-graph (scenario) loads: fault-aware table rebuild
# ---------------------------------------------------------------------------

def _fault_aware_channel_load(g: LatticeGraph, scenario,
                              pairs: int = 20_000, seed: int = 0,
                              tables=None,
                              backend: str = "auto") -> np.ndarray:
    """Monte-Carlo channel loads on a *degraded* graph: `pairs` uniform
    live-src → live-dst pairs are walked along the fault-aware BFS
    next-hop tables (`routing.fault_aware_next_hop`), so the load
    distribution — and the saturation bound 1/max derived from it —
    reflects the faulted topology instead of the pristine minimal records.
    Unreachable/self pairs are redrawn out of the sample; by construction
    no dead channel is ever crossed (asserted).  Scaled to one packet per
    live node, matching the `channel_load` convention.  The table rebuild
    runs on device by default (`routing.fault_aware_next_hop_device`,
    identical tables); backend="host" forces the numpy BFS loop."""
    from .routing import fault_aware_next_hop, fault_aware_next_hop_device
    if backend not in ("auto", "device", "host"):
        raise ValueError(f"unknown BFS backend {backend!r}")
    link_ok = scenario.link_ok(g)
    node_ok = scenario.node_ok(g)
    if tables is not None:
        dist, next_hop = tables
    elif backend != "host":
        try:
            dist, next_hop = fault_aware_next_hop_device(g, link_ok, node_ok)
        except ImportError:   # jax absent — only "auto" may fall back
            if backend == "device":
                raise
            dist, next_hop = fault_aware_next_hop(g, link_ok, node_ok)
    else:
        dist, next_hop = fault_aware_next_hop(g, link_ok, node_ok)
    live = np.flatnonzero(node_ok)
    if live.size < 2:
        raise InfeasibleNetwork("scenario leaves fewer than 2 live nodes")
    rng = np.random.default_rng(seed)
    srcs = live[rng.integers(0, live.size, pairs)]
    dsts = live[rng.integers(0, live.size, pairs)]
    use = dist[srcs, dsts] > 0                   # reachable, not self
    pos, dst = srcs[use].copy(), dsts[use]
    n_used = pos.size
    load = np.zeros((g.order, 2 * g.n), dtype=np.float64)
    nbr = g.neighbor_indices
    while pos.size:
        p = next_hop[pos, dst]
        assert (p >= 0).all() and link_ok[pos, p].all(), \
            "fault-aware walk stepped onto a dead channel"
        np.add.at(load, (pos, p), 1.0)
        pos = nbr[pos, p]
        alive = pos != dst
        pos, dst = pos[alive], dst[alive]
    return load * (live.size / max(n_used, 1))


def _fault_aware_saturation_throughput(g: LatticeGraph, scenario,
                                       pairs: int = 20_000,
                                       seed: int = 0) -> float:
    """1/max-link-load of the degraded graph under uniform live-pair
    traffic routed around the faults (phits/cycle/node)."""
    return float(
        1.0 / _fault_aware_channel_load(g, scenario, pairs, seed).max())


def _fault_aware_schedule_load(g: LatticeGraph, schedule, slots: int = 512,
                               pairs: int = 20_000, seed: int = 0,
                               link_spec=None) -> np.ndarray:
    """Per-EPOCH Monte-Carlo channel loads of a transient-fault timeline
    (`repro.core.fault_schedule.FaultSchedule` / `CompiledSchedule`):
    the fault-aware BFS tables for ALL epochs are rebuilt in one compiled
    device program (`routing.fault_aware_next_hop_device`'s stacked-epoch
    mode), then each epoch's live-pair traffic is walked along its own
    tables.  Returns (E, N, 2n) loads — or (E, N, 2n+2X) with a
    `link_spec` carrying express overlays, where the walk follows
    weighted-shortest-path tables over the extended port axis and link
    events may kill/repair express channels — the per-epoch load curve
    the degraded saturation bound below derives from."""
    from .fault_schedule import ensure_compiled
    from .routing import fault_aware_next_hop_device
    ls = link_spec if link_spec is not None and not link_spec.is_trivial \
        else None
    compiled = ensure_compiled(schedule, g, slots, ls)
    if ls is not None:
        dist, nh = fault_aware_next_hop_device(
            g, compiled.link_ok_stack(g, ls), compiled.node_ok_stack(g),
            link_spec=ls)
        nbr = ls.extended_neighbors(g)
        return np.stack([
            _walk_loads(nbr, dist[e], nh[e], scen.node_ok(g), pairs, seed,
                        link_ok=scen.link_ok(g, ls))
            for e, scen in enumerate(compiled.epochs)])
    dist, nh = fault_aware_next_hop_device(
        g, compiled.link_ok_stack(g), compiled.node_ok_stack(g))
    return np.stack([
        _fault_aware_channel_load(g, scen, pairs, seed,
                                  tables=(dist[e], nh[e]))
        for e, scen in enumerate(compiled.epochs)])


def _fault_aware_schedule_saturation(g: LatticeGraph, schedule,
                                     slots: int = 512, pairs: int = 20_000,
                                     seed: int = 0,
                                     link_spec=None) -> np.ndarray:
    """(E,) per-epoch saturation bounds of a transient-fault timeline —
    how the fabric's degraded capacity moves as links flap and nodes
    die/return.  Uniform fabrics use 1/max-load; a weighted `link_spec`
    scales each channel's load by its slot cost first (the
    `weighted_saturation_throughput` convention)."""
    loads = _fault_aware_schedule_load(g, schedule, slots, pairs, seed,
                                       link_spec=link_spec)
    if link_spec is not None and not link_spec.is_trivial:
        w = link_spec.port_weights(g.n).astype(np.float64)
        loads = loads * w[None, None, :]
    return 1.0 / loads.reshape(loads.shape[0], -1).max(axis=1)


# ---------------------------------------------------------------------------
# heterogeneous-link (LinkSpec) loads: weighted tables over extended ports
# ---------------------------------------------------------------------------

def _walk_loads(nbr: np.ndarray, dist: np.ndarray, next_hop: np.ndarray,
                node_ok: np.ndarray, pairs: int, seed: int,
                link_ok: np.ndarray | None = None) -> np.ndarray:
    """Shared Monte-Carlo table walk over an arbitrary (N, P) port axis:
    `pairs` uniform live-src → live-dst draws stepped along `next_hop`,
    unreachable/self pairs redrawn out of the sample, loads scaled to one
    packet per live node.  With `link_ok` every step additionally asserts
    it never crosses a dead channel (express columns included)."""
    N, P = nbr.shape
    node_ok = np.asarray(node_ok, dtype=bool)
    live = np.flatnonzero(node_ok)
    if live.size < 2:
        raise InfeasibleNetwork("scenario leaves fewer than 2 live nodes")
    rng = np.random.default_rng(seed)
    srcs = live[rng.integers(0, live.size, pairs)]
    dsts = live[rng.integers(0, live.size, pairs)]
    use = dist[srcs, dsts] > 0                   # reachable, not self
    pos, dst = srcs[use].copy(), dsts[use]
    n_used = pos.size
    load = np.zeros((N, P), dtype=np.float64)
    while pos.size:
        p = next_hop[pos, dst]
        assert (p >= 0).all(), "fault-aware walk hit an unreachable pair"
        if link_ok is not None:
            assert link_ok[pos, p].all(), \
                "fault-aware walk stepped onto a dead channel"
        np.add.at(load, (pos, p), 1.0)
        pos = nbr[pos, p]
        alive = pos != dst
        pos, dst = pos[alive], dst[alive]
    return load * (live.size / max(n_used, 1))


def _weighted_channel_load(g: LatticeGraph, link_spec, pairs: int = 20_000,
                           seed: int = 0, scenario=None) -> np.ndarray:
    """Monte-Carlo channel loads on a HETEROGENEOUS fabric: `pairs`
    uniform pairs walked along weighted-shortest-path next-hop tables
    over the extended (base + express) port axis — express channels
    attract the traffic whose weighted cost they lower, pillar masks
    divert Z-traffic through the pillar columns.  Returns (N, P) with
    P = 2n + 2·X (the base (N, 2n) block keeps the `channel_load`
    convention; express columns follow).  Scaled to one packet per live
    node.  An optional fault `scenario` composes over the FULL extended
    axis — dead_links may name express ports (they die like any link)
    and traffic reroutes around them through the base lattice."""
    from .routing import fault_aware_next_hop_device
    ls = link_spec if link_spec is not None and not link_spec.is_trivial \
        else None
    if scenario is not None:
        link_ok = scenario.link_ok(g, ls)
        node_ok = np.asarray(scenario.node_ok(g), dtype=bool)
    else:
        link_ok = np.ones((g.order, 2 * g.n), dtype=bool)
        node_ok = np.ones(g.order, dtype=bool)
    dist, next_hop = fault_aware_next_hop_device(
        g, link_ok, node_ok, link_spec=link_spec)
    nbr = ls.extended_neighbors(g) if ls is not None else g.neighbor_indices
    return _walk_loads(nbr, dist, next_hop, node_ok, pairs, seed,
                       link_ok=None if scenario is None else
                       scenario.link_ok(g, ls))


def _weighted_saturation_throughput(g: LatticeGraph, link_spec,
                                    pairs: int = 20_000,
                                    seed: int = 0, scenario=None) -> float:
    """Saturation bound of the heterogeneous fabric (phits/cycle/node):
    ``1 / max_c(load_c · w_c)`` — a weight-w channel serves one packet
    every w slots, so its effective service demand is its Monte-Carlo
    load times its slot cost.  With a trivial spec this is exactly the
    unweighted 1/max-link-load bound.  An optional fault `scenario`
    composes (the facade's weighted × faulted cell — the legacy
    `weighted_saturation_throughput` never grew this axis)."""
    load = _weighted_channel_load(g, link_spec, pairs, seed,
                                  scenario=scenario)
    w = _effective_port_weights(g, link_spec, load.shape[-1])
    return float(1.0 / (load * w[None, :]).max())


def _effective_port_weights(g: LatticeGraph, link_spec,
                            n_ports: int) -> np.ndarray:
    """(P,) slot costs matching a load array's port axis: the LinkSpec's
    per-port weights when heterogeneous, all-ones otherwise."""
    if link_spec is not None and not link_spec.is_trivial:
        return link_spec.port_weights(g.n).astype(np.float64)
    return np.ones(n_ports, dtype=np.float64)


# ---------------------------------------------------------------------------
# unified analytic surface: channel_load_stats / saturation facades + shims
# ---------------------------------------------------------------------------

def channel_load_stats(g: LatticeGraph,
                       condition: NetworkCondition | None = None,
                       **kwargs) -> dict:
    """Monte-Carlo channel-load summary of `g` under one
    `repro.core.NetworkCondition` — THE entry point for degraded/weighted
    load metrics (the shimmed `fault_aware_*`/`weighted_*` names all
    dispatch through here).

    Returns {"load", "max_load", "saturation"} where `load` is the
    (N, P) per-channel phit-crossing array (P = 2n, or 2n+2X with
    express overlays), `max_load` is the peak *effective* service demand
    ``max_c(load_c · w_c)`` and `saturation` is its reciprocal — so
    ``saturation == saturation(g, condition)`` always.  A `schedule`
    condition returns per-EPOCH arrays ((E, N, P) / (E,)) plus
    `epoch_start_slot`.

    Dispatch: `links` → weighted tables over the extended port axis
    (composable with `scenario`); `scenario` → fault-aware BFS tables;
    `schedule` → per-epoch stacked tables; pristine → DOR minimal-record
    crossings (`channel_load_uniform`)."""
    cond = NetworkCondition.from_kwargs(condition, **kwargs)
    if cond.schedule is not None:
        load = _fault_aware_schedule_load(
            g, cond.schedule, cond.slots, cond.pairs, cond.seed,
            link_spec=cond.links)
        w = _effective_port_weights(g, cond.links, load.shape[-1])
        max_load = (load * w[None, None, :]).reshape(
            load.shape[0], -1).max(axis=1)
        from .fault_schedule import ensure_compiled
        ls = cond.links if cond.links is not None \
            and not cond.links.is_trivial else None
        compiled = ensure_compiled(cond.schedule, g, cond.slots, ls)
        return {"load": load, "max_load": max_load,
                "saturation": 1.0 / max_load,
                "epoch_start_slot": np.asarray(compiled.starts, np.int64)}
    if cond.links is not None:
        load = _weighted_channel_load(g, cond.links, cond.pairs, cond.seed,
                                      scenario=cond.scenario)
    elif cond.scenario is not None:
        load = _fault_aware_channel_load(g, cond.scenario, cond.pairs,
                                         cond.seed, backend=cond.backend)
    else:
        load = channel_load_uniform(g, cond.pairs, cond.seed,
                                    cond.router_backend)
    w = _effective_port_weights(g, cond.links, load.shape[-1])
    max_load = float((load * w[None, :]).max())
    return {"load": load, "max_load": max_load,
            "saturation": 1.0 / max_load}


def saturation(g: LatticeGraph,
               condition: NetworkCondition | None = None,
               **kwargs) -> float | np.ndarray:
    """Saturation throughput of `g` under one
    `repro.core.NetworkCondition` (phits/cycle/node): the reciprocal of
    the peak effective channel demand ``max_c(load_c · w_c)`` under
    uniform (live-pair) Monte-Carlo traffic.  Scalar for static
    conditions; (E,) per-epoch array for a `schedule`.

    This subsumes `measured_saturation_throughput` (pristine),
    `fault_aware_saturation_throughput` (scenario),
    `weighted_saturation_throughput` (links — now composable with a
    scenario) and `fault_aware_schedule_saturation` (schedule)."""
    cond = NetworkCondition.from_kwargs(condition, **kwargs)
    if cond.schedule is not None:
        return _fault_aware_schedule_saturation(
            g, cond.schedule, cond.slots, cond.pairs, cond.seed,
            link_spec=cond.links)
    if cond.links is not None:
        return _weighted_saturation_throughput(
            g, cond.links, cond.pairs, cond.seed, scenario=cond.scenario)
    if cond.scenario is not None:
        return _fault_aware_saturation_throughput(
            g, cond.scenario, cond.pairs, cond.seed)
    return measured_saturation_throughput(g, cond.pairs, cond.seed,
                                          cond.router_backend)


def fault_aware_channel_load(g: LatticeGraph, scenario,
                             pairs: int = 20_000, seed: int = 0,
                             tables=None, backend: str = "auto") -> np.ndarray:
    """Deprecated shim — `channel_load_stats(g, scenario=...)`."""
    _warn_deprecated("fault_aware_channel_load",
                     "channel_load_stats(g, scenario=...)['load']")
    return _fault_aware_channel_load(g, scenario, pairs, seed, tables,
                                     backend)


def fault_aware_saturation_throughput(g: LatticeGraph, scenario,
                                      pairs: int = 20_000,
                                      seed: int = 0) -> float:
    """Deprecated shim — `saturation(g, scenario=...)`."""
    _warn_deprecated("fault_aware_saturation_throughput",
                     "saturation(g, scenario=...)")
    return _fault_aware_saturation_throughput(g, scenario, pairs, seed)


def fault_aware_schedule_load(g: LatticeGraph, schedule, slots: int = 512,
                              pairs: int = 20_000, seed: int = 0,
                              link_spec=None) -> np.ndarray:
    """Deprecated shim — `channel_load_stats(g, schedule=...)`."""
    _warn_deprecated("fault_aware_schedule_load",
                     "channel_load_stats(g, schedule=...)['load']")
    return _fault_aware_schedule_load(g, schedule, slots, pairs, seed,
                                      link_spec)


def fault_aware_schedule_saturation(g: LatticeGraph, schedule,
                                    slots: int = 512, pairs: int = 20_000,
                                    seed: int = 0,
                                    link_spec=None) -> np.ndarray:
    """Deprecated shim — `saturation(g, schedule=...)`."""
    _warn_deprecated("fault_aware_schedule_saturation",
                     "saturation(g, schedule=...)")
    return _fault_aware_schedule_saturation(g, schedule, slots, pairs, seed,
                                            link_spec)


def weighted_channel_load(g: LatticeGraph, link_spec, pairs: int = 20_000,
                          seed: int = 0, scenario=None) -> np.ndarray:
    """Deprecated shim — `channel_load_stats(g, links=...)`."""
    _warn_deprecated("weighted_channel_load",
                     "channel_load_stats(g, links=...)['load']")
    return _weighted_channel_load(g, link_spec, pairs, seed, scenario)


def weighted_saturation_throughput(g: LatticeGraph, link_spec,
                                   pairs: int = 20_000,
                                   seed: int = 0) -> float:
    """Deprecated shim — `saturation(g, links=...)`."""
    _warn_deprecated("weighted_saturation_throughput",
                     "saturation(g, links=...)")
    return _weighted_saturation_throughput(g, link_spec, pairs, seed)
