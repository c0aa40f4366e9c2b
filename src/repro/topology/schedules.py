"""Executable collective schedules from lattice routing (paper §5 → TPU).

The paper's minimal routing records are integer hop vectors on the pod's
lattice graph.  This module turns them into *collective schedules*:

  * `ring_schedule` — orders the chips of one logical mesh axis along a ring
    embedded in the lattice (from topology.placement) and derives, for every
    logical edge, the physical ICI links its traffic crosses (DOR over the
    minimal record).  `verify_contention_free` checks that a collective step
    uses every physical link at most once — the condition for the ring
    collective to run at full link bandwidth (dilation-1 embeddings pass).

  * `ppermute_ring_allreduce` — a reduce-scatter + all-gather all-reduce
    written explicitly with `jax.lax.ppermute` (2·(k−1) neighbor hops),
    numerically equal to `psum`.  This is the deterministic, topology-aware
    collective the schedule prices; on a real pod the ppermute pairs are
    laid onto the `ring_schedule` order.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LatticeGraph
from repro.core.routing import make_router


# ---------------------------------------------------------------------------
# physical link schedules from routing records
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RingSchedule:
    """One logical axis embedded as a ring of physical chips."""
    node_order: np.ndarray          # (k,) lattice node indices, ring order
    edge_paths: list[list[tuple[int, int]]]   # per logical edge: [(node, port)]
    dilation: float                 # mean physical hops per logical edge
    # heterogeneous fabrics (ring_schedule(link_spec=...)): per logical
    # edge, the weighted slot cost of its path; and the (P,) per-port slot
    # costs so contention accounting can price weight-w links at 1/w
    # bandwidth.  None on the uniform weight-1 fabric (the historical
    # schedule, unchanged).
    edge_costs: np.ndarray | None = None
    port_weights: np.ndarray | None = None


def ring_schedule(g: LatticeGraph, ring_labels: np.ndarray,
                  link_spec=None, scenario=None) -> RingSchedule:
    """ring_labels: (k, n) lattice labels of the chips of one logical axis,
    in ring order.  Paths follow DOR over minimal routing records (all k
    logical edges routed in one batched engine call).

    `link_spec=` (a non-trivial `repro.core.LinkSpec`) lifts the standing
    pristine-uniform-ring constraint: each logical edge is instead routed
    along WEIGHTED shortest paths over the extended (base + express) port
    axis — express channels shorten edges whose offset they span, pillar
    masks force Z-traffic through pillar columns, and per-dimension
    weights steer paths onto cheap dimensions.  The returned schedule
    then carries `edge_costs` (weighted slots per logical edge) and
    `port_weights`, which `verify_contention_free` /
    `effective_ring_bandwidth` fold into their contention accounting.

    `scenario=` (a faulted `repro.core.Scenario`) routes the logical ring
    edges AROUND dead links/nodes via the fault-aware BFS next-hop tables
    (composes with `link_spec=` — dead_links may name express ports).  A
    ring chip that is itself dead, or a logical edge the live fabric
    disconnects, raises with the offending node/edge named — the caller
    must re-place the ring, not silently run a broken collective."""
    ls = (link_spec if link_spec is not None
          and not link_spec.is_trivial else None)
    scen = (scenario if scenario is not None
            and (scenario.dead_links or scenario.dead_nodes) else None)
    k = ring_labels.shape[0]
    order = g.label_to_index(ring_labels)
    if ls is not None or scen is not None:
        from repro.core.routing import fault_aware_next_hop_device
        if scen is not None:
            link_ok = scen.link_ok(g, ls)
            node_ok = np.asarray(scen.node_ok(g), dtype=bool)
            dead = [int(u) for u in order if not node_ok[u]]
            if dead:
                raise ValueError(
                    f"ring chip(s) {dead} are dead in scenario "
                    f"{scen.name!r}; re-place the ring on live nodes")
        else:
            link_ok = np.ones((g.order, 2 * g.n), dtype=bool)
            node_ok = None
        dist, nh = fault_aware_next_hop_device(g, link_ok, node_ok,
                                               link_spec=ls)
        nbr = (ls.extended_neighbors(g) if ls is not None
               else g.neighbor_indices)
        dsts = np.roll(np.asarray(order), -1)
        paths = []
        costs = []
        for t in range(k):
            u, d = int(order[t]), int(dsts[t])
            if u != d and dist[u, d] < 0:
                raise ValueError(
                    f"ring edge {u} -> {d} is unreachable — the live "
                    "fabric disconnects the ring"
                    + (f" (scenario {scen.name!r})" if scen is not None
                       else " (pillar mask cut the fabric)"))
            path = []
            pos = u
            while pos != d:
                p = int(nh[pos, d])
                path.append((pos, p))
                pos = int(nbr[pos, p])
            paths.append(path)
            costs.append(int(dist[u, d]) if u != d else 0)
        hops = [len(p) for p in paths]
        return RingSchedule(node_order=order, edge_paths=paths,
                            dilation=float(np.mean(hops)),
                            edge_costs=np.asarray(costs, dtype=np.int64),
                            port_weights=(None if ls is None
                                          else ls.port_weights(g.n)))
    router = make_router(g.matrix)
    recs = np.asarray(router(np.roll(ring_labels, -1, axis=0) - ring_labels))
    paths = []
    for t in range(k):
        src = ring_labels[t]
        rec = recs[t]
        path = []
        pos = src.copy()
        for dim in range(g.n):
            step = int(rec[dim])
            sgn = 1 if step >= 0 else -1
            for _ in range(abs(step)):
                port = 2 * dim + (0 if sgn > 0 else 1)
                path.append((int(g.label_to_index(pos)), port))
                pos = pos + sgn * np.eye(g.n, dtype=np.int64)[dim]
        paths.append(path)
    hops = [len(p) for p in paths]
    return RingSchedule(node_order=order, edge_paths=paths,
                        dilation=float(np.mean(hops)),
                        edge_costs=np.asarray(hops, dtype=np.int64))


def verify_contention_free(sched: RingSchedule) -> dict:
    """In a ring collective step every logical edge is active simultaneously;
    full bandwidth requires each directional physical link to appear in at
    most one logical edge's path.  On a weighted schedule the serialization
    unit is SERVICE slots, not crossings: a weight-w link needs w slots per
    packet, so `max_link_service` = max over links of use·w (equal to
    `max_link_use` on uniform fabrics)."""
    use: dict[tuple[int, int], int] = {}
    for path in sched.edge_paths:
        for link in path:
            use[link] = use.get(link, 0) + 1
    max_use = max(use.values()) if use else 0
    if sched.port_weights is not None:
        w = np.asarray(sched.port_weights)
        max_service = max((c * int(w[p]) for (_, p), c in use.items()),
                          default=0)
    else:
        max_service = max_use
    return {"contention_free": max_use <= 1, "max_link_use": max_use,
            "max_link_service": max_service,
            "links_used": len(use), "dilation": sched.dilation}


def effective_ring_bandwidth(sched: RingSchedule, link_bw: float = 50e9) -> float:
    """Per-step ring bandwidth after contention: the busiest link serializes
    (weight-aware — a weight-w link delivers link_bw/w, so the serialization
    denominator is the max per-link SERVICE load use·w)."""
    stats = verify_contention_free(sched)
    return link_bw / max(stats["max_link_service"], 1)


# ---------------------------------------------------------------------------
# explicit ppermute ring all-reduce (≡ psum)
# ---------------------------------------------------------------------------

def ppermute_ring_allreduce(x, axis_name: str, axis_size: int):
    """Bandwidth-optimal ring all-reduce via 2·(k−1) ppermute steps.

    Call inside shard_map.  x: any array whose leading dim is divisible by
    the ring size (the chunk dimension)."""
    k = axis_size
    if k == 1:
        return x
    chunks = jnp.stack(jnp.split(x, k, axis=0))       # (k, m/k, ...)
    perm = [(i, (i + 1) % k) for i in range(k)]
    rank = jax.lax.axis_index(axis_name)

    # reduce-scatter: after k-1 steps, chunk (rank+1) mod k is fully reduced
    def rs_step(t, buf):
        send_idx = (rank - t) % k
        piece = jnp.take(buf, send_idx, axis=0)
        received = jax.lax.ppermute(piece, axis_name, perm)
        recv_idx = (rank - t - 1) % k
        return buf.at[recv_idx].add(received)

    buf = jax.lax.fori_loop(0, k - 1, rs_step, chunks)

    # all-gather: circulate the reduced chunks
    def ag_step(t, buf):
        send_idx = (rank + 1 - t) % k
        piece = jnp.take(buf, send_idx, axis=0)
        received = jax.lax.ppermute(piece, axis_name, perm)
        recv_idx = (rank - t) % k
        return buf.at[recv_idx].set(received)

    buf = jax.lax.fori_loop(0, k - 1, ag_step, buf)
    return buf.reshape(x.shape)


def grad_ring_allreduce(grads, mesh, axis: str = "data"):
    """DP gradient all-reduce over one mesh axis using the explicit ring —
    a drop-in for psum when the collective must follow a known physical ring
    order (e.g. the `ring_schedule` embedding).  Call inside shard_map."""
    k = mesh.shape[axis]

    def one(g):
        flat = g.reshape(-1)
        pad = (-flat.shape[0]) % k
        if pad:
            flat = jnp.pad(flat, (0, pad))
        out = ppermute_ring_allreduce(flat, axis, k)
        return out[: g.size].reshape(g.shape)

    return jax.tree.map(one, grads)
