"""Batched, `jax.jit`-compatible minimal routing for lattice graphs (§5).

This is the device-side counterpart of `repro.core.routing` (the numpy
reference oracle).  Two layers:

  1. **Closed-form routers** — `route_ring`, `route_torus`, `route_rtt`,
     `route_fcc`, `route_bcc` are line-for-line `jnp` ports of Algorithms
     2–4 with identical signatures; the `rng: np.random.Generator` argument
     of the numpy versions becomes a `key: jax.random.PRNGKey`.  With
     `key=None` the output is bitwise-identical to the numpy router with
     `rng=None`; with a key, exact-norm ties are broken by a fair coin
     (Remark 30) so antipodal traffic spreads over both minimal records.

  2. **`RoutingEngine`** — compiles a `HierarchicalRouter(M)` (generic
     Algorithm 1, Theorem 29) into static device arrays.  At construction
     time (host, exact integer arithmetic) we walk the Hermite normal form
     top-down and precompute, per non-diagonal level `m`:

       * ``cycle_labels``: the ord(e_m) canonical labels of the cycle
         generated by e_m,
       * ``copy_table``: for each copy y of the projection graph, the
         cycle positions k with label last-component y (ord(e_m)/side
         of them — Remark 33),

     flattened into per-level gather tables.  At call time the recursion
     is unrolled **at trace time** into a fixed sequence of gathers +
     argmin reductions over the whole `(B, n)` batch — there is no Python
     loop over copies or records when the jitted function runs, and the
     whole all-pairs table build is a single XLA computation.

     For graphs whose order N = |det M| fits on device (the common pod
     sizes: N ≤ `table_threshold`, default 2²⁰ nodes), the engine runs the
     compiled recursion **once** over all N labels and materializes the
     full record table; deterministic batched routing then collapses to
     canonical reduction + one gather, which is another ~20× on CPU.  The
     recursion path remains the only path for randomized tie-breaking
     (every call must re-flip the coins) and for graphs too large to
     tabulate.

  Contract: for every difference vector v, ``RoutingEngine(M)(v)`` returns
  a record r with r ≡ v (mod M) and |r|₁ = d_G(0, v), and with `key=None`
  it reproduces the numpy `HierarchicalRouter(M)(v)` **bitwise** (same
  deterministic first-minimum tie policy).  `tests/test_routing_engine.py`
  cross-validates both properties against the BFS and CVP oracles.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import intmat

__all__ = [
    "route_ring", "route_torus", "route_rtt", "route_fcc", "route_bcc",
    "RoutingEngine", "norm1", "policy_ports", "credit_vc_select",
]


def norm1(r) -> jnp.ndarray:
    """Minkowski norm |r| = Σ|r_i| (path length of a record)."""
    return jnp.abs(r).sum(axis=-1)


def policy_ports(rec, link_ok, policy: str = "dor") -> jnp.ndarray:
    """Preferred output port per packet under a scenario routing policy
    (pure jnp — the simulator's hot path threads this as a table build,
    never as per-packet Python).

    rec: (..., n) integer routing records; link_ok: bool, broadcastable to
    (..., 2n) — liveness of the local output channels (port 2i = +e_i,
    2i+1 = −e_i).  Returns int32 ports in [0, 2n]; the sentinel value 2n
    means "no admissible port" (blocked, or nothing left to route).

      * "dor"      — first nonzero dimension of the record, live or not
                     (a dead required channel blocks the packet in place),
      * "adaptive" — first *live* productive port: minimal-adaptive over
                     the equal-norm minimal ports,
      * "escape"   — adaptive, with a non-minimal escape hop when every
                     productive port is dead: prefer a live port of an
                     ORTHOGONAL dimension (record component zero — the
                     detour sidesteps the fault and minimal-adaptive
                     resumes from the new node), else the first live port
                     (a reverse hop; note that with no orthogonal
                     dimension available — e.g. an n=1 ring — reverse +
                     minimal-first can ping-pong at the fault, a livelock
                     a memoryless policy table cannot avoid).
    """
    rec = jnp.asarray(rec)
    n = rec.shape[-1]
    P = 2 * n
    # productive[..., 2i] ⇔ rec_i > 0, productive[..., 2i+1] ⇔ rec_i < 0
    prod = jnp.stack([rec > 0, rec < 0], axis=-1).reshape(rec.shape[:-1] + (P,))

    def first(mask):
        return jnp.where(mask.any(-1), jnp.argmax(mask, axis=-1),
                         P).astype(jnp.int32)

    if policy == "dor":
        return first(prod)
    ok = jnp.broadcast_to(link_ok, prod.shape)
    minimal = first(prod & ok)
    if policy == "adaptive":
        return minimal
    if policy == "escape":
        zero = rec == 0
        ortho = jnp.stack([zero, zero], axis=-1).reshape(prod.shape)
        fallback = first(ortho & ok)
        fallback = jnp.where(fallback < P, fallback, first(ok))
        # misroute only packets that still have distance to cover
        fallback = jnp.where(prod.any(-1), fallback, jnp.int32(P))
        return jnp.where(minimal < P, minimal, fallback)
    raise ValueError(f"unknown routing policy {policy!r}")


def credit_vc_select(rec, link_ok, credit, policy: str = "adaptive",
                     rot=0, port_geom=None, escape_fallback: bool = False):
    """Credit-aware (output port, virtual channel) selection for the VC
    router (pure jnp; the simulator re-evaluates it every slot, so a
    waiting packet tracks the live credit state instead of a stale
    assignment).

    rec: (..., n) integer routing records; link_ok: bool, broadcastable
    to (..., 2n); credit: broadcastable to (..., 2n, V) — the free-credit
    view of each candidate queue one hop along port p on VC v (for
    transit that is the downstream node's input queue; for injection the
    local ring queue).  `rot` is an int32 scalar (typically the slot
    counter) rotating the round-robin tie-break between equal-credit
    candidates.  Returns ``(port, vc)`` int32 arrays; `port` carries the
    2n sentinel only when the record is zero (nothing left to route).

      * ``"dor"``      — the port is the first nonzero dimension of the
                         record (dead or not, exactly `policy_ports`);
                         the VC is the max-credit lane, so even DOR
                         traffic load-balances across the V lanes.  Every
                         lane stays dimension-ordered.
      * ``"adaptive"`` / ``"escape"`` — credit-aware minimal-adaptive:
                         candidates are the productive (minimal) LIVE
                         ports crossed with the adaptive lanes VC 1..V−1,
                         eligible while their queue advertises at least
                         one credit; the winner is the max-credit
                         candidate (round-robin tie-break).  With no
                         eligible adaptive candidate the packet requests
                         the ESCAPE lane: (DOR port, VC0).  VC0 is
                         restricted to dimension-ordered traffic by
                         construction — only ever requested through the
                         DOR port — which is what makes its channel-
                         dependence graph acyclic (deadlock freedom; see
                         docs/simulator.md).  Under the VC router
                         "escape" is an alias of "adaptive": the escape
                         *lane* replaces the V=1 misroute heuristic, so
                         records never grow and the n=1-ring ping-pong
                         livelock of the misrouting policy cannot occur.

    ``port_geom`` — optional ``(pdim, psgn, pspan)`` int arrays of shape
    (P,) describing an EXTENDED port axis (express overlays,
    `LinkSpec.port_dims/port_signs/port_spans`); link_ok/credit then
    broadcast to (..., P[, V]).  Productive ports become the ports whose
    span fits the remaining offset (``psgn·rec[pdim] > 0`` and
    ``pspan <= |rec[pdim]|``), and the DOR/escape port is the
    largest-span LIVE fitting port of the FIRST nonzero record dimension
    (base span-1 always fits, so a dead express hop degrades onto the
    base-lattice port instead of wedging the escape lane).

    ``escape_fallback`` — fault-aware escape (enable on non-trivial
    fault surfaces only): when the DOR escape port itself is dead, the
    escape request becomes the PR 3 misroute — a live orthogonal
    (record-zero dimension) port, else the first live port — still on
    VC0 ONLY.  Selections through a live DOR port are unchanged, so
    pristine runs are bitwise-identical with or without the flag.
    """
    rec = jnp.asarray(rec)
    n = rec.shape[-1]
    lead = rec.shape[:-1]
    cols = [rec[..., d] for d in range(n)]
    if port_geom is None:
        P = 2 * n
        # DOR: the first nonzero dimension, by a chain from the last one
        dor = jnp.int32(P)
        for d in range(n - 1, -1, -1):
            dor = jnp.where(cols[d] > 0, jnp.int32(2 * d),
                            jnp.where(cols[d] < 0, jnp.int32(2 * d + 1), dor))
        prod = [cols[p // 2] > 0 if p % 2 == 0 else cols[p // 2] < 0
                for p in range(P)]
        ortho = [cols[p // 2] == 0 for p in range(P)]
    else:
        pdim, psgn, pspan = (np.asarray(a).tolist() for a in port_geom)
        P = len(pdim)
        rv = [cols[d] for d in pdim]
        prod = [((r > 0) if s > 0 else (r < 0) if s < 0
                 else jnp.zeros(r.shape, bool))
                & (jnp.abs(r).astype(jnp.int32) >= span)
                for r, s, span in zip(rv, psgn, pspan)]
        ortho = [r == 0 for r in rv]
    ok = jnp.asarray(link_ok, bool)
    ok = jnp.broadcast_to(ok, jnp.broadcast_shapes(ok.shape, (P,)))
    ok = list(jnp.moveaxis(ok, -1, 0))
    if port_geom is not None:
        # largest-span LIVE fitting port of the first nonzero dimension;
        # dead spans rank below every live one (base span-1 fits whenever
        # rec[d0] != 0, so only a dead base port leaves the packet
        # blocking on it).  First maximum, as an argmax takes it.
        d0 = jnp.int32(0)
        for d in range(n - 1, -1, -1):
            d0 = jnp.where(cols[d] != 0, jnp.int32(d), d0)
        cand = [prod[p] & (d0 == pdim[p]) for p in range(P)]
        dkey = [jnp.where(cand[p], ok[p].astype(jnp.int32) * 4096 + pspan[p],
                          -1) for p in range(P)]
        best, dor, anyc = dkey[0], jnp.int32(0), cand[0]
        for p in range(1, P):
            up = dkey[p] > best
            best = jnp.where(up, dkey[p], best)
            dor = jnp.where(up, jnp.int32(p), dor)
            anyc = anyc | cand[p]
        dor = jnp.where(anyc, dor, jnp.int32(P))
    credit = jnp.asarray(credit)
    V = credit.shape[-1]
    credit = jnp.broadcast_to(credit,
                              jnp.broadcast_shapes(credit.shape, (P, V)))
    rot = jnp.asarray(rot, jnp.int32)
    if policy == "dor":
        # max-credit lane of the DOR port (the last port stands in for
        # the sentinel); keys are unique mod V, so their maximum names
        # the lane, and ties rotate with `rot`
        key = (credit.astype(jnp.int32) * V
               + (jnp.arange(V, dtype=jnp.int32) + rot) % V)
        kmax = None
        for v in range(V):
            kv = _at_port(dor, [key[..., p, v] for p in range(P - 1)],
                          key[..., P - 1, v])
            kmax = kv if kmax is None else jnp.maximum(kmax, kv)
        vc = (kmax % V - rot % V) % V
        return (jnp.broadcast_to(dor, lead).astype(jnp.int32),
                jnp.broadcast_to(vc, lead).astype(jnp.int32))
    if policy not in ("adaptive", "escape"):
        raise ValueError(f"unknown routing policy {policy!r}")
    if V < 2:
        raise ValueError("credit_vc_select needs V >= 2 (VC0 is reserved "
                         "for the escape lane)")
    # adaptive candidates: productive live ports × lanes 1..V-1 with at
    # least one free credit.  Candidate c = p·(V−1) + v−1 scores
    # credit·C + (c + rot) mod C, unique among the C = P·(V−1) of them
    # (−1 when ineligible), so the maximum key alone names the winner:
    # the max-credit candidate, ties broken round-robin by `rot`.  The
    # keys are scored at the credit view's own (per-node) shape, then
    # masked per record, one static candidate at a time.
    C = P * (V - 1)
    perm = (jnp.arange(C, dtype=jnp.int32) + rot) % C
    ckey = jnp.where(credit[..., 1:] > 0,
                     credit[..., 1:].astype(jnp.int32) * C
                     + perm.reshape(P, V - 1), -1)
    kmax = None
    for p in range(P):
        live = prod[p] & ok[p]
        for v in range(V - 1):
            key = jnp.where(live, ckey[..., p, v], -1)
            kmax = key if kmax is None else jnp.maximum(kmax, key)
    has = kmax >= 0
    best = (kmax % C - rot % C) % C
    esc = dor
    if escape_fallback:
        # fault-aware escape: a DEAD DOR port turns the escape request
        # into the PR 3 misroute — a live orthogonal-dimension port,
        # else the first live port — still on VC0 only.  Live DOR ports
        # are untouched, so pristine selections are bitwise-identical.
        dor_live = _at_port(dor, ok, False)
        mis = _first_port([o & k for o, k in zip(ortho, ok)])
        mis = jnp.where(mis < P, mis, _first_port(ok))
        mis = jnp.where(dor < P, mis, jnp.int32(P))   # nothing to route
        esc = jnp.where(dor_live, dor, mis)
    port = jnp.where(has, best // (V - 1), esc)
    vc = jnp.where(has, best % (V - 1) + 1, 0)
    return (jnp.broadcast_to(port, lead).astype(jnp.int32),
            jnp.broadcast_to(vc, lead).astype(jnp.int32))


def _at_port(port, per_port, fill):
    """The value of `per_port[port]` (a list of broadcastable arrays, one
    per port), `fill` for a port past the list: a select chain over the
    static ports, elementwise, in place of an element gather."""
    out = fill
    for p in range(len(per_port) - 1, -1, -1):
        out = jnp.where(port == p, per_port[p], out)
    return out


def _first_port(masks):
    """The first port whose mask is set, len(masks) where none is: a
    select chain from the last port to the first."""
    out = jnp.int32(len(masks))
    for p in range(len(masks) - 1, -1, -1):
        out = jnp.where(masks[p], jnp.int32(p), out)
    return out


# ---------------------------------------------------------------------------
# closed-form routers (Algorithms 2, 3, 4) — jnp ports
# ---------------------------------------------------------------------------

def route_ring(a: int, d, key=None) -> jnp.ndarray:
    """Signed shortest hop count in a ring of size a.  For even a the
    half-way distance has two minimal directions; ties are broken toward +
    unless a PRNG key is given (Remark 30)."""
    d = jnp.asarray(d)
    r = jnp.mod(d, a)
    r = jnp.where(r > a // 2, r - a, r)
    if key is not None and a % 2 == 0:
        flip = (r == a // 2) & (jax.random.uniform(key, r.shape) < 0.5)
        r = jnp.where(flip, r - a, r)
    return r


def route_torus(sides, v, key=None) -> jnp.ndarray:
    """Per-dimension ring routing (DOR components) in T(sides)."""
    v = jnp.asarray(v)
    cols = []
    for i, a in enumerate(sides):
        k_i = None if key is None else jax.random.fold_in(key, i)
        cols.append(route_ring(int(a), v[..., i], k_i))
    return jnp.stack(cols, axis=-1)


def route_rtt(a: int, v) -> jnp.ndarray:
    """Minimal routing record in the rectangular twisted torus RTT(a)
    (Algorithm 3).  No ties → no key."""
    v = jnp.asarray(v)
    x, y = v[..., 0], v[..., 1]
    p = jnp.mod(x + y + a, 2 * a)
    q = jnp.mod(y - x + a, 2 * a)
    xo = (p - q) // 2
    yo = (p + q - 2 * a) // 2
    return jnp.stack([xo, yo], axis=-1)


def _pick_min(c1: jnp.ndarray, c2: jnp.ndarray, key) -> jnp.ndarray:
    """Choose the lower-norm record; break exact ties by fair coin when a
    key is supplied (Remark 30)."""
    n1, n2 = norm1(c1), norm1(c2)
    pick = n2 < n1
    if key is not None:
        tie = (n2 == n1) & (jax.random.uniform(key, n1.shape) < 0.5)
        pick = pick | tie
    return jnp.where(pick[..., None], c2, c1)


def route_fcc(a: int, v, key=None) -> jnp.ndarray:
    """Minimal routing record in FCC(a) via two RTT(a) sub-routes
    (Algorithm 2)."""
    v = jnp.asarray(v)
    x, y, z = v[..., 0], v[..., 1], v[..., 2]
    yneg, zneg = y < 0, z < 0
    y1 = y + a * yneg
    z1 = z + a * zneg
    xh = x + a * (yneg ^ zneg)
    x1 = xh + 2 * a * (xh < 0) - 2 * a * (xh >= 2 * a)
    xy = jnp.stack([x1, y1], axis=-1)
    r1 = route_rtt(a, xy)
    r2 = route_rtt(a, xy - jnp.array([a, 0], dtype=xy.dtype))
    c1 = jnp.concatenate([r1, z1[..., None]], axis=-1)
    c2 = jnp.concatenate([r2, (z1 - a)[..., None]], axis=-1)
    return _pick_min(c1, c2, key)


def route_bcc(a: int, v, key=None) -> jnp.ndarray:
    """Minimal routing record in BCC(a) via two T(2a, 2a) sub-routes
    (Algorithm 4, corrected — see repro.core.routing docstring)."""
    v = jnp.asarray(v)
    x, y, z = v[..., 0], v[..., 1], v[..., 2]
    zneg = z < 0
    z1 = z + a * zneg
    xh = x + a * zneg
    yh = y + a * zneg
    x1 = xh + 2 * a * (xh < 0) - 2 * a * (xh >= 2 * a)
    y1 = yh + 2 * a * (yh < 0) - 2 * a * (yh >= 2 * a)
    xy = jnp.stack([x1, y1], axis=-1)
    k1 = k2 = k3 = None
    if key is not None:
        k1, k2, k3 = jax.random.split(key, 3)
    r1 = route_torus((2 * a, 2 * a), xy, k1)
    r2 = route_torus((2 * a, 2 * a), xy - jnp.array([a, a], dtype=xy.dtype), k2)
    c1 = jnp.concatenate([r1, z1[..., None]], axis=-1)
    c2 = jnp.concatenate([r2, (z1 - a)[..., None]], axis=-1)
    return _pick_min(c1, c2, k3)


# ---------------------------------------------------------------------------
# RoutingEngine: compiled generic Algorithm 1
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _Level:
    """Static tables of one non-diagonal level of the Hermite recursion."""
    m: int                   # dimension of this level's matrix H[:m, :m]
    side: int                # H[m-1, m-1]
    order: int               # ord(e_m) in Z^m / H[:m,:m] Z^m
    per_copy: int            # order // side  (cycle ∩ copy, Remark 33)
    cycle_labels: np.ndarray  # (order, m) canonical labels of k·e_m
    copy_table: np.ndarray    # (side, per_copy) cycle positions per copy
    hermite: np.ndarray       # (m, m) this level's Hermite block


def canonical_reduce(v: jnp.ndarray, H: jnp.ndarray,
                     diag=None) -> jnp.ndarray:
    """Reduce (..., m) vectors into the Hermite labelling box (jnp port of
    intmat.canonical_label).  Pass `diag` as a static tuple of ints for
    cheap scalar division; with diag=None the (traced) H diagonal is used.
    Shared by the routing engine and the simulator's delta indexing."""
    m = H.shape[0]
    for i in range(m - 1, -1, -1):
        d = diag[i] if diag is not None else H[i, i]
        q = jnp.floor_divide(v[..., i], d)
        v = v - q[..., None] * H[:, i]
    return v


class RoutingEngine:
    """Batched jitted minimal routing in G(M) for *any* nonsingular integer
    matrix M (generic Algorithm 1 compiled to gathers + argmin reductions).

    >>> eng = RoutingEngine(fcc_matrix(8))
    >>> r = eng(v)                 # v: (..., n) int array → records (..., n)
    >>> r = eng(v, key=jax.random.PRNGKey(0))   # randomized tie-breaking

    `__call__` accepts/returns numpy (host convenience, int64 to match the
    numpy router); `route` is the pure-jnp path for use inside other jitted
    computations.  Tables are compiled once at construction from exact
    integer arithmetic and stay on device across calls.

    Device arithmetic is int32: difference vectors must satisfy
    |v_i| < 2³¹ (node-label differences always do; reduce astronomically
    large v modulo the Hermite diagonal on host first if you have them).
    """

    def __init__(self, M, table_threshold: int = 1 << 20):
        self.H = intmat.hermite_normal_form(M)
        self.n = int(self.H.shape[0])
        levels: list[_Level] = []
        m = self.n
        while True:
            Hm = self.H[:m, :m]
            diag = np.diagonal(Hm)
            if m == 1 or np.array_equal(Hm, np.diag(diag)):
                break  # diagonal block → torus/ring base case
            side = int(diag[m - 1])
            order, cyc, table = intmat.cycle_copy_tables(Hm)
            levels.append(_Level(m=m, side=side, order=order,
                                 per_copy=order // side, cycle_labels=cyc,
                                 copy_table=table, hermite=Hm))
            m -= 1
        self.levels = tuple(levels)
        self.base_sides = tuple(int(x) for x in np.diagonal(self.H[:m, :m]))
        # device copies of the static tables (int32: labels/records are tiny)
        self._cyc = [jnp.asarray(l.cycle_labels, dtype=jnp.int32) for l in levels]
        self._tab = [jnp.asarray(l.copy_table, dtype=jnp.int32) for l in levels]
        self._Hj = [jnp.asarray(l.hermite, dtype=jnp.int32) for l in levels]
        self._route_det = jax.jit(lambda v: self._route_flat(v, None))
        self._route_rng = jax.jit(lambda v, key: self._route_flat(v, key))
        # all-pairs record table (built lazily by the recursion itself)
        self.N = int(np.prod([int(self.H[i, i]) for i in range(self.n)]))
        self._tabulate = self.N <= table_threshold
        self._strides = np.ones(self.n, dtype=np.int64)
        for i in range(self.n - 2, -1, -1):
            self._strides[i] = self._strides[i + 1] * int(self.H[i + 1, i + 1])
        self._records = None
        self._diag = tuple(int(self.H[i, i]) for i in range(self.n))
        self._H_full = jnp.asarray(self.H, dtype=jnp.int32)
        self._strides_j = jnp.asarray(self._strides, dtype=jnp.int32)
        self._route_lut = jax.jit(self._lut_flat)

    # -- all-pairs table ----------------------------------------------------
    def all_records(self) -> jnp.ndarray:
        """(N, n) minimal records for every canonical label, in mixed-radix
        index order (the order of `LatticeGraph.labels`).  Built once by one
        jitted pass of the compiled recursion over all N labels."""
        if self._records is None:
            grids = np.meshgrid(*[np.arange(a) for a in self._diag],
                                indexing="ij")
            labels = np.stack([g.ravel() for g in grids], axis=-1)
            self._records = self._route_det(
                jnp.asarray(labels, dtype=jnp.int32))
        return self._records

    def _lut_flat(self, v: jnp.ndarray, records: jnp.ndarray) -> jnp.ndarray:
        w = canonical_reduce(v, self._H_full, self._diag)
        return records[(w * self._strides_j).sum(axis=-1)]

    # -- trace-time recursion (unrolled; no Python at jitted call time) -----
    def _route_flat(self, v: jnp.ndarray, key) -> jnp.ndarray:
        return self._level(0, jnp.asarray(v, dtype=jnp.int32), key)

    def _level(self, li: int, v: jnp.ndarray, key) -> jnp.ndarray:
        if li == len(self.levels):           # diagonal base: torus / ring
            return route_torus(self.base_sides, v, key)
        lev = self.levels[li]
        diag = tuple(int(x) for x in np.diagonal(lev.hermite))
        W = canonical_reduce(v, self._Hj[li], diag)
        y = W[:, lev.m - 1]
        half = lev.order // 2
        best_r = best_n = None
        for slot in range(lev.per_copy):
            k_slot = None if key is None else jax.random.fold_in(key, slot)
            k = self._tab[li][y, slot]                       # (B,)
            c = self._cyc[li][k]                             # (B, m)
            sub_key = None if k_slot is None else jax.random.fold_in(k_slot, 0)
            rproj = self._level(li + 1, W[:, : lev.m - 1] - c[:, : lev.m - 1],
                                sub_key)
            # last coordinate: min-|·| lift of k along the e_m cycle.  The
            # two candidates of the numpy router, k and k − ord, share rproj,
            # so the strict-< scan reduces to a ring route over Z_ord (ties
            # toward +, or a coin when a key is supplied — Remark 30).
            kk = jnp.where(k > half, k - lev.order, k)
            if k_slot is not None and lev.order % 2 == 0:
                u = jax.random.uniform(jax.random.fold_in(k_slot, 1), k.shape)
                kk = jnp.where((k == half) & (u < 0.5), k - lev.order, kk)
            r = jnp.concatenate([rproj, kk[:, None]], axis=-1)
            nrm = norm1(r)
            if best_r is None:
                best_r, best_n = r, nrm
            else:
                take = nrm < best_n
                if k_slot is not None:
                    u = jax.random.uniform(jax.random.fold_in(k_slot, 2),
                                           nrm.shape)
                    take = take | ((nrm == best_n) & (u < 0.5))
                best_r = jnp.where(take[:, None], r, best_r)
                best_n = jnp.where(take, nrm, best_n)
        return best_r

    # -- public entry points ------------------------------------------------
    def route(self, v: jnp.ndarray, key=None) -> jnp.ndarray:
        """Pure-jnp batched routing: v (..., n) → records (..., n), int32.
        Safe to call from inside jit/vmap (tables are trace-time constants).
        Deterministic calls go through the all-pairs table when tabulated;
        keyed calls always run the recursion (fresh coins per call)."""
        v = jnp.asarray(v, dtype=jnp.int32)
        flat = v.reshape(-1, self.n)
        if key is None and self._tabulate:
            out = self._lut_flat(flat, self.all_records())
        else:
            out = self._route_flat(flat, key)
        return out.reshape(v.shape)

    def route_recursive(self, v, key=None) -> np.ndarray:
        """Force the unrolled Algorithm-1 recursion (no table lookup) —
        the path taken for graphs too large to tabulate.  Host in/out."""
        v = np.asarray(v, dtype=np.int64)
        flat = jnp.asarray(v.reshape(-1, self.n), dtype=jnp.int32)
        out = self._route_det(flat) if key is None else self._route_rng(flat, key)
        return np.asarray(out, dtype=np.int64).reshape(v.shape)

    def __call__(self, v, key=None) -> np.ndarray:
        """Host convenience: numpy in, numpy int64 out (drop-in for the
        numpy HierarchicalRouter)."""
        v = np.asarray(v, dtype=np.int64)
        flat = jnp.asarray(v.reshape(-1, self.n), dtype=jnp.int32)
        if key is None and self._tabulate:
            out = self._route_lut(flat, self.all_records())
        elif key is None:
            out = self._route_det(flat)
        else:
            out = self._route_rng(flat, key)
        return np.asarray(out, dtype=np.int64).reshape(v.shape)
