"""VC credit-flow router (ISSUE 7): deadlock-freedom enumeration, credit
invariants, per-VC conservation, the V=1 bitwise contract, and the n=1-ring
cell that used to carry the escape-livelock caveat.

Deadlock freedom is checked the Duato way: enumerate the ESCAPE lane's
channel-dependence graph and show it cannot cycle.  VC0 only ever carries
dimension-ordered traffic (`credit_vc_select` requests it through the DOR
port — the first nonzero record dimension), records never grow under the
VC router, and a record's low dimensions stay zero once corrected.  So
every escape transition either continues the SAME directed ring (need=1,
protected by the bubble invariant: entering a ring costs 2 credits, so a
ring never fills completely) or turns into a STRICTLY higher dimension.
Contracting each directed ring to one node therefore yields a DAG — the
test walks every (source, record-table) DOR path, collects the channel
transitions, asserts the dimension monotonicity hop-by-hop, and runs a
topological sort over the ring-quotient graph on T(4,4,4,4), RTT, FCC
and BCC.  Dimension monotonicity depends only on (node, first nonzero
dim), never on magnitudes, so it also covers records partially consumed
by adaptive-lane hops before falling back to the escape lane.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import BCC, FCC, RTT, LinkSpec, Scenario, SimConfig, Torus
from repro.core.routing_engine import credit_vc_select
from repro.core.simulation import (_init_state, _make_ctx,
                                   _make_slot_step_vc_batched,
                                   _make_traffic, build_tables, simulate)

# ---------------------------------------------------------------------------
# escape-CDG acyclicity (the deadlock-freedom enumeration)
# ---------------------------------------------------------------------------

_CDG_GRAPHS = {
    "T4444": Torus(4, 4, 4, 4),
    "RTT4": RTT(4),
    "FCC2": FCC(2),
    "BCC2": BCC(2),
}


def _ring_ids(nbr: np.ndarray) -> np.ndarray:
    """(N, P) id of the directed ring each channel (node, port) belongs
    to: the orbit of `node` under the port-p neighbor permutation."""
    N, P = nbr.shape
    rid = np.full((N, P), -1, np.int64)
    nxt = 0
    for p in range(P):
        for w in range(N):
            if rid[w, p] >= 0:
                continue
            c = w
            while rid[c, p] < 0:
                rid[c, p] = nxt
                c = int(nbr[c, p])
            nxt += 1
    return rid


def _escape_edges(g):
    """All channel-dependence edges ((w1,p1) → (w2,p2)) of escape-lane
    walks from every source × every injectable record (both Remark-30
    minimal tables), plus the neighbor table."""
    t = build_tables(g)
    nbr, n = t.neighbors, t.n
    N = t.N
    edges = set()
    for table in (t.records_a, t.records_b):
        # start every delta from every source (vertex-transitive, but the
        # channel ids are per-node — enumerate them all)
        di = np.arange(N)
        src = np.repeat(np.arange(N), N)
        rec = np.tile(table[di], (N, 1)).reshape(N * N, n).copy()
        cur = src.copy()
        prev_ch = np.full(N * N, -1, np.int64)
        while True:
            live = np.abs(rec).sum(axis=1) > 0
            if not live.any():
                break
            cur, rec, prev_ch = cur[live], rec[live], prev_ch[live]
            d = np.argmax(np.abs(rec) > 0, axis=1)
            s = rec[np.arange(len(rec)), d]
            p = 2 * d + (s < 0)
            ch = cur * (2 * n) + p
            has_prev = prev_ch >= 0
            edges.update(zip(prev_ch[has_prev].tolist(),
                             ch[has_prev].tolist()))
            cur = nbr[cur, p]
            rec[np.arange(len(rec)), d] -= np.sign(s)
            prev_ch = ch
    return edges, nbr


@pytest.mark.parametrize("name", sorted(_CDG_GRAPHS))
def test_escape_cdg_acyclic(name):
    g = _CDG_GRAPHS[name]
    edges, nbr = _escape_edges(g)
    assert edges, "escape walks produced no channel dependencies"
    P = nbr.shape[1]
    rid = _ring_ids(nbr)
    quotient = set()
    for c1, c2 in edges:
        w1, p1 = divmod(c1, P)
        w2, p2 = divmod(c2, P)
        assert w2 == nbr[w1, p1]          # a dependence follows the hop
        if p1 == p2:
            # same-ring continuation — the bubble's territory, and
            # genuinely the same directed ring
            assert rid[w1, p1] == rid[w2, p2]
            continue
        # leaving a ring must climb the dimension order strictly (DOR
        # corrects the first nonzero dimension; low dims stay zero)
        assert p2 // 2 > p1 // 2, (name, (w1, p1), (w2, p2))
        quotient.add((rid[w1, p1], rid[w2, p2]))
    # ring-quotient graph must topologically sort (Kahn) — acyclicity
    nodes = {r for e in quotient for r in e}
    indeg = {r: 0 for r in nodes}
    succ = {r: [] for r in nodes}
    for a, b in quotient:
        indeg[b] += 1
        succ[a].append(b)
    ready = [r for r in nodes if indeg[r] == 0]
    seen = 0
    while ready:
        r = ready.pop()
        seen += 1
        for b in succ[r]:
            indeg[b] -= 1
            if indeg[b] == 0:
                ready.append(b)
    assert seen == len(nodes), f"{name}: escape ring-quotient has a cycle"


# ---------------------------------------------------------------------------
# credit accounting invariants, slot by slot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("credits", [None, 3])
def test_credit_invariant_per_slot(credits):
    """credit[w,p,v] == credit_init − occupancy(w,p,v) after EVERY slot,
    never below 0, never above the advertised window."""
    g = Torus(4, 4)
    t = build_tables(g)
    ctx = _make_ctx(t, g, "uniform", 0, 4, Scenario(), vcs=2,
                    credits=credits)
    state = _init_state(ctx, 0.6, "batched")
    slots = 48
    tr = _make_traffic(ctx, state, jax.random.PRNGKey(7), slots)
    step = jax.jit(_make_slot_step_vc_batched(ctx, 0))
    cinit = ctx["credit_init"]
    for s in range(slots):
        state, _ = step(state, {k: v[s] for k, v in tr.items()})
        credit = np.asarray(state["credit"])
        occ = (np.asarray(state["birth"]) >= 0).sum(axis=3)
        assert (credit == cinit - occ).all(), f"slot {s}"
        assert credit.min() >= 0 and credit.max() <= cinit, f"slot {s}"
    assert int(state["delivered"]) > 0    # the run actually moved traffic


# ---------------------------------------------------------------------------
# per-VC conservation + batched/reference oracle agreement
# ---------------------------------------------------------------------------

_T44 = Torus(4, 4)
_T44_TAB = build_tables(_T44)
_FAULTS = Scenario(dead_links=((5, 0), (9, 2)), policy="adaptive")


def _vc_run(impl, vcs=2, scenario=None, load=0.4, credits=None):
    # warmup=0: the conservation ledger only balances when every
    # injection is counted (warmup-gated counters skip pre-warmup births)
    cfg = SimConfig(slots=160, warmup=0, seed=5, tables=_T44_TAB,
                    impl=impl, vcs=vcs, credits=credits, scenario=scenario)
    return simulate(_T44, "uniform", load, config=cfg)


@pytest.mark.parametrize("impl", ["batched", "reference"])
@pytest.mark.parametrize("scenario", [None, _FAULTS])
@pytest.mark.parametrize("vcs", [2, 3])
def test_vc_conservation(impl, scenario, vcs):
    r = _vc_run(impl, vcs=vcs, scenario=scenario)
    assert r.delivered + r.in_flight + r.dropped == r.injected
    assert r.vc_delivered.shape == (vcs,)
    # packets switch lanes hop to hop, so only the V-sums are conserved
    assert int(r.vc_delivered.sum()) == r.delivered
    assert int(r.vc_injected.sum()) == r.injected + r.dropped
    assert int(r.vc_in_flight.sum()) == r.in_flight
    assert r.delivered > 0


def test_vc_batched_vs_reference_statistical():
    """Independent arbitration streams, same physics: accepted load of
    the two VC implementations agrees within a loose band."""
    a = _vc_run("batched", load=0.5)
    b = _vc_run("reference", load=0.5)
    assert abs(a.accepted_load - b.accepted_load) < 0.06, (
        a.accepted_load, b.accepted_load)


# ---------------------------------------------------------------------------
# V=1 bitwise contract (pre-PR goldens, recorded at PR 6)
# ---------------------------------------------------------------------------

_GOLDEN_CELLS = {
    "t444_uniform": (Torus(4, 4, 4), "uniform", 0.45,
                     dict(slots=192, warmup=32, seed=1), None),
    "t444_antipodal": (Torus(4, 4, 4), "antipodal", 0.3,
                       dict(slots=192, warmup=32, seed=2), None),
    "ring_escape": (Torus(8), "uniform", 0.25,
                    dict(slots=256, warmup=0, seed=3),
                    Scenario(dead_links=((0, 0),), policy="escape")),
    "t44_adaptive_faults": (Torus(4, 4), "uniform", 0.4,
                            dict(slots=160, warmup=16, seed=5),
                            Scenario(dead_links=((5, 0), (9, 2)),
                                     policy="adaptive")),
    "t44_deadnode_dor": (Torus(4, 4), "uniform", 0.35,
                         dict(slots=160, warmup=16, seed=7),
                         Scenario(dead_nodes=(6,), policy="adaptive")),
    "fcc2_hist": (FCC(2), "uniform", 0.4,
                  dict(slots=160, warmup=16, seed=4, hist_bins=24), None),
}

# every counter of the pre-VC batched simulator on the cells above —
# recorded at ef9ac4d (PR 6), BEFORE the VC router landed.  vcs=1 +
# credits=None must keep reproducing them bit for bit.  They were drawn
# from jax's original threefry stream; jax >= 0.5 defaults to the
# partitionable stream, which draws other traffic from the same seed, so
# the contract runs under the stream the goldens were recorded on.
_GOLDENS = {
    "t444_uniform": dict(delivered=4604, injected=4585, dropped=0,
                         in_flight=88, lat_count=4497,
                         accepted_load=0.449609375,
                         avg_latency_cycles=69.59306204136091),
    "t444_antipodal": dict(delivered=3160, injected=3139, dropped=0,
                           in_flight=120, lat_count=3019,
                           accepted_load=0.30859375,
                           avg_latency_cycles=122.10135806558463),
    "ring_escape": dict(delivered=175, injected=235, dropped=0,
                        in_flight=60, lat_count=175,
                        accepted_load=0.08544921875,
                        avg_latency_cycles=73.32571428571428),
    "t44_adaptive_faults": dict(delivered=810, injected=875, dropped=0,
                                in_flight=84, lat_count=795,
                                accepted_load=0.3515625,
                                avg_latency_cycles=52.548427672955974),
    "t44_deadnode_dor": dict(delivered=731, injected=743, dropped=0,
                             in_flight=30, lat_count=715,
                             accepted_load=0.3172743055555556,
                             avg_latency_cycles=51.55804195804196),
    "fcc2_hist": dict(delivered=908, injected=913, dropped=0,
                      in_flight=15, lat_count=898,
                      accepted_load=0.3940972222222222,
                      avg_latency_cycles=44.0445434298441),
}
_FCC2_HIST = np.zeros(24, np.int64)
_FCC2_HIST[2:6] = (375, 390, 113, 20)


def golden_stream():
    """The PRNG stream `_GOLDENS` were recorded on."""
    return jax.threefry_partitionable(False)


@pytest.mark.parametrize("cell", sorted(_GOLDEN_CELLS))
def test_v1_bitwise_matches_pre_vc_goldens(cell):
    g, pattern, load, kw, scen = _GOLDEN_CELLS[cell]
    with golden_stream():
        r = simulate(g, pattern, load, scenario=scen, **kw)
        # the SimConfig path compiles the same program: identical results
        r2 = simulate(g, pattern, load, config=SimConfig(scenario=scen, **kw))
    gold = _GOLDENS[cell]
    for k, v in gold.items():
        got = getattr(r, k)
        if isinstance(v, float):
            assert got == v, (cell, k, got, v)     # bitwise, not approx
        else:
            assert int(got) == v, (cell, k, got, v)
    assert r.vc_delivered is None and r.vc_in_flight is None
    if "hist_bins" in kw:
        np.testing.assert_array_equal(r.latency_hist, _FCC2_HIST)
    assert (r2.delivered, r2.injected, r2.accepted_load) == \
        (r.delivered, r.injected, r.accepted_load)


# ---------------------------------------------------------------------------
# the n=1-ring cell: escape lane vs the misroute heuristic
# ---------------------------------------------------------------------------

def test_ring_dead_link_vc_beats_escape_misroute():
    """T(8) with one dead link was the ROADMAP livelock caveat: the V=1
    "escape" policy ping-pongs packets trapped against the fault (60 of
    235 injected never arrive).  The VC router's restricted-DOR escape
    lane routes them out — strictly more deliveries at the same offered
    load, with conservation intact."""
    ring = Torus(8)
    rt = build_tables(ring)
    cfg = SimConfig(slots=256, warmup=0, seed=3, tables=rt)
    with golden_stream():       # the stream the pinned caveat was drawn on
        esc = simulate(ring, "uniform", 0.25, config=cfg.replace(
            scenario=Scenario(dead_links=((0, 0),), policy="escape")))
        vc = simulate(ring, "uniform", 0.25, config=cfg.replace(
            scenario=Scenario(dead_links=((0, 0),), policy="adaptive"),
            vcs=2))
    assert esc.delivered == 175                    # the caveat, pinned
    assert vc.delivered >= 2 * esc.delivered
    assert vc.accepted_load > 2 * esc.accepted_load
    assert vc.delivered + vc.in_flight + vc.dropped == vc.injected


# ---------------------------------------------------------------------------
# livelock/starvation property: low-load packets always drain
# ---------------------------------------------------------------------------

@settings(max_examples=4)
@given(seed=st.integers(0, 5), link=st.sampled_from([(0, 0), (3, 1), (9, 2)]))
def test_no_starvation_at_low_load(seed, link):
    """At low load every injected packet is eventually delivered: running
    the same seed twice as long must not accumulate in-flight packets
    (bounded drain ⇒ no livelocked/starved packet under the VC router)."""
    scen = Scenario(dead_links=(link,), policy="adaptive")
    cfg = SimConfig(warmup=0, seed=seed, tables=_T44_TAB, vcs=2,
                    scenario=scen, slots=200)
    short = simulate(_T44, "uniform", 0.05, config=cfg)
    long = simulate(_T44, "uniform", 0.05, config=cfg.replace(slots=400))
    bound = 2 * _T44.order                         # transit residue only
    assert short.in_flight <= bound
    assert long.in_flight <= bound
    assert long.delivered > short.delivered        # traffic keeps moving
    assert long.delivered + long.in_flight + long.dropped == long.injected


# ---------------------------------------------------------------------------
# credit_vc_select unit behavior
# ---------------------------------------------------------------------------

def test_credit_vc_select_prefers_max_credit_adaptive_lane():
    rec = np.array([2, -1])                        # productive: +x (0), -y (3)
    link_ok = np.ones(4, bool)
    credit = np.zeros((4, 2), np.int32)
    credit[3, 1] = 3                               # best adaptive candidate
    credit[0, 1] = 1
    port, vc = credit_vc_select(rec, link_ok, credit, "adaptive")
    assert (int(port), int(vc)) == (3, 1)


def test_credit_vc_select_falls_back_to_escape():
    rec = np.array([2, -1])
    link_ok = np.ones(4, bool)
    credit = np.zeros((4, 2), np.int32)            # no adaptive credit
    port, vc = credit_vc_select(rec, link_ok, credit, "adaptive")
    assert (int(port), int(vc)) == (0, 0)          # DOR port, escape lane
    # a dead productive port drops out of the adaptive candidate set
    credit[:, 1] = 3
    live = np.array([False, True, True, True])     # +x dead, -y alive
    port, vc = credit_vc_select(rec, live, credit, "adaptive")
    assert (int(port), int(vc)) == (3, 1)          # only live minimal port


def test_credit_vc_select_dor_stays_dimension_ordered():
    rec = np.array([0, 3])
    credit = np.arange(8, dtype=np.int32).reshape(4, 2)
    port, vc = credit_vc_select(rec, np.ones(4, bool), credit, "dor")
    assert int(port) == 2                          # first nonzero dim, +y
    assert int(vc) == 1                            # max-credit lane of it


def test_credit_vc_select_rejects_v1():
    with pytest.raises(ValueError, match="V >= 2"):
        credit_vc_select(np.array([1, 0]), np.ones(4, bool),
                         np.ones((4, 1), np.int32), "adaptive")


# ---------------------------------------------------------------------------
# credit_vc_select is exactly the documented rule (argmax formulation)
# ---------------------------------------------------------------------------

def _credit_vc_select_argmax(rec, link_ok, credit, policy, rot, port_geom,
                             escape_fallback):
    """The documented rule as argmax reduces over the candidate axes and
    element gathers: the oracle of `credit_vc_select`'s select chains."""
    import jax.numpy as jnp
    rec = jnp.asarray(rec)
    n = rec.shape[-1]
    V = credit.shape[-1]
    if port_geom is None:
        P = 2 * n
        prod = jnp.stack([rec > 0, rec < 0],
                         axis=-1).reshape(rec.shape[:-1] + (P,))
        dor = jnp.where(prod.any(-1), jnp.argmax(prod, axis=-1),
                        P).astype(jnp.int32)
        ortho0 = rec == 0
        ortho = jnp.stack([ortho0, ortho0], axis=-1).reshape(prod.shape)
    else:
        pdim, psgn, pspan = (jnp.asarray(a, jnp.int32) for a in port_geom)
        P = int(pdim.shape[0])
        rv = jnp.take(rec, pdim, axis=-1)
        prod = (psgn * rv > 0) & (pspan <= jnp.abs(rv))
        d0 = jnp.argmax(rec != 0, axis=-1).astype(jnp.int32)
        cand = prod & (pdim == d0[..., None])
        okx = jnp.broadcast_to(link_ok, prod.shape)
        dkey = jnp.where(cand, okx.astype(jnp.int32) * 4096 + pspan, -1)
        dor = jnp.where(cand.any(-1), jnp.argmax(dkey, axis=-1),
                        P).astype(jnp.int32)
        ortho = rv == 0
    credit = jnp.broadcast_to(credit, rec.shape[:-1] + (P, V))
    rot = jnp.asarray(rot, jnp.int32)
    if policy == "dor":
        cp = jnp.take_along_axis(
            credit, jnp.minimum(dor, P - 1)[..., None, None], axis=-2
        )[..., 0, :]
        key = cp.astype(jnp.int32) * V + (jnp.arange(V) + rot) % V
        return dor, jnp.argmax(key, axis=-1).astype(jnp.int32)
    ok = jnp.broadcast_to(link_ok, prod.shape)
    C = P * (V - 1)
    elig = (prod & ok)[..., None] & (credit[..., 1:] > 0)
    flat = elig.reshape(elig.shape[:-2] + (C,))
    cred_flat = credit[..., 1:].reshape(flat.shape).astype(jnp.int32)
    perm = (jnp.arange(C) + rot) % C
    key = jnp.where(flat, cred_flat * C + perm, -1)
    best = jnp.argmax(key, axis=-1)
    has = jnp.take_along_axis(key, best[..., None], axis=-1)[..., 0] >= 0
    esc = dor
    if escape_fallback:
        def first(mask):
            return jnp.where(mask.any(-1), jnp.argmax(mask, axis=-1),
                             P).astype(jnp.int32)

        dor_live = (dor < P) & jnp.take_along_axis(
            ok, jnp.minimum(dor, P - 1)[..., None], axis=-1)[..., 0]
        mis = first(ortho & ok)
        mis = jnp.where(mis < P, mis, first(ok))
        mis = jnp.where(dor < P, mis, jnp.int32(P))
        esc = jnp.where(dor_live, dor, mis)
    port = jnp.where(has, (best // (V - 1)).astype(jnp.int32), esc)
    vc = jnp.where(has, (best % (V - 1) + 1).astype(jnp.int32), 0)
    return port, vc


def _selection_inputs(rng, lead, n, P, V, view):
    """Records with zero rows and extreme components, link masks with
    dead ports, and credits with equal values, all-zero rows and all-zero
    views.  `view` gives the credit and link masks per node only, as the
    VC step passes them: (N, 1, 1, 1, P[, V]) against (N, P, V, Q, n)."""
    rec = rng.integers(-4, 5, lead + (n,)).astype(np.int8)
    rec[rng.random(lead + (n,)) < 0.35] = 0
    rec[rng.random(lead) < 0.1] = 0                     # nothing to route
    rec[rng.random(lead + (n,)) < 0.02] = -128
    rec[rng.random(lead + (n,)) < 0.02] = 127
    per = (lead[:1] + (1,) * (len(lead) - 1)) if view else lead
    link_ok = rng.random(per + (P,)) < 0.7
    credit = rng.integers(0, 3, per + (P, V)).astype(np.int32)
    credit[rng.random(per) < 0.2] = 0                  # no credit at all
    credit[rng.random(per) < 0.2] = 2                  # every credit tied
    return rec, link_ok, credit


@pytest.mark.parametrize("shape", ["record", "batch", "queue_slots"])
@pytest.mark.parametrize("express", [False, True])
@pytest.mark.parametrize("escape_fallback", [False, True])
@pytest.mark.parametrize("V", [2, 3])
@pytest.mark.parametrize("policy", ["dor", "adaptive", "escape"])
def test_credit_vc_select_is_the_argmax_rule(policy, V, escape_fallback,
                                             express, shape):
    """`credit_vc_select` returns exactly what argmax reduces and element
    gathers over the candidate axes return, value for value and as int32:
    equal-credit ties under several `rot`, no credit (the escape lane),
    zero records (the sentinel port), dead DOR ports, V = 3 and an
    express port axis."""
    if express:
        n = 2
        ls = LinkSpec(express=((0, 2, 1), (0, 4, 2)))
        geom = (ls.port_dims(n), ls.port_signs(n), ls.port_spans(n))
        P = ls.num_ports(n)
    else:
        n, geom = 3, None
        P = 2 * n
    rng = np.random.default_rng([V, escape_fallback, express,
                                 ["dor", "adaptive", "escape"].index(policy),
                                 ["record", "batch", "queue_slots"].index(shape)])
    if shape == "record":
        cases = [_selection_inputs(rng, (), n, P, V, False) for _ in range(6)]
    elif shape == "batch":
        cases = [_selection_inputs(rng, (96,), n, P, V, False)]
    else:
        cases = [_selection_inputs(rng, (8, P, V, 2), n, P, V, True)]
    for rec, link_ok, credit in cases:
        for rot in (0, 1, 7, 1000003):
            want = _credit_vc_select_argmax(rec, link_ok, credit, policy, rot,
                                            geom, escape_fallback)
            got = credit_vc_select(rec, link_ok, credit, policy, rot=rot,
                                   port_geom=geom,
                                   escape_fallback=escape_fallback)
            for g, w in zip(got, want):
                assert g.dtype == np.int32 and g.shape == w.shape
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

