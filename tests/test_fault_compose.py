"""ISSUE 9 (robustness): fault tolerance composes with everything.

The full fault-injection matrix is now
``{pristine, Scenario, FaultSchedule} × {V=1, V≥2} × {trivial, weighted,
pillar, express}`` with only the fused kernel's documented exclusions
remaining (docs/simulator.md, "Feature-compatibility matrix").  This
module pins the composition contracts:

  * **VC × FaultSchedule bitwise bridge** — a degenerate single-epoch
    schedule run at ``vcs ≥ 2`` equals the static `Scenario` VC run bit
    for bit (PR 5's bridge, lifted to the credit-flow router);
  * **credit accounting under churn** — ``credit == credit_init −
    occupancy`` at EVERY slot of a scheduled VC run, including slots
    where a node death drops enqueued phits across all lanes (the freed
    occupancy's downstream credits are restored in the same slot);
  * **express channels die and repair like any link** — zero
    dead-channel crossings over the extended 2n+2X port axis, per-slot
    conservation through death/repair, and the greedy weighted-DOR
    record falls back to base-lattice ports while an express hop is
    masked;
  * **fault-aware escape under VCs** — with DOR's escape port dead,
    `credit_vc_select` falls back to the PR 3 escape-policy misroute on
    VC0 only; the escape-CDG stays acyclic on faulted cells because the
    fallback only ever crosses LIVE channels (re-enumerated here in
    tests/test_vc_router.py style);
  * **single source of combo rejection** — every remaining unsupported
    cell raises the same actionable message from `SimConfig` and from
    the internal planner paths;
  * **composition property** (propcheck) — random (vcs, dim_weights,
    express, event-list) draws hold per-slot conservation, zero
    dead-channel crossings, and per-VC conservation V-sums.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (FaultSchedule, LinkSpec, Scenario, SimConfig,
                        Torus)
from repro.core.sim_config import validate_feature_combo
from repro.core.simulation import (_init_state, _make_ctx,
                                   _make_slot_step_vc_batched,
                                   _make_traffic, build_tables,
                                   schedule_recovery_slots, simulate,
                                   simulate_schedule_sweep)
from repro.core.fault_schedule import ensure_compiled

G = Torus(4, 4)
TAB = build_tables(G)
KW = dict(slots=96, warmup=0, seed=2, tables=TAB)


def check_timeline(r):
    tl = r.timeline
    assert tl is not None
    assert tl.conservation_ok(), tl.conservation_violations()
    assert tl.dead_crossings.sum() == 0
    assert tl.delivered[-1] == r.delivered
    assert tl.injected[-1] == r.injected
    assert tl.dropped[-1] == r.dropped
    assert tl.in_flight[-1] == r.in_flight


# ---------------------------------------------------------------------------
# VC × FaultSchedule: the E=1 bitwise bridge + per-slot accounting
# ---------------------------------------------------------------------------

_VC_CELLS = [
    (Scenario.random_link_faults(G, 2, seed=3, policy="dor"), "uniform"),
    (Scenario.random_link_faults(G, 3, seed=4, policy="adaptive"),
     "randompairings"),
    (Scenario.random_link_faults(G, 2, seed=5, policy="escape"),
     "uniform"),
    (Scenario.random_node_faults(G, 2, seed=6, policy="adaptive"),
     "uniform"),
]


@pytest.mark.parametrize("impl", ["batched", "reference"])
@pytest.mark.parametrize("scen,pattern", _VC_CELLS,
                         ids=[f"{s.policy}-{p}" for s, p in _VC_CELLS])
def test_vc_single_epoch_schedule_bitwise_equals_static(scen, pattern,
                                                        impl):
    """E=1 schedule ≡ static scenario at vcs=2, counter for counter —
    PR 5's bridge extended to the credit-flow router on both the traced
    and the baked-mask implementation."""
    a = simulate(G, pattern, 0.45, scenario=scen, vcs=2, impl=impl, **KW)
    b = simulate(G, pattern, 0.45,
                 schedule=FaultSchedule.from_scenario(scen), vcs=2,
                 impl=impl, **KW)
    for f in ("delivered", "injected", "dropped", "in_flight",
              "accepted_load", "lat_count"):
        assert getattr(a, f) == getattr(b, f), f
    np.testing.assert_array_equal(a.vc_delivered, b.vc_delivered)
    np.testing.assert_array_equal(a.vc_injected, b.vc_injected)
    check_timeline(b)


@pytest.mark.parametrize("vcs", [2, 3])
def test_vc_schedule_conservation_through_flap(vcs):
    sched = FaultSchedule.link_flap((0, 0), 16, 56, policy="adaptive")
    r = simulate(G, "uniform", 0.5, schedule=sched, vcs=vcs, **KW)
    check_timeline(r)
    assert int(r.vc_delivered.sum()) == r.delivered
    assert int(r.vc_injected.sum()) == r.injected + r.dropped
    assert int(r.vc_in_flight.sum()) == r.in_flight


def test_vc_schedule_node_death_drops_all_lanes():
    """A killed node's enqueued phits drop across every lane the slot it
    dies; the ledger balances at every slot, not just run end."""
    sched = FaultSchedule(events=((20, "node_down", 5),
                                  (60, "node_up", 5)),
                          base=Scenario(policy="adaptive"))
    r = simulate(G, "uniform", 0.5, schedule=sched, vcs=2, **KW)
    check_timeline(r)
    assert r.dropped > 0          # the death actually cost packets
    compiled = ensure_compiled(sched, G, KW["slots"])
    death = compiled.starts[1]
    tl = r.timeline
    # the drop ledger moves at (or after: dead-destination refusals) the
    # death slot and never before it
    assert tl.dropped[death - 1] == 0
    assert tl.dropped[-1] == r.dropped


@pytest.mark.parametrize("credits", [None, 3])
def test_vc_credit_invariant_per_slot_under_schedule(credits):
    """credit[w,p,v] == credit_init − occupancy(w,p,v) after EVERY slot
    of a scheduled run — including the node-death slots where dropped
    occupancy must hand its credits back."""
    sched = FaultSchedule(events=((12, "node_down", 5),
                                  (30, "node_up", 5),
                                  (36, "link_down", (1, 2))),
                          base=Scenario(policy="adaptive"))
    compiled = sched.compile(G, 48)
    ctx = _make_ctx(TAB, G, "uniform", 0, 4, schedule=compiled, vcs=2,
                    credits=credits)
    state = _init_state(ctx, 0.6, "batched")
    slots = 48
    tr = _make_traffic(ctx, state, jax.random.PRNGKey(7), slots)
    tr["epoch"] = state["slot2epoch"]
    step = jax.jit(_make_slot_step_vc_batched(ctx, 0))
    cinit = ctx["credit_init"]
    for s in range(slots):
        state, _ = step(state, {k: v[s] for k, v in tr.items()})
        credit = np.asarray(state["credit"])
        occ = (np.asarray(state["birth"]) >= 0).sum(axis=3)
        assert (credit == cinit - occ).all(), f"slot {s}"
        assert credit.min() >= 0 and credit.max() <= cinit, f"slot {s}"
    assert int(state["delivered"]) > 0


def test_vc_schedule_sweep_lane_bitwise_vs_single():
    """Sweep lane k at vcs=2 ≡ the single-schedule run (common random
    numbers), and a static lane ≡ the scenario run."""
    scen = Scenario(dead_links=((5, 0),), policy="adaptive")
    flap = FaultSchedule.link_flap((9, 2), 16, 48,
                                   base=Scenario(policy="adaptive"))
    rows = simulate_schedule_sweep(G, "uniform", [scen, flap],
                                   loads=(0.45,), vcs=2, **KW)
    single = simulate(G, "uniform", 0.45, schedule=flap, vcs=2, **KW)
    static = simulate(G, "uniform", 0.45, scenario=scen, vcs=2, **KW)
    assert rows[1][0].delivered == single.delivered
    assert rows[1][0].injected == single.injected
    assert rows[0][0].delivered == static.delivered
    for row in rows:
        check_timeline(row[0])


# ---------------------------------------------------------------------------
# faults × express overlays: the extended 2n+2X port axis
# ---------------------------------------------------------------------------

_XLS = LinkSpec(express=((0, 2, 1),))


def test_express_link_death_and_repair():
    """An express channel dies and repairs like any link: conservation
    and the dead-crossing audit hold per slot over the extended axis,
    and traffic falls back to base-lattice ports while it is down."""
    sched = FaultSchedule.link_flap((0, 4), 16, 56)
    r = simulate(G, "uniform", 0.45, schedule=sched, links=_XLS, **KW)
    check_timeline(r)
    pristine = simulate(G, "uniform", 0.45, links=_XLS, **KW)
    assert r.delivered > 0.9 * pristine.delivered   # graceful, not broken


def test_express_scenario_masks_extended_axis():
    scen = Scenario(dead_links=((0, 4),))
    r = simulate(G, "uniform", 0.45, scenario=scen, links=_XLS, **KW)
    assert r.delivered > 0
    # the dead express channel is never crossed (link_use audit covers
    # the full extended axis for non-trivial scenarios)
    assert r.link_use is not None and r.link_use.shape[1] == 6
    assert r.link_use[0, 4] == 0 and r.link_use[0, 5] > 0


def test_express_dead_node_kills_its_express_ports():
    scen = Scenario(dead_nodes=(5,))
    r = simulate(G, "uniform", 0.45, scenario=scen, links=_XLS, **KW)
    assert r.link_use[5].sum() == 0
    assert r.delivered + r.in_flight + r.dropped == r.injected


def test_express_faults_compose_with_vcs():
    scen = Scenario(dead_links=((0, 4),), policy="adaptive")
    r = simulate(G, "uniform", 0.45, scenario=scen, links=_XLS, vcs=2,
                 **KW)
    assert r.delivered + r.in_flight + r.dropped == r.injected
    assert int(r.vc_delivered.sum()) == r.delivered
    # and under a timeline too
    sched = FaultSchedule.link_flap((0, 4), 16, 56,
                                    base=Scenario(policy="adaptive"))
    rt = simulate(G, "uniform", 0.45, schedule=sched, links=_XLS, vcs=2,
                  **KW)
    check_timeline(rt)


def test_scenario_link_ok_extends_and_validates_ports():
    ok = Scenario(dead_links=((0, 4),)).link_ok(G, _XLS)
    assert ok.shape == (G.order, 6)
    assert not ok[0, 4]
    v = int(_XLS.extended_neighbors(G)[0, 4])
    assert not ok[v, 5]          # undirected: far endpoint's paired port
    with pytest.raises(ValueError, match="only 4 ports"):
        Scenario(dead_links=((0, 4),)).link_ok(G)
    with pytest.raises(ValueError, match="express-port events"):
        FaultSchedule(events=((5, "link_down", (0, 4)),)).compile(G, 32)


# ---------------------------------------------------------------------------
# fault-aware escape under VCs: the VC0 misroute fallback
# ---------------------------------------------------------------------------

def test_credit_vc_select_escape_fallback_unit():
    """When the DOR escape port is dead and no adaptive lane has credit,
    the fallback misroutes through a live record-zero-dimension port on
    VC0 only; on a live DOR port the flag is bitwise-invisible."""
    import jax.numpy as jnp

    from repro.core.routing_engine import credit_vc_select

    rec = jnp.array([[2, 0]], dtype=jnp.int32)       # DOR dim 0, port 0
    link_ok = jnp.array([[False, True, True, True]])
    credit = jnp.zeros((1, 4, 2), jnp.int32).at[:, :, 0].set(4)
    p0, v0 = credit_vc_select(rec, link_ok, credit, policy="escape",
                              escape_fallback=False)
    p1, v1 = credit_vc_select(rec, link_ok, credit, policy="escape",
                              escape_fallback=True)
    # without the flag the escape request still names the dead port
    assert (int(p0[0]), int(v0[0])) == (0, 0)
    # with it: a live orthogonal port, still VC0
    assert int(p1[0]) in (2, 3) and int(v1[0]) == 0
    live = jnp.ones_like(link_ok)
    pa, va = credit_vc_select(rec, live, credit, policy="escape",
                              escape_fallback=False)
    pb, vb = credit_vc_select(rec, live, credit, policy="escape",
                              escape_fallback=True)
    assert (int(pa[0]), int(va[0])) == (int(pb[0]), int(vb[0]))


def test_vc_escape_fallback_drains_stale_cohort():
    """Records are written fault-aware at injection, so a STATIC dead
    link never strands a VC packet — the fallback earns its keep when a
    link dies mid-run under packets already in flight with stale
    records.  Under 'adaptive' that cohort wedges (its escape port is
    dead and stays dead); the 'escape' fallback misroutes it on VC0 and
    in_flight returns to its pre-death level."""
    g = Torus(8, 8)
    kw = dict(slots=384, warmup=0, seed=3, vcs=2)

    def run(pol):
        sched = FaultSchedule(events=((96, "link_down", (0, 0)),),
                              base=Scenario(policy=pol))
        return simulate(g, "uniform", 0.3, schedule=sched, **kw)

    esc, ad = run("escape"), run("adaptive")
    check_timeline(esc)
    check_timeline(ad)
    pre = int(esc.timeline.injected[90] - esc.timeline.delivered[90]
              - esc.timeline.dropped[90])
    # escape drains back toward the pre-death baseline; adaptive strands
    # the stale cohort on top of it
    assert esc.in_flight <= 1.3 * pre
    assert ad.in_flight > esc.in_flight


def test_vc_escape_fallback_never_crosses_dead_channels():
    scen = Scenario(dead_links=((0, 0), (3, 2)), policy="escape")
    sched = FaultSchedule.from_scenario(scen)
    r = simulate(G, "uniform", 0.5, schedule=sched, vcs=2, **KW)
    check_timeline(r)


def test_escape_cdg_acyclic_on_faulted_cells():
    """Duato's argument survives the fallback: VC0's restricted-DOR
    transitions still only continue a ring or climb dimensions, and the
    misroute egress is always a LIVE channel, so removing dead channels
    from the escape CDG cannot create a cycle.  Enumerate the faulted
    CDG (test_vc_router style) and topologically sort its ring
    quotient."""
    scen = Scenario(dead_links=((5, 0), (9, 2)), policy="escape")
    link_ok = scen.link_ok(G)
    t = TAB
    nbr, n, N = t.neighbors, t.n, t.N
    edges = set()
    for table in (t.records_a, t.records_b):
        for src in range(N):
            for di in range(N):
                rec = table[di].copy()
                cur, prev = src, None
                guard = 0
                while np.abs(rec).sum() > 0 and guard < 8 * N:
                    guard += 1
                    d = int(np.argmax(np.abs(rec) > 0))
                    s = int(rec[d])
                    p = 2 * d + (s < 0)
                    if not link_ok[cur, p]:
                        break     # escape lane blocked: the fallback
                                  # misroutes on an adaptive-score port,
                                  # leaving the escape CDG entirely
                    ch = (cur, p)
                    if prev is not None:
                        edges.add((prev, ch))
                    cur = int(nbr[cur, p])
                    rec[d] -= int(np.sign(s))
                    prev = ch
    assert edges
    # every surviving escape transition climbs dimensions or stays on
    # its directed ring — the faulted CDG is a sub-DAG of the pristine
    for (w1, p1), (w2, p2) in edges:
        assert link_ok[w1, p1] and link_ok[w2, p2]
        assert p1 == p2 or p2 // 2 > p1 // 2


# ---------------------------------------------------------------------------
# centralized combo rejection: one message everywhere
# ---------------------------------------------------------------------------

_EXCLUDED = [
    (dict(impl="fused", vcs=2), "V=1-only",
     dict(impl="fused", vcs=2)),
    (dict(impl="fused", links_trivial=False), "weight-1/no-overlay",
     dict(impl="fused", links=LinkSpec(dim_weights=(1, 2)))),
    (dict(express=True, vcs=1, policy="adaptive"), "greedy",
     dict(links=LinkSpec(express=((0, 2, 1),)),
          scenario=Scenario(dead_links=((0, 0),), policy="adaptive"))),
    (dict(express=True, vcs=1, policy="escape"), "greedy",
     dict(links=LinkSpec(express=((0, 2, 1),)),
          scenario=Scenario(policy="escape"))),
]


@pytest.mark.parametrize("combo,match,cfg_kw", _EXCLUDED,
                         ids=["fused-vcs", "fused-links",
                              "express-adaptive", "express-escape"])
def test_unsupported_cells_raise_same_message_everywhere(combo, match,
                                                         cfg_kw):
    """`validate_feature_combo` is the single source: the SimConfig
    surface and the internal planner raise the IDENTICAL message."""
    with pytest.raises(ValueError, match=match) as direct:
        validate_feature_combo(**combo)
    with pytest.raises(ValueError, match=match) as via_cfg:
        SimConfig(**cfg_kw)
    assert str(direct.value) == str(via_cfg.value)


def test_make_ctx_rejects_express_adaptive_like_simconfig():
    with pytest.raises(ValueError, match="greedy"):
        _make_ctx(TAB, G, "uniform", 0, 4,
                  Scenario(dead_links=((0, 0),), policy="adaptive"),
                  links=LinkSpec(express=((0, 2, 1),)))


# ---------------------------------------------------------------------------
# recovery telemetry on VC scheduled runs
# ---------------------------------------------------------------------------

def test_recovery_slots_on_vc_link_flap():
    sched = FaultSchedule.link_flap((0, 0), 96, 224,
                                    base=Scenario(policy="adaptive"))
    # the outage picture below was calibrated on seed 3 of jax's original
    # threefry stream (the default before jax 0.5)
    with jax.threefry_partitionable(False):
        r = simulate(G, "uniform", 0.6, slots=384, warmup=0, seed=3,
                     tables=TAB, vcs=2, schedule=sched, hist_bins=32)
    tl = r.timeline
    assert tl.lat_hist is not None and tl.lat_hist.shape == (384, 32)
    check_timeline(r)
    rec = schedule_recovery_slots(r, sched, q=0.99, window=48,
                                  slack_cycles=16.0)
    assert rec is not None and 0 <= rec < 384 - 224
    # the p99 trace visibly degrades during the outage
    trace = tl.latency_percentile_trace(q=0.99, window=48)
    assert np.nanmax(trace[96:224]) >= np.nanmax(trace[:96])


# ---------------------------------------------------------------------------
# the propcheck composition property
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(
    vcs=st.sampled_from([1, 2, 3]),
    wy=st.sampled_from([1, 2]),
    express=st.booleans(),
    events=st.lists(
        st.tuples(st.integers(min_value=0, max_value=63),
                  st.sampled_from(["link_down", "link_up", "node_down",
                                   "node_up"]),
                  st.integers(min_value=0, max_value=15),
                  st.integers(min_value=0, max_value=3)),
        min_size=1, max_size=6),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_composition_property(vcs, wy, express, events, seed):
    """Random (vcs, dim_weights, express, FaultSchedule) draws hold the
    per-slot ledger, cross no dead channel (express ports included), and
    keep per-VC conservation V-sums."""
    ls = LinkSpec(dim_weights=(1, wy),
                  express=((0, 2, 1),) if express else ())
    evs = []
    for slot, kind, node, port in events:
        if kind.startswith("link"):
            evs.append((slot, kind, (node, port)))   # base ports only:
        elif node != 0:                              # events may also be
            evs.append((slot, kind, node))           # no-ops — fine
    sched = FaultSchedule(events=tuple(evs),
                          base=Scenario(policy="adaptive" if vcs > 1
                                        else "dor"))
    r = simulate(G, "uniform", 0.45, slots=64, warmup=0, seed=seed,
                 tables=TAB, vcs=vcs, schedule=sched, links=ls)
    tl = r.timeline
    assert tl.conservation_ok(), tl.conservation_violations()
    assert tl.dead_crossings.sum() == 0
    if vcs > 1:
        assert int(r.vc_delivered.sum()) == r.delivered
        # injection-drops are already inside BOTH counters; queue drops
        # (node death) are in neither — so the V-sum matches `injected`
        # exactly, with no `dropped` correction
        assert int(r.vc_injected.sum()) == r.injected
        assert int(r.vc_in_flight.sum()) == r.in_flight
