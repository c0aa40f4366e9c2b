"""Differential test suite for the fault-injection & adaptive-routing
scenario engine (ISSUE 3).

For every (scenario × pattern) cell on T(4,4,4,4) — the acceptance
topology — plus small RTT/FCC/BCC crystal cells, the port-batched
simulator must agree with the per-port reference oracle on the whole
load curve (seed-averaged, ±5 % per point), and every run must satisfy
the exact invariants:

  * conservation — delivered + in-flight + dropped == injected (integer
    equality, warmup=0 so every slot is counted),
  * dead-channel audit — `SimResult.link_use` records every crossing;
    masked channels must show exactly zero,
  * adaptivity dominance — on a faulted graph, minimal-adaptive accepted
    load at saturation ≥ DOR's (which blocks on dead required channels),
  * escape routing — when every productive port is dead the escape
    policy misroutes and still delivers (a ring with a dead link is the
    sharpest case: adaptive wedges, escape goes the long way round),
  * multi-seed axis — same seeds ⇒ bitwise-identical curves; more seeds
    ⇒ tighter CI; the whole (loads × seeds) sweep is ONE device program
    (a single top-level `lax.scan` under the nested vmaps).

Everything is seeded and deterministic — no flaky tolerances.
"""
import numpy as np
import pytest

from repro.core import BCC, FCC, RTT, Scenario, Torus, scenario_connected
from repro.core.simulation import (_RUNNER_CACHE, _sweep_plan, build_tables,
                                   simulate, simulate_sweep)

# acceptance topology: every differential cell runs on T(4,4,4,4)
G = Torus(4, 4, 4, 4)
TABLES = build_tables(G)
LOADS = (0.25, 0.6, 0.95)
# warmup=0: exact conservation every cell; four seeds keep the ±5 %
# differential clear of seed noise on any PRNG stream
SLOTS, SEEDS = 256, 4

SCENARIOS = {
    "baseline": None,
    "links3/dor": Scenario.random_link_faults(G, 3, seed=1, policy="dor"),
    "links3/adaptive": Scenario.random_link_faults(G, 3, seed=1,
                                                   policy="adaptive"),
    "links3/escape": Scenario.random_link_faults(G, 3, seed=1,
                                                 policy="escape"),
    "nodes2/adaptive": Scenario.random_node_faults(G, 2, seed=2,
                                                   policy="adaptive"),
}
PATTERNS = ("uniform", "centralsymmetric")

_CELLS: dict = {}


def cell(scen_name: str, pattern: str, impl: str):
    """One differential cell: a seed-averaged load curve (cached so the
    invariant tests reuse the differential runs)."""
    key = (scen_name, pattern, impl)
    if key not in _CELLS:
        _CELLS[key] = simulate_sweep(
            G, pattern, LOADS, slots=SLOTS, warmup=0, seed=0, seeds=SEEDS,
            tables=TABLES, impl=impl, scenario=SCENARIOS[scen_name])
    return _CELLS[key]


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("scen_name", sorted(SCENARIOS))
def test_differential_batched_vs_reference(scen_name, pattern):
    """Batched ≡ reference within ±5 % per load point (seed-averaged)."""
    b = cell(scen_name, pattern, "batched").accepted_mean()
    r = cell(scen_name, pattern, "reference").accepted_mean()
    rel = np.abs(b - r) / np.maximum(r, 1e-9)
    assert (np.minimum(rel, np.abs(b - r) / 0.4) <= 0.05).all(), \
        (scen_name, pattern, b, r, rel)


@pytest.mark.parametrize("impl", ("batched", "reference"))
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("scen_name", sorted(SCENARIOS))
def test_conservation_and_dead_link_audit(scen_name, pattern, impl):
    """EXACT accounting on every cell: delivered + in-flight + dropped ==
    injected, and zero crossings of masked channels."""
    scen = SCENARIOS[scen_name]
    for row in cell(scen_name, pattern, impl).results:
        for r in row:
            assert r.delivered + r.in_flight + r.dropped == r.injected, \
                (scen_name, pattern, impl, r)
            if scen is not None:
                assert r.link_use is not None
                assert int(r.link_use[~scen.link_ok(G)].sum()) == 0, \
                    (scen_name, pattern, impl)
                # sanity: the audit actually counted live traffic
                assert int(r.link_use.sum()) > 0


def test_adaptive_dominates_dor_at_saturation():
    """On the faulted graph, minimal-adaptive accepted load at the
    saturating offered loads beats DOR, which blocks on dead channels."""
    for pattern in PATTERNS:
        dor = cell("links3/dor", pattern, "batched").accepted_mean()
        ada = cell("links3/adaptive", pattern, "batched").accepted_mean()
        # compare at the saturating points (offered 0.6 and 0.95)
        assert (ada[1:] >= dor[1:] - 0.005).all(), (pattern, dor, ada)
        assert ada[1:].sum() > dor[1:].sum(), (pattern, dor, ada)


def test_dropped_only_for_dead_fixed_destinations():
    """Uniform traffic samples live destinations (never drops); a fixed
    pattern aimed at a dead node drops — and both conserve exactly."""
    for row in cell("nodes2/adaptive", "uniform", "batched").results:
        assert all(r.dropped == 0 for r in row)
    dropped = [r.dropped
               for row in cell("nodes2/adaptive", "centralsymmetric",
                               "batched").results for r in row]
    assert all(d > 0 for d in dropped), dropped


def test_escape_routes_around_a_wedged_node():
    """Both dim-0 channels of one T(4,4) node dead: a packet sitting there
    with a pure dim-0 record has NO live productive port — minimal-adaptive
    wedges it forever, escape takes an orthogonal non-minimal hop and
    delivers.  Expected ordering: escape > adaptive > dor in delivered
    packets, and escape strands far fewer packets in flight."""
    g = Torus(4, 4)
    t = build_tables(g)
    base = Scenario(dead_links=((5, 0), (5, 1)), policy="adaptive")
    assert scenario_connected(g, base)
    res = {}
    for policy in ("dor", "adaptive", "escape"):
        res[policy] = simulate(g, "uniform", 0.7, slots=384, warmup=0,
                               seed=3, tables=t,
                               scenario=base.with_policy(policy))
        r = res[policy]
        assert r.delivered + r.in_flight + r.dropped == r.injected
        assert int(r.link_use[~base.link_ok(g)].sum()) == 0
    assert res["escape"].delivered > res["adaptive"].delivered > \
        res["dor"].delivered, res
    assert res["escape"].in_flight < res["adaptive"].in_flight, res


def test_ring_escape_livelock_still_conserves():
    """An n=1 ring has no orthogonal escape dimension: a memoryless escape
    policy ping-pongs at the fault (documented livelock).  Even then the
    hard invariants hold — exact conservation, zero dead crossings — and
    the stranded packets show up as in-flight, not as loss."""
    ring = Torus(8)
    t = build_tables(ring)
    scen = Scenario(dead_links=((0, 0),), policy="escape")
    assert scenario_connected(ring, scen)
    r = simulate(ring, "uniform", 0.25, slots=256, warmup=0, seed=3,
                 tables=t, scenario=scen)
    assert r.delivered + r.in_flight + r.dropped == r.injected
    assert int(r.link_use[~scen.link_ok(ring)].sum()) == 0
    assert r.in_flight > 0


@pytest.mark.parametrize("gname,graph", [
    ("RTT3", RTT(3)), ("FCC2", FCC(2)), ("BCC2", BCC(2))])
def test_differential_small_crystals(gname, graph):
    """The (scenario × RTT/FCC/BCC) axis of the differential matrix:
    faulted adaptive cells on the crystal families, batched vs reference,
    seed-averaged (small N ⇒ more seeds, looser per-point noise floor)."""
    t = build_tables(graph)
    scen = Scenario.random_link_faults(graph, 2, seed=4, policy="adaptive")
    acc = {}
    for impl in ("batched", "reference"):
        st = simulate_sweep(graph, "uniform", (0.3, 0.8), slots=320,
                            warmup=0, seed=0, seeds=4, tables=t, impl=impl,
                            scenario=scen)
        for row in st.results:
            for r in row:
                assert r.delivered + r.in_flight + r.dropped == r.injected
                assert int(r.link_use[~scen.link_ok(graph)].sum()) == 0
        acc[impl] = st.accepted_mean()
    diff = np.abs(acc["batched"] - acc["reference"])
    assert (diff <= np.maximum(0.05 * acc["reference"], 0.025)).all(), \
        (gname, acc)


# ---------------------------------------------------------------------------
# multi-seed axis
# ---------------------------------------------------------------------------

def test_multi_seed_bitwise_determinism():
    """Same seeds ⇒ bitwise-identical curves (counters are integers)."""
    g = BCC(2)
    t = build_tables(g)
    kw = dict(slots=160, warmup=40, seed=0, seeds=4, tables=t)
    a = simulate_sweep(g, "uniform", (0.3, 0.8), **kw)
    b = simulate_sweep(g, "uniform", (0.3, 0.8), **kw)
    for ra, rb in zip(
            (r for row in a.results for r in row),
            (r for row in b.results for r in row)):
        assert (ra.delivered, ra.injected, ra.in_flight) == \
               (rb.delivered, rb.injected, rb.in_flight)


def test_multi_seed_slice_equals_single_seed_sweep():
    """Seed-axis slice s of a multi-seed sweep is bitwise the single-seed
    sweep run with seed=seeds[s]."""
    g = BCC(2)
    t = build_tables(g)
    st = simulate_sweep(g, "uniform", (0.3, 0.8), slots=160, warmup=40,
                        seed=0, seeds=(5, 9), tables=t)
    for si, sd in enumerate(st.seeds):
        single = simulate_sweep(g, "uniform", (0.3, 0.8), slots=160,
                                warmup=40, seed=sd, tables=t)
        for li in range(2):
            assert st.results[li][si].delivered == single[li].delivered
            assert st.results[li][si].injected == single[li].injected
    # single-LOAD multi-seed sweeps use the unfolded base keys, so each
    # seed slice equals the plain single run with that seed
    st1 = simulate_sweep(g, "uniform", (0.8,), slots=160, warmup=40,
                         seed=0, seeds=(5, 9), tables=t)
    for si, sd in enumerate(st1.seeds):
        single = simulate(g, "uniform", 0.8, slots=160, warmup=40, seed=sd,
                          tables=t)
        assert st1.results[0][si].delivered == single.delivered
        assert st1.results[0][si].injected == single.injected


def test_fixed_pattern_drop_mask_not_cached_across_patterns():
    """The compiled runner is shared across fixed patterns (the cache key
    only carries fixed-ness), so the pattern-specific dead-destination
    drop mask must travel in the STATE: running pattern A first must not
    poison pattern B's drops."""
    g = Torus(4, 4)
    t = build_tables(g)
    # dead node 6=(1,2): centralsymmetric drops source 14=(3,2), antipodal
    # drops source 12=(3,0) — distinct masks, so cache poisoning is visible
    scen = Scenario(dead_nodes=(6,), policy="adaptive")
    kw = dict(slots=160, warmup=0, seed=2, tables=t, scenario=scen)
    simulate(g, "centralsymmetric", 0.5, **kw)       # primes the runner
    poisoned = simulate(g, "antipodal", 0.5, **kw)
    _RUNNER_CACHE.clear()
    fresh = simulate(g, "antipodal", 0.5, **kw)
    assert (poisoned.delivered, poisoned.injected, poisoned.dropped) == \
           (fresh.delivered, fresh.injected, fresh.dropped)
    assert fresh.dropped > 0


def test_random_link_faults_rejects_infeasible_k():
    g = Torus(2, 2)
    with pytest.raises(ValueError, match="exceeds"):
        Scenario.random_link_faults(g, g.order * g.n + 1)


def test_multi_seed_ci_shrinks_with_k():
    """CI half-width z·s/√k tightens with more seeds (disjoint seed sets;
    fully deterministic, so this is a fixed numerical fact, not a flake):
    expect ≈ 1/√4 = 0.5× going from k=16 to k=64.  Sixteen seeds per side
    keep the sample standard deviation itself within ~20 %, so the ratio
    does not hinge on one PRNG stream (k=4 spread it over 0.4–0.85)."""
    g = BCC(2)
    t = build_tables(g)
    kw = dict(slots=160, warmup=40, seed=0, tables=t)
    small = simulate_sweep(g, "uniform", (0.5, 0.9), seeds=range(100, 116),
                           **kw)
    big = simulate_sweep(g, "uniform", (0.5, 0.9), seeds=range(200, 264),
                         **kw)
    ci_small = small.accepted_ci().mean()
    ci_big = big.accepted_ci().mean()
    assert ci_big < 0.9 * ci_small, (ci_small, ci_big)
    # and the seed means agree within the (generous) joint CI
    assert np.abs(small.accepted_mean() - big.accepted_mean()).max() \
        < 4 * (ci_small + ci_big)


def test_sweep_is_single_scan_device_program():
    """The (loads × seeds) sweep is ONE device program: exactly one
    top-level lax.scan under the nested vmaps, and re-invoking it does not
    grow the compiled-runner cache."""
    import jax
    g = BCC(2)
    t = build_tables(g)
    runner, state, keys, _, _ = _sweep_plan(
        g, "uniform", [0.3, 0.8], slots=96, warmup=24, queue=4, seed=0,
        seed_list=[0, 1, 2], tables=t, impl="batched", scenario=None)
    jaxpr = jax.make_jaxpr(runner)(state, keys)

    def scans(jx):
        n = 0
        for e in jx.eqns:
            if e.primitive.name == "scan":
                n += 1                 # don't descend: inner fixed-point
            elif "jaxpr" in e.params:  # unwrap pjit/closed calls
                sub = e.params["jaxpr"]
                n += scans(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
        return n

    assert scans(jaxpr.jaxpr) == 1
    kw = dict(slots=96, warmup=24, seed=0, seeds=3, tables=t)
    simulate_sweep(g, "uniform", (0.3, 0.8), **kw)
    n_cache = len(_RUNNER_CACHE)
    simulate_sweep(g, "uniform", (0.3, 0.8), **kw)
    assert len(_RUNNER_CACHE) == n_cache


def test_trivial_scenario_is_bitwise_baseline():
    """Scenario() (no faults, DOR) compiles to the exact baseline program:
    results equal scenario=None bitwise, run for run."""
    g = BCC(2)
    t = build_tables(g)
    a = simulate(g, "uniform", 0.6, slots=160, warmup=40, seed=2, tables=t)
    b = simulate(g, "uniform", 0.6, slots=160, warmup=40, seed=2, tables=t,
                 scenario=Scenario())
    assert (a.delivered, a.injected, a.avg_latency_cycles) == \
           (b.delivered, b.injected, b.avg_latency_cycles)


# ---------------------------------------------------------------------------
# fault-aware analytic rebuilds (distances / channel loads)
# ---------------------------------------------------------------------------

def test_fault_aware_tables_match_bfs_when_pristine():
    """With no faults the rebuilt tables reproduce the BFS distances of
    the vertex-transitive graph, row for row."""
    from repro.core import fault_aware_next_hop
    g = BCC(2)
    scen = Scenario(policy="adaptive")      # no faults
    dist, next_hop = fault_aware_next_hop(g, scen.link_ok(g),
                                          scen.node_ok(g))
    d0 = g.distances_from_origin
    assert np.array_equal(dist[:, 0], d0[g.label_to_index(-g.labels)])
    assert np.array_equal(np.sort(dist[0]), np.sort(d0))
    # next hops step one closer
    u = np.flatnonzero(dist[:, 0] > 0)
    v = g.neighbor_indices[u, next_hop[u, 0]]
    assert np.array_equal(dist[v, 0], dist[u, 0] - 1)


def test_faulted_distances_and_saturation_degrade():
    """Dead links can only lengthen distances and add channel load: the
    degraded k̄/diameter are ≥ pristine and the degraded saturation bound
    is ≤ the pristine measured one (MC noise margin)."""
    from repro.core import (channel_load_stats, distance_stats,
                            faulted_distance_matrix,
                            measured_saturation_throughput, saturation)
    g = Torus(4, 4, 4)
    scen = Scenario.random_link_faults(g, 4, seed=7)
    assert scenario_connected(g, scen)
    dist = faulted_distance_matrix(g, scen)
    assert (dist > 0).any() and (dist[dist > 0] >= 1).all()
    dstats = distance_stats(g, scenario=scen)
    assert dstats["diameter"] >= g.diameter
    assert dstats["average_distance"] >= g.average_distance
    load = channel_load_stats(g, scenario=scen, pairs=4000, seed=1)["load"]
    assert load[~scen.link_ok(g)].sum() == 0
    sat_f = saturation(g, scenario=scen, pairs=4000)
    sat_0 = measured_saturation_throughput(g, pairs=4000)
    assert 0 < sat_f <= sat_0 * 1.05, (sat_f, sat_0)


def test_analyze_pod_reports_faulted_capacity():
    from repro.core import NetworkCondition
    from repro.topology.collective_model import PodOptions, analyze_pod
    g = BCC(2)
    scen = Scenario.random_link_faults(g, 2, seed=3)
    rep = analyze_pod("BCC2", g,
                      condition=NetworkCondition(scenario=scen, pairs=2000))
    assert rep.faulted_capacity is not None and rep.faulted_capacity > 0
    rep0 = analyze_pod("BCC2", g, options=PodOptions(routed_pairs=2000))
    assert rep0.faulted_capacity is None
    # the legacy kwargs survive as a conflict-raising shim
    legacy = analyze_pod("BCC2", g, scenario=scen, routed_pairs=2000)
    assert legacy.faulted_capacity == rep.faulted_capacity
    with pytest.raises(ValueError, match="both condition="):
        analyze_pod("BCC2", g, scenario=scen,
                    condition=NetworkCondition(scenario=scen))
    with pytest.raises(ValueError, match="both options="):
        analyze_pod("BCC2", g, routed_pairs=2000,
                    options=PodOptions(routed_pairs=2000))


def test_dead_node_scenario_masks_everything():
    """A dead node neither injects nor relays: every incident channel
    shows zero crossings in both implementations."""
    g = Torus(4, 4)
    t = build_tables(g)
    scen = Scenario(dead_nodes=(5,), policy="adaptive")
    assert scenario_connected(g, scen)
    for impl in ("batched", "reference"):
        r = simulate(g, "uniform", 0.5, slots=192, warmup=0, seed=1,
                     tables=t, impl=impl, scenario=scen)
        assert r.delivered + r.in_flight + r.dropped == r.injected
        assert int(r.link_use[5].sum()) == 0
        # incoming channels of node 5 are its neighbours' masked ports
        assert int(r.link_use[~scen.link_ok(g)].sum()) == 0
