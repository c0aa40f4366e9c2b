"""Heterogeneous links (ISSUE 8 tentpole): weighted latencies, pillar
Z-connectivity and express channels, validated through every simulator
layer.

The contracts pinned here:

  * **bitwise weight-1 contract** — `links=LinkSpec()` (and any spec with
    `is_trivial`) compiles the EXACT pre-heterogeneous program: all six
    PR 7 golden cells (counters bit for bit, float for float) plus the
    24-bin FCC2 histogram reproduce under the trivial spec;
  * **weighted differential** — batched and reference implement the same
    multi-slot channel-hold physics: accepted load agrees within ±5% at
    every load point of a weighted sweep;
  * **express acceptance** — a span-2 express overlay on the long axis of
    the mixed-radix T(8,4) measurably raises routed saturation (above
    the analytic mixed-radix ceiling, closing most of the gap to the
    same-order BCC(2) lattice peer) and lowers simulated latency;
  * **pillar masks** — non-pillar Z-channels are structurally dead:
    `link_use` audits zero crossings, conservation holds, and the mask
    composes with `FaultSchedule` epochs (per-slot dead-crossing audit);
  * **composition** — weights × vcs≥2, weights × FaultSchedule, and the
    fused-impl rejection of non-trivial specs.
"""
import numpy as np
import pytest

from repro.core import (BCC, FaultSchedule, LinkSpec, Scenario, SimConfig,
                        Torus, channel_load_stats, distance_stats,
                        saturation, weighted_distance_matrix)
from repro.core.distances import faulted_distance_matrix
from repro.core.simulation import build_tables, simulate

# the pre-PR goldens live with the VC-router bitwise contract; the
# trivial-LinkSpec program must reproduce every one of them
from test_vc_router import _FCC2_HIST, _GOLDEN_CELLS, _GOLDENS, golden_stream


# ---------------------------------------------------------------------------
# bitwise weight-1 contract (satellite: golden pin)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(_GOLDEN_CELLS))
def test_trivial_linkspec_bitwise_matches_goldens(cell):
    """`links=LinkSpec()` IS `links=None`: all pre-PR goldens reproduce
    bit for bit (ints and floats compared exactly, not approximately)."""
    g, pattern, load, kw, scen = _GOLDEN_CELLS[cell]
    with golden_stream():
        r = simulate(g, pattern, load,
                     config=SimConfig(scenario=scen, links=LinkSpec(), **kw))
    for k, v in _GOLDENS[cell].items():
        got = getattr(r, k)
        if isinstance(v, float):
            assert got == v, (cell, k, got, v)
        else:
            assert int(got) == v, (cell, k, got, v)
    if "hist_bins" in kw:
        np.testing.assert_array_equal(r.latency_hist, _FCC2_HIST)


def test_weight1_spec_is_trivial_and_uniform_weights_too():
    assert LinkSpec().is_trivial
    assert LinkSpec(dim_weights=(1, 1, 1)).is_trivial
    assert LinkSpec(pillar_dim=2, pillar_every=1).is_trivial
    assert not LinkSpec(dim_weights=(1, 2)).is_trivial
    assert not LinkSpec(pillar_dim=2, pillar_every=2).is_trivial
    assert not LinkSpec(express=((0, 2, 1),)).is_trivial
    # trivial specs share the None fingerprint: one compile-cache entry
    assert LinkSpec().fingerprint() is None
    assert LinkSpec(dim_weights=(1, 1)).fingerprint() is None


# ---------------------------------------------------------------------------
# weighted differential: batched ≡ reference within ±5% per load point
# ---------------------------------------------------------------------------

def test_weighted_differential_batched_vs_reference():
    g = Torus(4, 4)
    t = build_tables(g)
    ls = LinkSpec(dim_weights=(1, 2))
    for load in (0.2, 0.4, 0.6):
        runs = {}
        for impl in ("batched", "reference"):
            r = runs[impl] = simulate(
                g, "uniform", load,
                config=SimConfig(slots=160, warmup=0, seed=3, impl=impl,
                                 links=ls, tables=t))
            # exact conservation at warmup=0, weighted or not
            assert r.delivered + r.in_flight + r.dropped == r.injected
        a, b = runs["batched"], runs["reference"]
        assert a.accepted_load == pytest.approx(b.accepted_load, rel=0.05), \
            (load, a.accepted_load, b.accepted_load)


def test_weights_slow_the_fabric_monotonically():
    """Same run, heavier Z: average latency rises monotonically, and at
    a saturating offered load the weight-4 fabric accepts measurably
    less than the uniform one — the weight axis reaches the physics."""
    g = Torus(4, 4, 4)
    t = build_tables(g)
    lat = []
    acc = []
    for wz in (1, 2, 4):
        r = simulate(g, "uniform", 0.8,
                     config=SimConfig(slots=160, warmup=32, seed=1,
                                      links=LinkSpec(dim_weights=(1, 1, wz)),
                                      tables=t))
        lat.append(r.avg_latency_cycles)
        acc.append(r.accepted_load)
    assert lat[0] < lat[1] < lat[2], lat
    assert acc[2] < 0.9 * acc[0], acc


# ---------------------------------------------------------------------------
# express channels (acceptance: mixed-radix torus vs lattice peer)
# ---------------------------------------------------------------------------

def test_express_port_geometry_invariants():
    """Extended ports keep the two structural invariants the whole
    simulator relies on: opp(p) == p ^ 1 and nbr[nbr[u, p], p ^ 1] == u."""
    g = Torus(8, 4)
    ls = LinkSpec(express=((0, 2, 1), (0, 4, 2)))
    nbr = ls.extended_neighbors(g)
    P = ls.num_ports(g.n)
    assert nbr.shape == (g.order, P) and P == 2 * g.n + 4
    for p in range(P):
        back = nbr[nbr[:, p], p ^ 1]
        np.testing.assert_array_equal(back, np.arange(g.order))
    # span-2 express really lands 2 hops away along dim 0
    lab = np.asarray(g.labels)
    np.testing.assert_array_equal(
        lab[nbr[:, 2 * g.n]][:, 0], (lab[:, 0] + 2) % 8)


def test_express_raises_mixed_radix_saturation_toward_lattice_peer():
    """The acceptance cell: T(8,4) is capacity-limited by its long axis
    (analytic ceiling Δ/(n·k̄_max) = 1.0 phit/cycle/node).  A span-2
    express overlay on that axis lifts routed saturation ABOVE the
    ceiling, closing more than half the gap to the same-order (32-node)
    BCC(2) lattice peer measured with the identical methodology."""
    g = Torus(8, 4)
    base = saturation(g, links=LinkSpec(dim_weights=(1, 1)), pairs=20_000)
    ex = saturation(g, links=LinkSpec(express=((0, 2, 1),)), pairs=20_000)
    peer = saturation(BCC(2), links=LinkSpec(dim_weights=(1, 1, 1)),
                      pairs=20_000)
    assert ex > 1.5 * base, (base, ex)
    assert ex > 1.0                    # beats the analytic mixed ceiling
    assert peer > base
    assert (ex - base) / (peer - base) > 0.5, (base, ex, peer)


def test_express_lowers_simulated_latency_both_impls():
    g = Torus(8, 4)
    t = build_tables(g)
    ls = LinkSpec(express=((0, 2, 1),))
    for impl in ("batched", "reference"):
        cfg = SimConfig(slots=160, warmup=32, seed=0, impl=impl, tables=t)
        r0 = simulate(g, "uniform", 0.3, config=cfg)
        r1 = simulate(g, "uniform", 0.3, config=cfg.replace(links=ls))
        assert r1.avg_latency_cycles < 0.9 * r0.avg_latency_cycles, \
            (impl, r0.avg_latency_cycles, r1.avg_latency_cycles)
        assert r1.delivered > 0


def test_express_shortens_weighted_distances():
    g = Torus(8, 4)
    d0 = weighted_distance_matrix(g, LinkSpec(dim_weights=(1, 1)))
    d1 = weighted_distance_matrix(g, LinkSpec(express=((0, 2, 1),)))
    assert (d1 <= d0).all()
    assert (d1 < d0).any()
    # antipodal along dim 0: 4 base hops collapse onto 2 express hops
    u = int(g.label_to_index(np.array([0, 0])))
    v = int(g.label_to_index(np.array([4, 0])))
    assert d0[u, v] == 4 and d1[u, v] == 2


# ---------------------------------------------------------------------------
# pillar Z-connectivity
# ---------------------------------------------------------------------------

def test_pillar_mask_structure():
    g = Torus(4, 4, 4)
    ls = LinkSpec(pillar_dim=2, pillar_every=2)
    m = ls.structural_mask(g)
    lab = np.asarray(g.labels)
    pillar = (lab[:, 0] % 2 == 0) & (lab[:, 1] % 2 == 0)
    np.testing.assert_array_equal(m[:, 4], pillar)
    np.testing.assert_array_equal(m[:, 5], pillar)
    assert m[:, :4].all()              # in-plane links untouched
    # symmetric: u and its Z-neighbour agree, so no half-dead channels
    nbr = np.asarray(g.neighbor_indices)
    np.testing.assert_array_equal(m[:, 4], m[nbr[:, 4], 5])


def test_pillar_kills_nonpillar_z_crossings_and_conserves():
    g = Torus(4, 4, 4)
    ls = LinkSpec(pillar_dim=2, pillar_every=2)
    mask = ls.structural_mask(g)
    for impl in ("batched", "reference"):
        r = simulate(g, "uniform", 0.4,
                     config=SimConfig(slots=128, warmup=0, seed=4, impl=impl,
                                      links=ls,
                                      scenario=Scenario(policy="adaptive")))
        assert r.delivered + r.in_flight + r.dropped == r.injected
        assert r.delivered > 0
        assert r.link_use is not None
        assert int(r.link_use[~mask].sum()) == 0, impl   # the audit
        assert int(r.link_use[:, 4:6][mask[:, 4:6]].sum()) > 0


def test_pillar_composes_with_fault_schedule():
    """Epoch link_ok stacks AND in the static pillar mask: a mid-run
    link flap on an in-plane channel coexists with the pillar holes,
    per-slot conservation and the dead-crossing audit stay exact."""
    g = Torus(4, 4, 4)
    ls = LinkSpec(pillar_dim=2, pillar_every=2)
    sched = FaultSchedule.link_flap((1, 0), down_at=24, up_at=60,
                                    policy="adaptive")
    r = simulate(g, "uniform", 0.5,
                 config=SimConfig(slots=96, warmup=0, seed=2, links=ls,
                                  schedule=sched))
    tl = r.timeline
    assert tl is not None
    assert tl.conservation_ok(), tl.conservation_violations()
    assert tl.dead_crossings.sum() == 0
    mask = ls.structural_mask(g)
    assert int(r.link_use[~mask].sum()) == 0


def test_pillar_disconnection_is_detected_not_silent():
    """pillar_every=4 on T(4,4,4) leaves a single pillar column; routing
    the weighted tables still reaches everything through it (finite
    distances), but a ring schedule that needs an unreachable edge under
    a *disconnecting* mask raises rather than emitting a bogus path."""
    g = Torus(4, 4, 4)
    ls = LinkSpec(pillar_dim=2, pillar_every=4)
    d = weighted_distance_matrix(g, ls)
    assert (d >= 0).all()              # single pillar still connects
    assert d.max() > int(g.diameter)   # ...at a real detour cost


# ---------------------------------------------------------------------------
# composition: vcs ≥ 2, schedules, fused rejection
# ---------------------------------------------------------------------------

def test_weights_compose_with_vc_router():
    g = Torus(4, 4)
    ls = LinkSpec(dim_weights=(1, 3))
    for impl in ("batched", "reference"):
        r = simulate(g, "uniform", 0.4,
                     config=SimConfig(slots=128, warmup=0, seed=6, impl=impl,
                                      vcs=2, links=ls))
        assert r.delivered + r.in_flight + r.dropped == r.injected
        assert r.delivered > 0
        assert int(np.asarray(r.vc_delivered).sum()) == r.delivered


def test_weights_compose_with_fault_schedule_every_slot():
    g = Torus(4, 4)
    sched = FaultSchedule(events=((16, "link_down", (1, 0)),
                                  (48, "link_up", (1, 0))),
                          base=Scenario(policy="adaptive"))
    r = simulate(g, "uniform", 0.6,
                 config=SimConfig(slots=96, warmup=0, seed=5,
                                  links=LinkSpec(dim_weights=(2, 1)),
                                  schedule=sched))
    tl = r.timeline
    assert tl.conservation_ok(), tl.conservation_violations()
    assert tl.dead_crossings.sum() == 0


def test_fused_rejects_nontrivial_spec():
    g = Torus(4, 4)
    with pytest.raises(ValueError, match="fused"):
        SimConfig(impl="fused", links=LinkSpec(dim_weights=(1, 2)))
    # the trivial spec is fine — it IS the weight-1 program
    r = simulate(g, "uniform", 0.3,
                 config=SimConfig(slots=64, warmup=0, seed=0, impl="fused",
                                  links=LinkSpec()))
    assert r.delivered > 0


def test_express_config_guards():
    # ISSUE 9 lifted the pristine-fabric and vcs=1-only guards: express
    # now composes with VCs and with fault scenarios/schedules.  The one
    # remaining exclusion is the V=1 adaptive/escape heuristics, whose
    # port scoring is base-lattice-only.
    assert SimConfig(vcs=2, links=LinkSpec(express=((0, 2, 1),))).vcs == 2
    assert SimConfig(links=LinkSpec(express=((0, 2, 1),)),
                     scenario=Scenario(dead_links=((0, 0),))).links.express
    with pytest.raises(ValueError, match="greedy"):
        SimConfig(links=LinkSpec(express=((0, 2, 1),)),
                  scenario=Scenario(dead_links=((0, 0),),
                                    policy="adaptive"))
    with pytest.raises(ValueError):
        LinkSpec(express=((0, 2, 1),), pillar_dim=2, pillar_every=2)
    with pytest.raises(ValueError):
        LinkSpec(express=((0, 1, 1),))          # span-1 is a base link
    with pytest.raises(ValueError):
        LinkSpec(dim_weights=(0, 1))


# ---------------------------------------------------------------------------
# analytic layer exactness
# ---------------------------------------------------------------------------

def test_trivial_weighted_distances_equal_hop_distances():
    g = Torus(4, 4, 4)
    dw = weighted_distance_matrix(g, LinkSpec(dim_weights=(1, 1, 1)))
    dh = faulted_distance_matrix(g, Scenario())
    np.testing.assert_array_equal(dw, dh)


def test_uniform_weight_scaling_doubles_costs_exactly():
    g = Torus(4, 4)
    d1 = weighted_distance_matrix(g, LinkSpec(dim_weights=(1, 1)))
    d2 = weighted_distance_matrix(g, LinkSpec(dim_weights=(2, 2)))
    np.testing.assert_array_equal(d2, 2 * d1)
    a1 = distance_stats(
        g, links=LinkSpec(dim_weights=(1, 1)))["average_distance"]
    a2 = distance_stats(
        g, links=LinkSpec(dim_weights=(2, 2)))["average_distance"]
    assert a2 == pytest.approx(2 * a1)


def test_weighted_channel_load_shapes_and_saturation():
    g = Torus(4, 4)
    ls = LinkSpec(dim_weights=(1, 2))
    stats = channel_load_stats(g, links=ls, pairs=5_000, seed=1)
    load = stats["load"]
    assert load.shape == (g.order, 4)
    w = ls.port_weights(g.n)
    theta = saturation(g, links=ls, pairs=5_000, seed=1)
    assert theta == pytest.approx(1.0 / float((load * w[None, :]).max()))
    assert stats["saturation"] == pytest.approx(theta)
    # heavier dim-1 channels cap saturation below the uniform fabric's
    theta1 = saturation(g, links=LinkSpec(dim_weights=(1, 1)),
                        pairs=5_000, seed=1)
    assert theta < theta1
