"""Parity suite for the Pallas fused slot step (`impl="fused"`, ISSUE 4).

Quick shapes, interpret mode on the CPU (the kernel does not lower for
TPU, where `impl="fused"` raises).  The contract is two-layered:

  * **bitwise vs batched** — the fused kernel consumes the same pre-drawn
    traffic and encodes the same arbitration keys, so its counters must
    equal `impl="batched"` integer-for-integer on every cell, and
  * **differential vs reference** — the same scenario × pattern cells the
    batched implementation is validated on (`tests/test_scenarios.py`)
    hold for fused, within the same ±5 %/point band.

These run in the offline CI matrix (slow-ok: interpret-mode Pallas traces
each slot's kernel into the scan, so shapes here stay small).
"""
import numpy as np
import pytest

from repro.core import Scenario, Torus
from repro.core.simulation import build_tables, simulate, simulate_sweep

G = Torus(4, 4)
TABLES = build_tables(G)
KW = dict(slots=128, warmup=0, seed=2, tables=TABLES)

SCENARIOS = {
    "baseline": None,
    "links2/dor": Scenario.random_link_faults(G, 2, seed=1, policy="dor"),
    "links2/adaptive": Scenario.random_link_faults(G, 2, seed=1,
                                                   policy="adaptive"),
    "links2/escape": Scenario.random_link_faults(G, 2, seed=1,
                                                 policy="escape"),
    "nodes1/adaptive": Scenario(dead_nodes=(6,), policy="adaptive"),
}


@pytest.mark.parametrize("pattern", ("uniform", "centralsymmetric"))
@pytest.mark.parametrize("scen_name", sorted(SCENARIOS))
def test_fused_bitwise_equals_batched(scen_name, pattern):
    scen = SCENARIOS[scen_name]
    b = simulate(G, pattern, 0.6, scenario=scen, **KW)
    f = simulate(G, pattern, 0.6, scenario=scen, impl="fused", **KW)
    assert (b.delivered, b.injected, b.in_flight, b.dropped) == \
           (f.delivered, f.injected, f.in_flight, f.dropped), (scen_name,
                                                               pattern)
    if scen is not None:
        assert np.array_equal(b.link_use, f.link_use)


@pytest.mark.parametrize("policy", ("adaptive", "dor"))
def test_fused_differential_vs_reference(policy):
    """The scenario differential cells at quick shapes: fused load curve ≡
    reference within ±5 % per point (seed-averaged), conservation and the
    dead-channel audit exact on every (load, seed) run.  T(4,4,4): big
    enough that arbitration-stream noise sits inside the band (at N=16
    even batched-vs-reference drifts past it at saturation)."""
    g = Torus(4, 4, 4)
    t = build_tables(g)
    scen = Scenario.random_link_faults(g, 3, seed=1, policy=policy)
    loads = (0.3, 0.8)
    acc = {}
    for impl in ("fused", "reference"):
        st = simulate_sweep(g, "uniform", loads, seeds=3, scenario=scen,
                            impl=impl, slots=128, warmup=0, seed=2,
                            tables=t)
        for row in st.results:
            for r in row:
                assert r.delivered + r.in_flight + r.dropped == r.injected
                assert int(r.link_use[~scen.link_ok(g)].sum()) == 0
        acc[impl] = st.accepted_mean()
    diff = np.abs(acc["fused"] - acc["reference"])
    assert (diff <= np.maximum(0.05 * acc["reference"], 0.025)).all(), acc


def test_fused_conservation_on_escape_ring():
    """The documented n=1-ring escape livelock: even the pathological cell
    conserves exactly under the fused kernel."""
    ring = Torus(8)
    t = build_tables(ring)
    scen = Scenario(dead_links=((0, 0),), policy="escape")
    r = simulate(ring, "uniform", 0.25, slots=128, warmup=0, seed=3,
                 tables=t, scenario=scen, impl="fused")
    assert r.delivered + r.in_flight + r.dropped == r.injected
    assert int(r.link_use[~scen.link_ok(ring)].sum()) == 0


def test_fused_kernel_node_tiling_exact():
    """Grid-tiled kernel (block_nodes < N) == single-tile kernel, output
    for output — the VMEM tiling changes residency, never results."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.sim_step import fused_slot_step
    key = jax.random.PRNGKey(0)
    N, P, Q, n = G.order, 2 * G.n, 4, G.n
    ks = jax.random.split(key, 8)
    rec = jax.random.randint(ks[0], (N, P, Q, n), -3, 4).astype(jnp.int8)
    birth = jnp.where(jax.random.uniform(ks[1], (N, P, Q)) < 0.5, 3,
                      -1).astype(jnp.int16)
    port = jax.random.randint(ks[2], (N, P, Q), 0, P).astype(jnp.int8)
    prio = jax.random.bits(ks[3], (N, P * Q), jnp.uint8)
    nbr = jnp.asarray(G.neighbor_indices.astype(np.int32))
    want = jax.random.uniform(ks[4], (N,)) < 0.5
    tr_r = jax.random.randint(ks[5], (N, n), -3, 4).astype(jnp.int8)
    tr_p = jax.random.randint(ks[6], (N,), 0, P).astype(jnp.int8)
    tr_v = jnp.ones((N,), bool)
    args = (rec, birth, port, prio, jnp.int32(5), want, tr_r, tr_p, tr_v,
            nbr)
    whole = fused_slot_step(*args)
    tiled = fused_slot_step(*args, block_nodes=4)
    for w, t_ in zip(whole, tiled):
        assert np.array_equal(np.asarray(w), np.asarray(t_))


def test_fused_sweep_and_scenario_sweep():
    """The fused runner composes with the sweep vmaps: load×seed sweeps
    and the K-scenario sweep both accept impl="fused" and match batched
    bitwise."""
    from repro.core.simulation import simulate_scenario_sweep
    scen = SCENARIOS["links2/adaptive"]
    kw = dict(slots=64, warmup=0, seed=0, tables=TABLES)
    sf = simulate_sweep(G, "uniform", (0.3, 0.8), seeds=2, scenario=scen,
                        impl="fused", **kw)
    sb = simulate_sweep(G, "uniform", (0.3, 0.8), seeds=2, scenario=scen,
                        impl="batched", **kw)
    assert np.array_equal(sf.accepted(), sb.accepted())
    scens = [Scenario.random_link_faults(G, k, seed=k, policy="adaptive")
             for k in (1, 2)]
    rf = simulate_scenario_sweep(G, "uniform", scens, loads=(0.5,),
                                 impl="fused", **kw)
    rb = simulate_scenario_sweep(G, "uniform", scens, loads=(0.5,),
                                 impl="batched", **kw)
    assert [r[0].delivered for r in rf] == [r[0].delivered for r in rb]


def test_unknown_impl_rejected():
    with pytest.raises(ValueError, match="unknown simulator impl"):
        simulate(G, "uniform", 0.5, impl="pallas", **KW)


def test_fused_refuses_tpu_backend(monkeypatch):
    """On a TPU backend the fused impl raises — Mosaic refuses its
    gathers — instead of quietly running batched or interpret mode."""
    from repro.core import simulation
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    # a fresh runner cache, so the slot step is built (and refuses) here
    monkeypatch.setattr(simulation, "_RUNNER_CACHE", {})
    with pytest.raises(NotImplementedError, match='impl="batched"'):
        simulate(G, "uniform", 0.5, impl="fused", **KW)
