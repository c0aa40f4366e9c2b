"""The simulator names its own work for the profiler (docs/simulator.md,
"Profiling a run"): every phase of both batched slot steps carries a
`jax.named_scope` that survives into the compiled program's op metadata,
and every public entry records host spans — plan, run, fetch, with the
runner lookup and the key derivation inside the plan — carrying the
call's work count."""
from __future__ import annotations

import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import FaultSchedule, Scenario, Torus
from repro.core.simulation import (_sweep_plan, build_tables, simulate,
                                   simulate_scenario_sweep,
                                   simulate_schedule_sweep, simulate_sweep)

V1_SCOPES = {"sim.predraw", "sim.arbitrate", "sim.link_view", "sim.accept",
             "sim.apply", "sim.histogram", "sim.finish"}
VC_SCOPES = V1_SCOPES | {"sim.epoch", "sim.vc_select"}
G = Torus(4, 4, 2)


@pytest.fixture(scope="module")
def tables():
    return build_tables(G)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """An executable loaded from the persistent cache keeps the metadata
    of the build that filled it (the cache key leaves the name stack
    out), so compile afresh here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_scopes(tables, loads, **plan) -> set[str]:
    runner, state, keys, _, _ = _sweep_plan(
        G, "uniform", loads, slots=16, queue=4, seed=0, tables=tables,
        impl="batched", scenario=None, **plan)
    text = runner.lower(state, keys).compile().as_text()
    stacks = re.findall(r'op_name="([^"]*)"', text)
    return {m for s in stacks for m in re.findall(r"sim\.[a-z_]+", s)}


def test_v1_step_carries_every_scope(tables):
    found = compiled_scopes(tables, [0.3, 0.7], warmup=4,
                            seed_list=[0, 1], hist_bins=8)
    assert V1_SCOPES <= found, V1_SCOPES - found
    assert not found & {"sim.epoch", "sim.vc_select"}


def test_vc_step_with_a_link_flap_carries_every_scope(tables):
    flap = FaultSchedule.link_flap((0, 0), 4, 10, policy="adaptive")
    found = compiled_scopes(tables, [0.4], warmup=0, seed_list=None,
                            hist_bins=8, vcs=2, credits=4,
                            schedules=[flap])
    assert VC_SCOPES <= found, VC_SCOPES - found


def host_spans(path) -> list[tuple[str, float, float, dict]]:
    """Every `sim.*` event of the host plane, (name, start, end, args),
    parents before children."""
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sim."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def children(spans, parent):
    _, lo, hi, _ = parent
    inside = [s for s in spans if lo <= s[1] and s[2] <= hi and s != parent]
    return [s for s in inside
            if not any(o[1] <= s[1] and s[2] <= o[2] and o != s
                       for o in inside)]


@pytest.mark.parametrize("entry", ["sim.sweep", "sim.simulate",
                                   "sim.scenario_sweep",
                                   "sim.schedule_sweep"])
def test_entry_spans_nest_plan_run_fetch(entry, tables, tmp_path):
    flap = FaultSchedule.link_flap((0, 0), 4, 10, policy="adaptive")
    call = {
        "sim.sweep": lambda: simulate_sweep(
            G, "uniform", [0.3, 0.7], slots=16, warmup=4, seeds=2,
            tables=tables, hist_bins=8),
        "sim.simulate": lambda: simulate(
            G, "uniform", 0.4, slots=16, warmup=0, vcs=2, credits=4,
            tables=tables, schedule=flap),
        "sim.scenario_sweep": lambda: simulate_scenario_sweep(
            G, "uniform", [None, Scenario(dead_nodes=(5,))], loads=[0.4],
            slots=16, warmup=0, tables=tables),
        "sim.schedule_sweep": lambda: simulate_schedule_sweep(
            G, "uniform", [None, flap], loads=[0.4], slots=16, warmup=0,
            tables=tables),
    }[entry]
    call()                                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        call()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = host_spans(path)
    entries = [s for s in spans if s[0] == entry]
    assert len(entries) == 1
    top = entries[0]
    lanes = {"sim.sweep": 4, "sim.simulate": 1, "sim.scenario_sweep": 2,
             "sim.schedule_sweep": 2}
    assert top[3] == {"nodes": 32, "slots": 16, "lanes": lanes[entry]}
    assert [s[0] for s in children(spans, top)] == [
        "sim.plan", "sim.run", "sim.fetch"]
    plan = children(spans, top)[0]
    assert [s[0] for s in children(spans, plan)] == [
        "sim.runner", "sim.keys"]
