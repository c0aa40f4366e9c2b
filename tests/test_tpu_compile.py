"""The simulator's main-path programs compile for a TPU v5e chip.

Nothing runs here: each program is compiled for one chip of a described
(not attached) v5e:2x2 topology, which raises whatever the chip's
compiler would refuse and reports the program's device memory.  The
topology is described inside a fixture, never at import time, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import FaultSchedule, FourD_FCC, Torus
from repro.core.fault_schedule import ensure_compiled
from repro.core.routing_engine import credit_vc_select
from repro.core.simulation import _sweep_plan, build_tables

HBM_BYTES = 16e9          # one v5e chip
# chip_smoke.py's main path: the paper's §6.2 large pair at full width
LOADS = (0.2, 0.4, 0.6, 0.8, 1.0)
SWEEP = dict(slots=288, warmup=64, seed_list=[0, 1], hist_bins=64)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_for_chip(sharding, g, loads, **plan):
    """The batched sweep runner of `g`, compiled for the described chip."""
    runner, state, keys, _, _ = _sweep_plan(
        g, "uniform", loads, queue=4, seed=0, tables=build_tables(g),
        impl="batched", scenario=None, **plan)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), (state, keys))
    return runner.lower(*shapes).compile()


@pytest.fixture(scope="module")
def full_width_sweep(one_chip):
    """chip_smoke.py's full-width sweep runner of a graph, compiled for the
    described chip once per module."""
    compiled = {}

    def get(graph):
        if graph not in compiled:
            g = Torus(16, 8, 8, 8) if graph.startswith("T") else FourD_FCC(8)
            assert g.order == 8192
            compiled[graph] = compiled_for_chip(one_chip, g, LOADS, **SWEEP)
        return compiled[graph]
    return get


def compile_for_chip(sharding, g, loads, **plan):
    """Compile the batched sweep runner of `g` for the described chip and
    return its memory analysis."""
    return compiled_for_chip(sharding, g, loads, **plan).memory_analysis()


def device_bytes(m) -> int:
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)


@pytest.mark.parametrize("graph", ["T(16,8,8,8)", "4D-FCC(8)"])
def test_full_width_sweep_compiles_and_fits(full_width_sweep, graph):
    m = full_width_sweep(graph).memory_analysis()
    assert 0 < device_bytes(m) < HBM_BYTES, device_bytes(m)


def test_full_width_fcc_sweep_has_no_per_slot_gather(full_width_sweep):
    """The compiled 4D-FCC(8) sweep runner reads each queue slot's port
    with selects: no gather's result is shaped [..., 8192, 32], one
    element per queue slot (P·Q = 8·4) of each of the 8192 nodes.  Such
    element gathers took two thirds of the chip's slot scan."""
    text = full_width_sweep("4D-FCC(8)").as_text()
    gathers = re.findall(r"= (\w+\[[\d,]*\])[^=]*? gather\(", text)
    assert gathers, "the node-axis gathers should still be seen"
    per_slot = [s for s in gathers if re.search(r"\b8192,32\]$", s)]
    assert not per_slot, per_slot


@pytest.mark.parametrize("lead", [(8192, 8, 2, 4), (8192, 64)])
def test_vc_select_has_no_per_slot_gather_or_argmax(one_chip, lead):
    """The VC step's per-slot `credit_vc_select` at the T(16,8,8,8) V = 2
    cell's shape (int8 records of 8192 nodes × 64 queue slots, as the
    (N, P, V, Q) queues the step passes or a flat (N, P·V·Q) view, with
    per-node credit and link views) decodes each slot's (port, lane) with
    elementwise selects: no gather and no reduce has a result of one
    element per queue slot (8192 · 64).  The argmax reduces and the
    per-slot element gather took 71 % of that cell's slot scan."""
    N, P, V, n = 8192, 8, 2, 4
    view = (N,) + (1,) * (len(lead) - 1)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    select = jax.jit(lambda rec, ok, credit, rot: credit_vc_select(
        rec, ok, credit, "adaptive", rot=rot))
    text = select.lower(arg(lead + (n,), jnp.int8), arg(view + (P,), bool),
                        arg(view + (P, V), jnp.int32),
                        arg((), jnp.int32)).compile().as_text()
    per_slot = N * P * V * 4

    def per_slot_ops(opcode):
        """Instructions `opcode` whose result (or a result of a tuple)
        holds one element per queue slot."""
        found = []
        for line in text.splitlines():
            m = re.search(rf" = (.*?) {opcode}\(", line)
            if m and per_slot in [
                    int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\[([\d,]*)\]", m.group(1))]:
                found.append(m.group(1))
        return found

    assert per_slot_ops("select"), "the per-slot selects should be seen"
    assert not per_slot_ops("gather")
    assert not per_slot_ops("reduce")


def test_vc_flap_runner_compiles(one_chip):
    """The credit-flow VC router with per-slot epoch gathers and the
    latency histogram (a 2D torus: the same operations as chip_smoke's
    4D composed phase, at a tenth of its compile time)."""
    g = Torus(16, 16)
    flap = FaultSchedule.link_flap((0, 0), 64, 160)
    m = compile_for_chip(one_chip, g, (0.5,), slots=256, warmup=0,
                         seed_list=None, hist_bins=64, vcs=2,
                         schedules=[ensure_compiled(flap, g, 256)])
    assert 0 < device_bytes(m) < HBM_BYTES


@pytest.mark.parametrize("vcs", [1, 2])
def test_phase_scopes_survive_the_chip_compiler(one_chip, vcs):
    """Every `sim.*` phase scope of the slot step is still in the op
    metadata of the program the chip's compiler builds, which is where
    the profiler reads it from (docs/simulator.md, "Profiling a run")."""
    g = Torus(4, 4, 2)
    plan = dict(slots=16, warmup=0, seed_list=None, hist_bins=16)
    want = {"sim.predraw", "sim.arbitrate", "sim.link_view", "sim.accept",
            "sim.apply", "sim.histogram", "sim.finish"}
    if vcs > 1:
        flap = FaultSchedule.link_flap((0, 0), 4, 10, policy="adaptive")
        plan.update(vcs=2, credits=4, schedules=[flap])
        want |= {"sim.epoch", "sim.vc_select"}
    text = compiled_for_chip(one_chip, g, (0.4,), **plan).as_text()
    stacks = re.findall(r'op_name="([^"]*)"', text)
    found = {m for st in stacks for m in re.findall(r"sim\.[a-z_]+", st)}
    assert want <= found, want - found
