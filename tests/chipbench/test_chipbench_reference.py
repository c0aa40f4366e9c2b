"""The plain reference against the program on the host CPU at small
sizes, and the control that the check has to refuse."""
from __future__ import annotations

import copy
import itertools

import numpy as np
import pytest

from chipbench import cell, reference
from chipbench.traffic import Mix
from chipbench_testkit import link_sets

SWEEP = dict(entry="simulate_sweep", pattern="uniform",
             loads=[0.3, 0.7, 1.0], seeds=2, slots=64, warmup=12,
             hist_bins=16)
SINGLE = dict(entry="simulate", pattern="uniform", load=0.5, slots=64,
              warmup=0, hist_bins=16)
SCENARIOS = dict(SWEEP, entry="simulate_scenario_sweep", loads=[0.4, 0.8],
                 seeds=1, warmup=8)
SCHEDULES = dict(SCENARIOS, entry="simulate_schedule_sweep", warmup=0)
STATIC = [([5, 2], 0, None), ([17, 0], 0, None), ([100, 5], 0, None)]
TIMED = [([5, 2], 6, 30), ([40, 1], 12, None), ([130, 7], 20, 44),
         ([201, 4], 3, 50)]
TORUS = dict(constructor="Torus", args=[8, 4, 4, 2])
CASES = {
    "torus-v1": (TORUS, None, SWEEP),
    "fcc-v1": (dict(constructor="FourD_FCC", args=[3]), None, SWEEP),
    "torus-vc2-flap": (TORUS, dict(kind="link_flap", link=[5, 2],
                                   down_at=10, up_at=40), SINGLE),
    # K = 3 fault sets, the pristine lane among them, at 2 loads
    "torus-vc2-scenarios": (TORUS, link_sets([], STATIC, STATIC[:1] + [
        ([64, 3], 0, None), ([250, 6], 0, None)]), SCENARIOS),
    "torus-vc2-schedules": (TORUS, link_sets([], TIMED,
                                             STATIC + TIMED[1:2]), SCHEDULES),
}


def _case(name):
    from repro import core
    topo, faults, mix = CASES[name]
    g = getattr(core, topo["constructor"])(*topo["args"])
    vc = faults is not None
    cfg = dict(topology=topo, generator_matrix=g.hermite.tolist(),
               nodes=g.order, faults=faults,
               router=dict(vcs=2 if vc else 1, queue=4, credits=4 if vc
                           else None, policy="adaptive" if vc else "dor"))
    return cfg, Mix.from_dict(name, mix)


def _program(cfg, mix, seed):
    from repro.core.simulation import build_tables
    tables = build_tables(cell.graph(cfg))
    return cell.program_call(cfg, mix, tables, seed)()


def _mismatches(got, want):
    return [cell.compare(a, b)
            for a, b in zip(cell.lanes(got), cell.lanes(want))]


@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_equals_program(name, seed):
    cfg, mix = _case(name)
    got = _program(cfg, mix, seed)
    want = cell.reference_records(cfg, mix, seed)
    n = cell.node_slots(cfg, mix) // (cfg["nodes"] * mix.slots)
    assert _mismatches(got, want) == [0] * n
    assert all(r["delivered"] > 0 for r in cell.lanes(want))


@pytest.mark.parametrize("end", ["down_at", "up_at"])
def test_reference_with_an_event_moved_one_slot_differs(end):
    """The reference told of one failure or repair a slot late no
    longer matches what the program ran."""
    cfg, mix = _case("torus-vc2-schedules")
    got = _program(cfg, mix, 5)
    moved = copy.deepcopy(cfg)
    moved["faults"]["sets"][1][0][end] += 1
    want = cell.reference_records(moved, mix, 5)
    bad = _mismatches(got, want)
    assert bad[:2] == [0, 0] and sum(bad[2:4]) > 0 and bad[4:] == [0, 0]


@pytest.mark.parametrize("name", sorted(CASES))
def test_control_in_bfloat16_is_refused(name):
    """The control: the reference with its injection draw rounded to
    bfloat16, put in the program's place, differs on every seed."""
    cfg, mix = _case(name)
    for seed in (1, 2, 2**31 + 3):
        ref = cell.reference_records(cfg, mix, seed)
        ctl = cell.reference_records(cfg, mix, seed,
                                     want=reference.want_bf16)
        assert sum(_mismatches(ctl, ref)) > 0


def test_route_is_minimal():
    """Every record the reference routes lies in its coset and has the
    least L1 length there (brute force over nearby lattice vectors)."""
    H = np.array([[6, 3, 3, 3], [0, 3, 0, 0], [0, 0, 3, 0], [0, 0, 0, 3]])
    lat = reference.Lattice(H)
    shifts = np.array(list(itertools.product(range(-2, 3), repeat=4))) @ H.T
    cand = lat.labels[:, None, :] + shifts[None]
    best = np.abs(cand).sum(-1).min(1)
    for rec in (lat.rec_a, lat.rec_b):
        assert np.array_equal(np.abs(rec).sum(1), best)
    assert np.array_equal(lat.index(lat.rec_a), np.arange(lat.N))
    assert np.array_equal(lat.index(lat.rec_b), np.arange(lat.N))


def test_lattice_refuses_a_matrix_not_in_hermite_form():
    with pytest.raises(ValueError):
        reference.Lattice([[4, 0], [1, 4]])
    with pytest.raises(ValueError):
        reference.Lattice([[4, 5], [0, 4]])
