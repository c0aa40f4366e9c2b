"""Topology-explorer tests (ISSUE 10 satellite 3): seeded determinism,
Pareto-archive invariants, checkpoint/resume equivalence, and the
propcheck property that every sampled/mutated HNF candidate is valid.

All explorer runs here use analytic mode + host BFS + tiny Monte-Carlo
budgets: deterministic and fast (no per-candidate device compiles)."""
import json
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import InfeasibleNetwork, intmat
from repro.explore import (Candidate, EvalSettings, Evaluator, Objectives,
                           ParetoArchive, SearchSpace, dominates, explore)

FAST = EvalSettings(mode="analytic", pairs=512, slots=128, fault_links=2)


def tiny_run(seed=0, generations=2, population=3, **kw):
    return explore(SearchSpace(), FAST, generations=generations,
                   population=population, seed=seed, **kw)


# ---------------------------------------------------------------------------
# dominance + archive invariants
# ---------------------------------------------------------------------------

def obj(t, p, f):
    return Objectives(throughput=t, p99=p, faulted=f)


def test_dominates_basics():
    a, b = obj(0.8, 10.0, 0.6), obj(0.5, 17.0, 0.4)
    assert dominates(a, b) and not dominates(b, a)
    assert not dominates(a, a)                    # needs a strict axis
    assert not dominates(obj(0.9, 20.0, 0.6), a)  # trade-off: incomparable


def test_nonfinite_objectives_never_dominate():
    bad = obj(math.nan, math.inf, 0.9)
    assert not dominates(bad, obj(0.1, 100.0, 0.1))
    assert dominates(obj(0.1, 100.0, 0.1), Objectives.worst())


def cand(seed):
    return SearchSpace().sample(np.random.default_rng(seed))


def test_archive_rejects_dominated_keeps_nondominated():
    a = ParetoArchive()
    assert a.add(cand(1), obj(0.8, 10.0, 0.6))
    assert not a.add(cand(2), obj(0.5, 17.0, 0.4))   # dominated: rejected
    assert a.add(cand(3), obj(0.9, 20.0, 0.6))       # trade-off: kept
    assert len(a.discovered()) == 2


def test_archive_evicts_newly_dominated():
    a = ParetoArchive()
    a.add(cand(1), obj(0.5, 17.0, 0.4))
    a.add(cand(2), obj(0.8, 10.0, 0.6))              # dominates cand(1)
    assert len(a.discovered()) == 1
    assert a.discovered()[0].objectives.throughput == 0.8


def test_archive_never_retains_a_dominated_point():
    rng = np.random.default_rng(7)
    a = ParetoArchive()
    for i in range(60):
        a.add(cand(i), obj(float(rng.uniform(0.1, 1)),
                           float(rng.uniform(5, 30)),
                           float(rng.uniform(0.1, 1))))
    disc = a.discovered()
    for x in disc:
        for y in disc:
            assert not dominates(x.objectives, y.objectives, a.eps) \
                or x is y


def test_baselines_pinned_never_evicted_never_block():
    a = ParetoArchive()
    base = cand(1)
    a.add(base, obj(0.9, 5.0, 0.9), baseline=True)
    # a baseline dominating a newcomer must NOT block it
    assert a.add(cand(2), obj(0.2, 20.0, 0.2))
    # a newcomer dominating the baseline must NOT evict it
    assert a.add(cand(3), obj(0.95, 4.0, 0.95))
    assert len([e for e in a.entries if e.baseline]) == 1
    assert a.front()[0].baseline                     # baselines listed first


def test_archive_dedups_identical_design_points():
    a = ParetoArchive()
    c = cand(1)
    assert a.add(c, obj(0.5, 10.0, 0.5))
    assert not a.add(c, obj(0.5, 10.0, 0.5))
    assert len(a.discovered()) == 1


def test_archive_json_round_trip():
    a = ParetoArchive(eps=1e-3)
    a.add(cand(1), obj(0.9, 5.0, 0.9), baseline=True)
    a.add(cand(2), obj(0.8, 10.0, 0.6))
    b = ParetoArchive.from_json(json.loads(json.dumps(a.to_json())))
    assert b.to_json() == a.to_json() and b.eps == a.eps


# ---------------------------------------------------------------------------
# the evolutionary loop: determinism, baselines, checkpoint/resume
# ---------------------------------------------------------------------------

def test_same_seed_identical_archive_json():
    a = tiny_run(seed=3).archive.to_json()
    b = tiny_run(seed=3).archive.to_json()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_different_seeds_differ():
    a = tiny_run(seed=3).archive.to_json()
    b = tiny_run(seed=4).archive.to_json()
    assert a != b


def test_all_four_baselines_present_in_front():
    front = tiny_run().archive.front()
    names = [e.candidate.name for e in front if e.baseline]
    assert names == ["FCC(4)/128", "BCC(3)/108", "RTT(8)/128",
                     "T(8,4,4)/128"]


def test_checkpoint_resume_equals_uninterrupted(tmp_path):
    ck = str(tmp_path / "ck.json")
    full = tiny_run(seed=5, generations=4).archive.to_json()
    tiny_run(seed=5, generations=2, checkpoint=ck)
    resumed = tiny_run(seed=5, generations=4, checkpoint=ck,
                       resume=True).archive.to_json()
    assert json.dumps(full, sort_keys=True) == \
        json.dumps(resumed, sort_keys=True)


def test_resume_refuses_mismatched_protocol(tmp_path):
    ck = str(tmp_path / "ck.json")
    tiny_run(seed=5, generations=1, checkpoint=ck)
    with pytest.raises(ValueError, match="seed"):
        tiny_run(seed=6, generations=2, checkpoint=ck, resume=True)
    with pytest.raises(ValueError, match="EvalSettings"):
        explore(SearchSpace(), FAST.replace(pairs=256), generations=2,
                population=3, seed=5, checkpoint=ck, resume=True)


def test_evaluator_memoizes_by_design_point():
    ev = Evaluator(FAST)
    c = SearchSpace().torus_baseline()
    a, b = ev.evaluate(c), ev.evaluate(c)
    assert a == b and ev.evaluations == 1


@pytest.mark.parametrize("exc, scored_worst", [
    (InfeasibleNetwork("no reachable pairs under this condition"), True),
    (ValueError("Shape mismatch in input, indices and output"), False),
    (AssertionError("fault-aware walk stepped onto a dead channel"), False),
    (RuntimeError("RESOURCE_EXHAUSTED: out of memory"), False),
], ids=["infeasible", "lowering", "invariant", "runtime"])
def test_evaluator_absorbs_only_infeasible_networks(monkeypatch, exc,
                                                    scored_worst):
    """An infeasible network scores worst; a compile, lowering, invariant
    or runtime failure propagates instead of posing as a bad candidate."""
    def boom(*_):
        raise exc

    ev = Evaluator(FAST)
    monkeypatch.setattr(ev, "_throughput", boom)
    c = SearchSpace().torus_baseline()
    if scored_worst:
        assert ev.evaluate(c) == Objectives.worst()
    else:
        with pytest.raises(type(exc)):
            ev.evaluate(c)


def test_worst_candidate_cannot_enter_front():
    res = tiny_run()
    assert all(e.objectives != Objectives.worst()
               for e in res.archive.discovered())


# ---------------------------------------------------------------------------
# propcheck property: sampled + mutated candidates are always valid
# ---------------------------------------------------------------------------

@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=10_000))
def test_sampled_candidates_always_valid(seed):
    space = SearchSpace()
    rng = np.random.default_rng(seed)
    c = space.sample(rng)
    assert space.valid(c)
    M = np.asarray(c.matrix, dtype=np.int64)
    np.testing.assert_array_equal(M, intmat.hermite_normal_form(M))
    assert space.min_nodes <= abs(int(intmat.det(M))) <= space.max_nodes


@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=10_000))
def test_mutated_candidates_always_valid(seed):
    space = SearchSpace()
    rng = np.random.default_rng(seed)
    c = space.mutate(space.sample(rng), rng)
    assert space.valid(c)


@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=10_000))
def test_candidate_json_round_trip(seed):
    c = SearchSpace().sample(np.random.default_rng(seed))
    assert Candidate.from_json(json.loads(json.dumps(c.to_json()))) == c
