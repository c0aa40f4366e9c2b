"""Multi-objective candidate evaluator.

Every score flows through the unified analytic surface
(`repro.core.NetworkCondition` + the `saturation` facade):

  * **throughput** — Monte-Carlo saturation of the pristine (or
    heterogeneous, when the candidate carries a `LinkSpec`) fabric:
    ``saturation(g, NetworkCondition(links=...))``;
  * **faulted capacity** — the WORST-epoch saturation under the
    canonical `FaultSchedule` (k seeded link fault/repair events —
    deterministic per candidate order and seed):
    ``min(saturation(g, NetworkCondition(schedule=...)))``;
  * **p99 latency** at the fixed offered load: in ``mode="sim"`` the
    slot-level simulator's exact bucketed percentile
    (`simulate_sweep` — the whole loads × seeds cell is ONE compiled
    program), in ``mode="analytic"`` a deterministic closed-form proxy
    (p99 pairwise distance inflated by the M/D/1-style queueing factor
    ``1/(1 − load/θ)``) that costs no compilation — the CI-budget and
    property-test path.

Evaluations are memoised by `Candidate.key()` (the HNF equivalence
class + parameters), so re-encountering a candidate across generations
is free, and the memo rides the optimizer checkpoint.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

from repro.core import (FaultSchedule, InfeasibleNetwork, LatticeGraph,
                        NetworkCondition, SimConfig, saturation)
from repro.core.distances import weighted_distance_matrix

from .pareto import Objectives
from .space import Candidate

EVAL_MODES = ("analytic", "sim")


@dataclass(frozen=True)
class EvalSettings:
    """Frozen evaluation protocol — one per explorer run, shared by every
    candidate and baseline so scores are comparable."""

    mode: str = "analytic"
    load: float = 0.30          # offered load for the p99 objective
    pairs: int = 4096           # Monte-Carlo pairs per channel-load walk
    seed: int = 0
    backend: str = "host"       # every candidate is a DISTINCT graph, so
    # the device BFS compile cache never hits; host tables are identical
    # and ~200x cheaper at explorer scale (N <= a few hundred)
    fault_links: int = 4        # canonical-schedule fault/repair events
    slots: int = 256            # schedule horizon + simulator run length
    warmup: int = 64
    hist_bins: int = 24
    sim_seeds: int = 2          # replication axis of the one-compile sweep

    def __post_init__(self):
        if self.mode not in EVAL_MODES:
            raise ValueError(
                f"unknown eval mode {self.mode!r}; expected one of "
                f"{EVAL_MODES}")
        if self.backend not in ("auto", "device", "host"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if not 0 < self.load < 1:
            raise ValueError(f"need 0 < load < 1, got {self.load}")
        if self.pairs <= 0 or self.slots <= 0 or self.sim_seeds <= 0:
            raise ValueError("pairs, slots and sim_seeds must be positive")

    def replace(self, **changes) -> "EvalSettings":
        return replace(self, **changes)

    def to_json(self) -> dict:
        return {"mode": self.mode, "load": self.load, "pairs": self.pairs,
                "seed": self.seed, "backend": self.backend,
                "fault_links": self.fault_links,
                "slots": self.slots, "warmup": self.warmup,
                "hist_bins": self.hist_bins, "sim_seeds": self.sim_seeds}

    @classmethod
    def from_json(cls, d: dict) -> "EvalSettings":
        return cls(mode=d["mode"], load=float(d["load"]),
                   pairs=int(d["pairs"]), seed=int(d["seed"]),
                   backend=d["backend"],
                   fault_links=int(d["fault_links"]), slots=int(d["slots"]),
                   warmup=int(d["warmup"]), hist_bins=int(d["hist_bins"]),
                   sim_seeds=int(d["sim_seeds"]))


def canonical_schedule(g: LatticeGraph,
                       settings: EvalSettings) -> FaultSchedule:
    """The shared resilience workload: `fault_links` seeded link
    fault/repair events over the settings horizon — identical event
    *process* for every candidate (the realised links differ with the
    topology, as they must: the schedule names real channels)."""
    return FaultSchedule.random_events(
        g, settings.fault_links, settings.slots, seed=settings.seed)


class Evaluator:
    """Memoised multi-objective scorer.  `evaluate` returns the
    `Objectives` for one candidate.  A candidate the network cannot
    serve (`InfeasibleNetwork`: no reachable pair or live node under the
    canonical schedule, an unsupported feature combination) scores
    `Objectives.worst()` rather than killing the search; any other error
    — a compile, lowering or runtime failure — propagates."""

    def __init__(self, settings: EvalSettings | None = None):
        self.settings = settings or EvalSettings()
        self.memo: dict[tuple, Objectives] = {}
        self._memo_cands: list[tuple[Candidate, Objectives]] = []
        self.evaluations = 0        # cache-miss count (the costly ones)

    # -- the three objectives ----------------------------------------------
    def _throughput(self, g: LatticeGraph, cand: Candidate) -> float:
        s = self.settings
        return float(saturation(g, NetworkCondition(
            links=cand.link_spec(), pairs=s.pairs, seed=s.seed,
            backend=s.backend)))

    def _faulted(self, g: LatticeGraph, cand: Candidate) -> float:
        s = self.settings
        sat = saturation(g, NetworkCondition(
            schedule=canonical_schedule(g, s), links=cand.link_spec(),
            slots=s.slots, pairs=s.pairs, seed=s.seed,
            backend=s.backend))
        return float(np.nanmin(np.asarray(sat)))

    def _p99_sim(self, g: LatticeGraph, cand: Candidate) -> float:
        from repro.core.simulation import simulate_sweep
        s = self.settings
        cfg = SimConfig(slots=s.slots, warmup=s.warmup, queue=cand.queue,
                        seed=s.seed, vcs=cand.vcs, credits=cand.credits,
                        hist_bins=s.hist_bins, links=cand.link_spec())
        sweep = simulate_sweep(g, "uniform", [s.load], config=cfg,
                               seeds=s.sim_seeds)
        return float(sweep.latency_percentile(0.99)[0])

    def _p99_analytic(self, g: LatticeGraph, cand: Candidate,
                      throughput: float) -> float:
        """Deterministic proxy: the 99th-percentile pairwise hop/slot
        cost, inflated by the M/D/1-flavoured queueing factor at the
        fixed offered load (utilisation clamped below 1)."""
        s = self.settings
        ls = cand.link_spec()
        if ls is None:
            d = np.asarray(g.distances_from_origin)
        else:
            d = weighted_distance_matrix(g, ls)
        d = d[d > 0]
        if d.size == 0:
            return float("inf")
        hop99 = float(np.percentile(d, 99))
        util = min(s.load / max(throughput, 1e-9), 0.95)
        return hop99 / (1.0 - util)

    # -- entry points -------------------------------------------------------
    def evaluate(self, cand: Candidate) -> Objectives:
        key = cand.key()
        hit = self.memo.get(key)
        if hit is not None:
            return hit
        self.evaluations += 1
        g = cand.graph()
        try:
            throughput = self._throughput(g, cand)
            faulted = self._faulted(g, cand)
            p99 = (self._p99_sim(g, cand) if self.settings.mode == "sim"
                   else self._p99_analytic(g, cand, throughput))
            obj = Objectives(throughput=throughput, p99=p99,
                             faulted=faulted)
        except InfeasibleNetwork:
            obj = Objectives.worst()
        self.memo[key] = obj
        self._memo_cands.append((cand, obj))
        return obj

    def evaluate_many(self, cands) -> list[Objectives]:
        """Batch entry point: scores in candidate order (memo makes the
        repeat visits free; distinct graphs still compile separately —
        the one-compile batching lives inside each candidate's
        loads × seeds sweep cell)."""
        return [self.evaluate(c) for c in cands]

    # -- memo persistence (rides the optimizer checkpoint) ------------------
    def memo_to_json(self) -> list:
        return [[c.to_json(), o.to_json()]
                for c, o in self._memo_items()]

    def _memo_items(self):
        # memo keys are Candidate.key() tuples; keep a parallel candidate
        # for serialisation by re-deriving from insertion order
        return self._memo_cands

    def load_memo(self, items: list) -> None:
        for cand_json, obj_json in items:
            cand = Candidate.from_json(cand_json)
            obj = Objectives.from_json(obj_json)
            self.memo[cand.key()] = obj
            self._memo_cands.append((cand, obj))
