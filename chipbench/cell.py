"""One cell's two sides: the program's timed call and the plain
reference's answer, as the same per-lane records.

A lane record holds every count a run reports: ``delivered``,
``injected``, ``dropped``, ``in_flight``, ``lat_cnt``, ``lat_sum``, the
latency histogram where the mix asks for one, and under the VC router
the per-lane counters, the per-channel link use and the per-slot
timeline.  `compare` counts the values in which two records differ.

A configuration's ``faults`` is null, a ``link_flap`` (one undirected
link down from ``down_at`` until ``up_at``) or ``link_sets``:

    {"kind": "link_sets", "sets": [[{"link": [u, p], "down_at": d,
                                     "up_at": r | null}, ...], ...]}

K sets of undirected link events (`link_sets`), each swept as one lane
group by the mix's ``simulate_scenario_sweep`` (static sets: every
``down_at`` 0, every ``up_at`` null) or ``simulate_schedule_sweep``
entry under the router's ``policy``; an empty set is the pristine lane.
Their records nest sets × loads × seeds, the other entries' loads ×
seeds; `lanes` flattens either.
"""
from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from multiprocessing import get_context

import numpy as np

from . import reference, traffic
from .traffic import Mix

PACKET_PHITS = 16


def graph(config: dict):
    from repro import core
    topo = config["topology"]
    return getattr(core, topo["constructor"])(*topo["args"])


def link_sets(config: dict, mix: Mix):
    """The configuration's ``link_sets`` as K lists of ``((u, p),
    down_at, up_at)`` events, checked against the mix's entry; None for
    a configuration with other faults or none.  Refused, each with its
    reason: a fault-sweep entry without ``link_sets`` and the reverse,
    ``link_sets`` at V = 1, node events, and a timed event under
    ``simulate_scenario_sweep``."""
    faults = config.get("faults")
    kind = None if faults is None else faults["kind"]
    if kind not in (None, "link_flap", "link_sets"):
        raise ValueError(f"unknown fault kind {kind!r}")
    if (kind == "link_sets") != (mix.entry in traffic.FAULT_SWEEPS):
        raise ValueError(
            f"mix {mix.name}: the entries {', '.join(traffic.FAULT_SWEEPS)} "
            f"and only they sweep a configuration's link_sets; entry "
            f"{mix.entry} meets faults of kind {kind}")
    if kind is None or kind == "link_flap":
        return None
    if config["router"]["vcs"] < 2:
        raise ValueError("link_sets at V = 1: the reference has no faulted "
                         "V = 1 router (it runs a fault under the VC router "
                         "alone)")
    if not faults["sets"]:
        raise ValueError("link_sets needs at least one set (an empty set "
                         "is the pristine lane)")
    N, P = int(config["nodes"]), 2 * len(config["generator_matrix"])
    sets = []
    for k, events in enumerate(faults["sets"]):
        sets.append([])
        for ev in events:
            if set(ev) != {"link", "down_at", "up_at"}:
                raise ValueError(
                    f"link_sets set {k}: {ev} is not a link event "
                    f"{{link, down_at, up_at}}; node events are refused: "
                    f"the reference has no dead-node destination draw")
            (u, p), down, up = ev["link"], ev["down_at"], ev["up_at"]
            if not (0 <= u < N and 0 <= p < P):
                raise ValueError(f"link_sets set {k}: link {[u, p]} is not "
                                 f"a (node < {N}, port < {P}) pair")
            if down < 0 or (up is not None and up <= down):
                raise ValueError(f"link_sets set {k}: link {[u, p]} needs "
                                 f"0 <= down_at < up_at, got {down}, {up}")
            if mix.entry == "simulate_scenario_sweep" and (down, up) != (
                    0, None):
                raise ValueError(
                    f"mix {mix.name}: simulate_scenario_sweep runs static "
                    f"patterns, so every event needs down_at 0 and up_at "
                    f"null; set {k} times link {[u, p]} at {down}, {up}: "
                    f"drive it with simulate_schedule_sweep")
            sets[-1].append(((int(u), int(p)), int(down),
                             None if up is None else int(up)))
    return sets


def node_slots(config: dict, mix: Mix) -> int:
    """Simulated node-slots of one call."""
    sets = link_sets(config, mix)
    return (int(config["nodes"]) * mix.slots * mix.n_lanes()
            * (1 if sets is None else len(sets)))


def lanes(records) -> list[dict]:
    """The lane records of a call, flattened in the program's order."""
    if isinstance(records, dict):
        return [records]
    return [r for row in records for r in lanes(row)]


def _sim_config(config: dict, mix: Mix, tables, seed: int):
    from repro.core import FaultSchedule, SimConfig
    router = config["router"]
    faults = config.get("faults")
    kw = dict(slots=mix.slots, warmup=mix.warmup, hist_bins=mix.hist_bins,
              queue=router["queue"], vcs=router["vcs"],
              credits=router.get("credits"), seed=seed, tables=tables)
    if faults is None:
        if router["policy"] != "dor":
            raise ValueError("a pristine configuration routes by DOR")
    elif faults["kind"] == "link_flap":
        kw["schedule"] = FaultSchedule.link_flap(
            tuple(faults["link"]), faults["down_at"], faults["up_at"],
            policy=router["policy"])
    return SimConfig(**kw)


def _fault_lanes(sets, entry: str, policy: str) -> list:
    """One program input per set: a static `Scenario`, or a
    `FaultSchedule` of its failures and repairs on a pristine base."""
    from repro.core import FaultSchedule, Scenario
    if entry == "simulate_scenario_sweep":
        return [Scenario(dead_links=tuple(link for link, _, _ in s),
                         policy=policy) for s in sets]
    return [FaultSchedule(
        events=tuple(ev for link, down, up in s for ev in (
            ((down, "link_down", link),)
            + (() if up is None else ((up, "link_up", link),)))),
        base=Scenario(policy=policy)) for s in sets]


def lane_record(r) -> dict:
    """The counts of one program `SimResult`."""
    out = dict(delivered=r.delivered, injected=r.injected,
               dropped=r.dropped, in_flight=r.in_flight, lat_cnt=r.lat_count,
               # the mean is 16 · lat_sum / lat_cnt in float64: exact back
               lat_sum=(int(round(r.avg_latency_cycles * r.lat_count
                                  / PACKET_PHITS)) if r.lat_count else 0))
    if r.latency_hist is not None:
        out["latency_hist"] = np.asarray(r.latency_hist)
    if r.vc_delivered is not None:
        out.update(vc_delivered=r.vc_delivered, vc_injected=r.vc_injected,
                   vc_in_flight=r.vc_in_flight)
    if r.link_use is not None:
        out["link_use"] = np.asarray(r.link_use)
    if r.timeline is not None:
        tl = r.timeline
        out["timeline"] = dict(
            delivered=tl.delivered, injected=tl.injected,
            dropped=tl.dropped, in_flight=tl.in_flight,
            dead_crossings=tl.dead_crossings,
            **({} if tl.lat_hist is None else {"lat_hist": tl.lat_hist}))
    return out


def program_call(config: dict, mix: Mix, tables, seed: int):
    """A closure that makes one call of the cell through the program's
    public entry and returns its lane records, (loads × seeds) nested,
    or (sets × loads × seeds) under a fault-sweep entry."""
    from repro.core import simulation
    from repro.core.simulation import simulate, simulate_sweep
    g = graph(config)
    sets = link_sets(config, mix)
    cfg = _sim_config(config, mix, tables, seed)
    if mix.entry == "simulate":
        return lambda: [[lane_record(
            simulate(g, mix.pattern, mix.loads[0], config=cfg))]]
    seeds = [seed + s for s in range(mix.seeds)]
    if sets is not None:
        entry = getattr(simulation, mix.entry)
        inputs = _fault_lanes(sets, mix.entry, config["router"]["policy"])

        def call():
            return [[[lane_record(r) for r in row] for row in st.results]
                    for st in entry(g, mix.pattern, inputs, mix.loads,
                                    config=cfg, seeds=seeds)]
        return call

    def call():
        st = simulate_sweep(g, mix.pattern, mix.loads, config=cfg,
                            seeds=seeds)
        return [[lane_record(r) for r in row] for row in st.results]
    return call


def _lane_job(job):
    kind, lat, load, tr, kw = job
    run = reference.run_lane if kind == "v1" else reference.run_vc_lane
    return run(lat, load, tr, **kw)


def reference_records(config: dict, mix: Mix, seed: int,
                      want=reference.want_f32) -> list:
    """The reference's lane records for the same cell and seed, nested
    as `program_call`'s.  The lanes' traffic is drawn here on the host
    CPU, once for all of a `link_sets` configuration's sets; each (set,
    lane) then runs in a worker process of its own, which imports numpy
    and the reference alone (never JAX, so no worker reaches for the
    chip)."""
    lat = reference.Lattice(config["generator_matrix"])
    if lat.N != int(config["nodes"]):
        raise ValueError("the generator matrix disagrees with `nodes`")
    router = config["router"]
    V, Q = router["vcs"], router["queue"]
    faults = config.get("faults")
    sets = link_sets(config, mix)
    grid = mix.lanes(seed)
    flat = [lane for row in grid for lane in row]
    kw = dict(slots=mix.slots, warmup=mix.warmup, queue=Q,
              hist_bins=mix.hist_bins, want=want)
    if V == 1 and faults is None:
        kind, masks = "v1", [None]
    else:
        kind = "vc"
        kw.update(vcs=V, credits=router.get("credits") or Q)
        if sets is not None:
            masks = [reference.link_events_mask(lat, mix.slots, s)
                     for s in sets]
        elif faults is not None:
            masks = [reference.link_flap_mask(
                lat, mix.slots, faults["link"], faults["down_at"],
                faults["up_at"])]
        else:
            masks = [np.ones((mix.slots, lat.N, lat.P), bool)]
    K = len(masks)
    workers = max(1, min(len(flat) * K, os.cpu_count() or 1))
    with ThreadPoolExecutor(min(workers, len(flat))) as ex:
        trs = list(ex.map(lambda lane: traffic.draw(
            lane, mix.slots, lat.N, lat.P * V * Q), flat))
    jobs = [(kind, lat, lane.load, tr,
             kw if mask is None else dict(kw, link_ok=mask))
            for mask in masks for lane, tr in zip(flat, trs)]
    del trs, masks
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
        recs = list(ex.map(_lane_job, jobs))
    if mix.entry == "simulate_scenario_sweep":
        for r in recs:                 # the static entry keeps no timeline
            del r["timeline"]
    it = iter(recs)
    grids = [[[next(it) for _ in row] for row in grid] for _ in range(K)]
    return grids if sets is not None else grids[0]


def compare(got: dict, want: dict) -> int:
    """Number of compared values in which `got` differs from `want`; a
    field on one side only counts as one difference per value."""
    bad = 0
    for k in sorted(set(got) | set(want)):
        if k not in got or k not in want:
            v = want.get(k, got.get(k))
            bad += (sum(np.size(x) for x in v.values()) if isinstance(v, dict)
                    else int(np.size(v)))
        elif isinstance(want[k], dict):
            bad += compare(got[k], want[k])
        else:
            a, b = np.asarray(got[k]), np.asarray(want[k])
            bad += (int(max(a.size, b.size)) if a.shape != b.shape
                    else int((a != b).sum()))
    return bad
