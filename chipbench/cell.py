"""One cell's two sides: the program's timed call and the plain
reference's answer, as the same per-lane records.

A lane record holds every count a run reports: ``delivered``,
``injected``, ``dropped``, ``in_flight``, ``lat_cnt``, ``lat_sum``, the
latency histogram where the mix asks for one, and under the VC router
the per-lane counters, the per-channel link use and the per-slot
timeline.  `compare` counts the values in which two records differ.
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


def node_slots(config: dict, mix: Mix) -> int:
    """Simulated node-slots of one call."""
    return int(config["nodes"]) * mix.slots * mix.n_lanes()


def _sim_config(config: dict, mix: Mix, tables, seed: int):
    from repro.core import FaultSchedule, SimConfig
    router = config["router"]
    faults = config.get("faults")
    kw = dict(slots=mix.slots, warmup=mix.warmup, hist_bins=mix.hist_bins,
              queue=router["queue"], vcs=router["vcs"],
              credits=router.get("credits"), seed=seed, tables=tables)
    if faults is not None:
        if faults["kind"] != "link_flap":
            raise ValueError(f"unknown fault kind {faults['kind']!r}")
        kw["schedule"] = FaultSchedule.link_flap(
            tuple(faults["link"]), faults["down_at"], faults["up_at"],
            policy=router["policy"])
    elif router["policy"] != "dor":
        raise ValueError("a pristine configuration routes by DOR")
    return SimConfig(**kw)


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
    public entry and returns its lane records, (loads × seeds) nested."""
    from repro.core.simulation import simulate, simulate_sweep
    g = graph(config)
    cfg = _sim_config(config, mix, tables, seed)
    if mix.entry == "simulate":
        return lambda: [[lane_record(
            simulate(g, mix.pattern, mix.loads[0], config=cfg))]]
    seeds = [seed + s for s in range(mix.seeds)]

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
                      want=reference.want_f32) -> list[list[dict]]:
    """The reference's lane records for the same cell and seed.  The
    lanes' traffic is drawn here on the host CPU; each lane then runs in
    a worker process of its own, which imports numpy and the reference
    alone (never JAX, so no worker reaches for the chip)."""
    lat = reference.Lattice(config["generator_matrix"])
    if lat.N != int(config["nodes"]):
        raise ValueError("the generator matrix disagrees with `nodes`")
    router = config["router"]
    V, Q = router["vcs"], router["queue"]
    faults = config.get("faults")
    grid = mix.lanes(seed)
    flat = [lane for row in grid for lane in row]
    kw = dict(slots=mix.slots, warmup=mix.warmup, queue=Q,
              hist_bins=mix.hist_bins, want=want)
    if V == 1 and faults is None:
        kind = "v1"
    else:
        kind = "vc"
        kw.update(vcs=V, credits=router.get("credits") or Q,
                  link_ok=(np.ones((mix.slots, lat.N, lat.P), bool)
                           if faults is None else reference.link_flap_mask(
                               lat, mix.slots, faults["link"],
                               faults["down_at"], faults["up_at"])))
    workers = max(1, min(len(flat), os.cpu_count() or 1))
    with ThreadPoolExecutor(workers) as ex:
        trs = list(ex.map(lambda lane: traffic.draw(
            lane, mix.slots, lat.N, lat.P * V * Q), flat))
    jobs = [(kind, lat, lane.load, tr, kw) for lane, tr in zip(flat, trs)]
    del trs
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
        recs = list(ex.map(_lane_job, jobs))
    it = iter(recs)
    return [[next(it) for _ in row] for row in grid]


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
