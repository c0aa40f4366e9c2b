"""Run the lattice-network simulator's main path once on one TPU chip.

    python chip_smoke.py

One process, public API only, four phases; any failure ends the script
with a non-zero exit and no result line:

  1. device — the first JAX device must be a TPU; the script never
     carries on on the CPU;
  2. parity — the same small `simulate_sweep` (T(8,8,4,2), N=512, uniform,
     three loads × two seeds, 64-bucket latency histogram) on the chip and
     pinned to the host CPU backend: every counter and histogram bitwise
     equal;
  3. main path — the paper's §6.2 large pair at full width, T(16,8,8,8)
     vs 4D-FCC(8) (8192 nodes each), five loads × two seeds, 288 slots
     with a 64-slot warmup: integer accounting in every lane (exact
     conservation in a second sweep counted from slot 0), and the
     crystal's peak beats the torus's (the paper reports a 1.50× gain);
  4. composed — one `simulate` on T(8,8,8,8) with two virtual channels, a
     link flap and the histogram: per-slot conservation and the per-VC
     V-sum identity.

Each phase prints one JSON line.  The last line of standard output is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
The compile cache lives in `JAX_COMPILATION_CACHE_DIR`, or in
`.jax_cache/` beside this script; compile seconds are a cold call minus
a warm call of the same program, so a second run against the same cache
reports fewer of them.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.fig5_8_simulation import PAPER_GAINS  # noqa: E402
from repro.compile_cache import configure_compile_cache  # noqa: E402
from repro.core import (FaultSchedule, FourD_FCC, SimConfig,  # noqa: E402
                        Torus)
from repro.core.simulation import (build_tables, simulate,  # noqa: E402
                                   simulate_sweep)

PARITY = dict(graph=(8, 8, 4, 2), loads=(0.3, 0.6, 1.0), seeds=2,
              slots=192, warmup=48, hist_bins=64)
MAIN = dict(torus=(16, 8, 8, 8), fcc=8, loads=(0.2, 0.4, 0.6, 0.8, 1.0),
            seeds=2, slots=288, warmup=64, hist_bins=64)
COMPOSED = dict(graph=(8, 8, 8, 8), load=0.5, vcs=2, slots=256,
                link=(0, 0), down_at=64, up_at=160, hist_bins=64)
COUNTERS = ("delivered", "injected", "dropped", "in_flight", "lat_count")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check_device() -> dict:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: the first JAX device is {dev.platform!r}, "
                 "not a TPU; refusing to run on it")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def torus_name(dims) -> str:
    return f"T({','.join(map(str, dims))})"


def lanes(stats):
    """Every (load, seed) SimResult of a SweepStats."""
    return [r for row in stats.results for r in row]


def lat_sum(r) -> int:
    """The summed measured latency in slots, recovered exactly from the
    mean (avg = 16 · lat_sum / lat_count)."""
    if not r.lat_count:
        return 0
    return int(round(r.avg_latency_cycles * r.lat_count / 16))


def counters(r) -> tuple:
    return tuple(getattr(r, f) for f in COUNTERS) + (lat_sum(r),)


def assert_same(a, b, what: str) -> None:
    for i, (x, y) in enumerate(zip(lanes(a), lanes(b))):
        assert counters(x) == counters(y), (what, i, counters(x),
                                            counters(y))
        assert np.array_equal(x.latency_hist, y.latency_hist), (what, i)


def timed(f):
    """(result, cold seconds, warm seconds) of two identical calls; the
    warm call reuses the compiled program, so it is the run time and
    cold − warm the set-up (trace, lower, compile or cache load)."""
    t0 = time.perf_counter()
    cold = f()
    t1 = time.perf_counter()
    warm = f()
    t2 = time.perf_counter()
    return cold, warm, t1 - t0, t2 - t1


def phase_parity() -> None:
    p = PARITY
    g = Torus(*p["graph"])
    cfg = SimConfig(slots=p["slots"], warmup=p["warmup"],
                    hist_bins=p["hist_bins"], tables=build_tables(g))

    def run():
        return simulate_sweep(g, "uniform", p["loads"], config=cfg,
                              seeds=p["seeds"])

    chip = run()
    with jax.default_device(jax.devices("cpu")[0]):
        host = run()
    assert_same(chip, host, "chip vs host CPU")
    assert sum(r.delivered for r in lanes(chip)) > 0
    emit("parity", graph=torus_name(p["graph"]), nodes=g.order,
         lanes=len(lanes(chip)), bitwise_equal=True,
         delivered=[r.delivered for r in lanes(chip)])


def check_lanes(name: str, stats, loads, nodes: int, ports: int,
                queue: int, warmup: int) -> None:
    """Integer accounting of every (load, seed) lane of a sweep.

    Counting opens at `warmup` while packets born earlier still sit in
    the queues, so `carried = delivered + in_flight + dropped − injected`
    is the number carried into the window: exactly 0 with warmup=0, and
    between 0 and the buffer capacity otherwise.  Injections never exceed
    the Bernoulli arrivals, ≤ offered·N·W + 6σ (σ = 0 at load 1)."""
    for li, row in enumerate(stats.results):
        load = loads[li]
        for si, r in enumerate(row):
            lane = (name, load, stats.seeds[si])
            window = nodes * (r.slots - warmup)
            carried = r.delivered + r.in_flight + r.dropped - r.injected
            if warmup == 0:
                assert carried == 0, (lane, "conservation", carried)
            else:
                assert 0 <= carried <= nodes * ports * queue, (lane, carried)
            arrivals = load * window + 6 * math.sqrt(
                load * (1 - load) * window)
            assert r.injected <= arrivals, (lane, r.injected, arrivals)
            assert r.lat_count <= min(r.delivered, r.injected), lane
            if r.latency_hist is not None:
                assert int(r.latency_hist.sum()) == r.lat_count, lane


def phase_main() -> None:
    p = MAIN
    cfg = SimConfig(slots=p["slots"], warmup=p["warmup"],
                    hist_bins=p["hist_bins"])
    peaks = {}
    for name, g in ((torus_name(p["torus"]), Torus(*p["torus"])),
                    (f"4D-FCC({p['fcc']})", FourD_FCC(p["fcc"]))):
        t0 = time.perf_counter()
        c = cfg.replace(tables=build_tables(g))
        tables_s = time.perf_counter() - t0

        def sweep(c):
            return simulate_sweep(g, "uniform", p["loads"], config=c,
                                  seeds=p["seeds"])

        cold, warm, cold_s, warm_s = timed(lambda: sweep(c))
        assert_same(cold, warm, f"{name} cold vs warm")
        dims = (2 * g.n, c.queue)
        check_lanes(name, cold, p["loads"], g.order, *dims, p["warmup"])
        # the same lanes counted from slot 0: exact conservation
        t0 = time.perf_counter()
        exact = sweep(c.replace(warmup=0, hist_bins=0))
        exact_s = time.perf_counter() - t0
        check_lanes(name, exact, p["loads"], g.order, *dims, 0)
        mean = cold.accepted_mean()
        i = int(np.argmax(mean))
        peaks[name] = float(mean[i])
        emit("main", network=name, nodes=g.order, peak=peaks[name],
             peak_load=p["loads"][i],
             p99_cycles_at_peak=finite(cold.latency_p99()[i]),
             accepted_mean=mean.tolist(), tables_s=tables_s,
             compile_s=cold_s - warm_s, run_s=warm_s,
             warmup0_sweep_s=exact_s)
    (tname, tpeak), (cname, cpeak) = peaks.items()
    assert cpeak > tpeak, peaks
    emit("main", torus=tname, crystal=cname, torus_peak=tpeak,
         crystal_peak=cpeak, gain=cpeak / tpeak,
         paper_gain=PAPER_GAINS[("large", "uniform")])


def finite(x):
    """A percentile for JSON: None where it fell in the overflow bucket."""
    x = float(x)
    return None if math.isinf(x) else x


def phase_composed() -> None:
    p = COMPOSED
    g = Torus(*p["graph"])
    sched = FaultSchedule.link_flap(p["link"], p["down_at"], p["up_at"],
                                    policy="adaptive")
    cfg = SimConfig(slots=p["slots"], warmup=0, vcs=p["vcs"],
                    schedule=sched, hist_bins=p["hist_bins"],
                    tables=build_tables(g))
    cold, warm, cold_s, warm_s = timed(
        lambda: simulate(g, "uniform", p["load"], config=cfg))
    assert counters(cold) == counters(warm)
    tl = cold.timeline
    assert tl.conservation_ok(), tl.conservation_violations()[:10]
    assert int(tl.dead_crossings.sum()) == 0
    assert (tl.delivered[-1], tl.injected[-1], tl.dropped[-1],
            tl.in_flight[-1]) == (cold.delivered, cold.injected,
                                  cold.dropped, cold.in_flight)
    assert int(cold.vc_delivered.sum()) == cold.delivered
    assert int(cold.vc_injected.sum()) == cold.injected + cold.dropped
    assert int(cold.vc_in_flight.sum()) == cold.in_flight
    assert cold.delivered > 0
    emit("composed", graph=torus_name(p["graph"]), nodes=g.order,
         vcs=p["vcs"],
         delivered=cold.delivered, injected=cold.injected,
         dropped=cold.dropped, in_flight=cold.in_flight,
         p99_cycles=finite(cold.latency_p99),
         compile_s=cold_s - warm_s, run_s=warm_s)


def main() -> None:
    cache_dir = configure_compile_cache()
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: hits.append(1)
        if event == "/jax/compilation_cache/cache_hits" else None)
    device = check_device()
    emit("device", **device)
    phase_parity()
    phase_main()
    phase_composed()
    # how warm the persistent cache was: programs loaded instead of compiled
    emit("cache", dir=cache_dir, hits=len(hits))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
