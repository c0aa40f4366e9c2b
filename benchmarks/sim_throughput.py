"""Slots/sec of the port-batched simulator vs the per-port-sweep reference.

This is the ISSUE 2 acceptance benchmark: a full 512-slot uniform-traffic
run at N=4096 (T(8,8,8,8)), batched vs reference, timed interleaved
best-of-`REPS` (the two implementations alternate so machine noise hits
both), plus the vmapped `simulate_sweep` cost per load point.  Quick mode
shrinks to N=512 / 192 slots for CI smoke.

The reference implementation is the pre-batching simulator algorithm
(sequential per-port sweep, in-scan PRNG draws), so `speedup` here is the
committed record of the batched rewrite's win.
"""
from __future__ import annotations

import time

from repro.core import SimConfig, Torus
from repro.core.simulation import build_tables, simulate, simulate_sweep

from .util import emit

REPS = 3


def _best(f, reps=REPS) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def main(quick: bool = False) -> None:
    g = Torus(8, 8, 4, 2) if quick else Torus(8, 8, 8, 8)
    slots = 192 if quick else 512
    warmup = 48 if quick else 128
    loads = (0.3, 0.6, 1.0) if quick else (0.2, 0.4, 0.6, 0.8, 1.0)
    t = build_tables(g)
    cfg = SimConfig(slots=slots, warmup=warmup, seed=1, tables=t)

    def run(impl, load=0.6):
        return simulate(g, "uniform", load, config=cfg.replace(impl=impl))

    # compile both before timing, then alternate (fair under machine
    # noise).  "fused" is not timed: it does not lower for TPU, and an
    # interpret-mode time is not the speed of anything
    impls = ("batched", "reference")
    for impl in impls:
        run(impl, 0.5)
    best = {impl: float("inf") for impl in impls}
    for _ in range(REPS):
        for impl in impls:
            t0 = time.perf_counter()
            run(impl)
            best[impl] = min(best[impl], time.perf_counter() - t0)
    for impl in impls:
        emit(f"sim/{impl}/N={g.order}", best[impl] * 1e6,
             f"slots_per_s={slots / best[impl]:.1f};slots={slots}")
    emit(f"sim/speedup/N={g.order}", 0.0,
         f"speedup={best['reference'] / best['batched']:.2f}x")

    # whole load curve as one vmapped device program
    simulate_sweep(g, "uniform", loads, config=cfg)          # compile
    dt = _best(lambda: simulate_sweep(g, "uniform", loads, config=cfg))
    emit(f"sim/sweep{len(loads)}/N={g.order}", dt * 1e6,
         f"sweep_loadpoints_per_s={len(loads) / dt:.2f};"
         f"per_point_s={dt / len(loads):.2f}")


if __name__ == "__main__":
    main()
