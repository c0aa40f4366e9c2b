"""CLI for the topology explorer.

    PYTHONPATH=src python -m repro.explore [--smoke] [options]

Prints the seeded Pareto front (throughput × p99 × faulted capacity)
with the RTT/FCC/BCC and mixed-radix-torus baselines pinned, then the
acceptance check: does a discovered lattice Pareto-dominate the
same-order torus?  `--require-dominance` turns that check into the
exit status (the CI smoke gate).
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.compile_cache import configure_compile_cache

from .evaluate import EvalSettings
from .optimizer import explore
from .pareto import dominates
from .space import SearchSpace


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.explore",
        description="Seeded evolutionary search over cubic-crystal "
                    "lattice topologies.")
    p.add_argument("--generations", type=int, default=12)
    p.add_argument("--population", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--eps", type=float, default=1e-3,
                   help="epsilon-Pareto dominance slack")
    p.add_argument("--mode", choices=("analytic", "sim"),
                   default="analytic",
                   help="p99 objective: closed-form proxy or the "
                        "slot-level simulator")
    p.add_argument("--load", type=float, default=0.30,
                   help="offered load for the p99 objective")
    p.add_argument("--pairs", type=int, default=4096,
                   help="Monte-Carlo pairs per saturation walk")
    p.add_argument("--smoke", action="store_true",
                   help="CI budget: <=8 generations, small population, "
                        "analytic mode")
    p.add_argument("--out", type=str, default=None,
                   help="write the front JSON here")
    p.add_argument("--checkpoint", type=str, default=None,
                   help="JSON checkpoint path (written every generation)")
    p.add_argument("--resume", action="store_true",
                   help="resume from --checkpoint if it exists")
    p.add_argument("--require-dominance", action="store_true",
                   help="exit 1 unless a discovered lattice "
                        "Pareto-dominates the torus baseline")
    return p


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    if args.smoke:
        args.generations = min(args.generations, 8)
        args.population = min(args.population, 6)
        args.mode = "analytic"
        args.pairs = min(args.pairs, 2048)

    configure_compile_cache()
    settings = EvalSettings(mode=args.mode, load=args.load,
                            pairs=args.pairs, seed=args.seed)
    space = SearchSpace()

    def progress(gen, archive):
        n = len(archive.discovered())
        print(f"  gen {gen:2d}: front holds {n} discovered candidate"
              f"{'s' if n != 1 else ''}")

    result = explore(space, settings, generations=args.generations,
                     population=args.population, seed=args.seed,
                     eps=args.eps, checkpoint=args.checkpoint,
                     resume=args.resume, progress=progress)
    archive = result.archive

    print(f"\n== Pareto front (seed={args.seed}, mode={args.mode}, "
          f"{result.generations} generations, "
          f"{result.evaluations} evaluations) ==")
    print(f"  {'candidate':26} {'kind':9} {'thr':>6} {'p99':>8} "
          f"{'faulted':>8}")
    for e in archive.front():
        o = e.objectives
        tag = "  [baseline]" if e.baseline else ""
        print(f"  {e.candidate.label():26} {e.candidate.kind:9} "
              f"{o.throughput:6.3f} {o.p99:8.1f} {o.faulted:8.3f}{tag}")

    # -- acceptance: a discovered lattice dominates the same-order torus --
    torus = next(e for e in archive.front()
                 if e.baseline and e.candidate.kind == "baseline"
                 and e.candidate.name.startswith("T("))
    winners = [e for e in archive.discovered()
               if dominates(e.objectives, torus.objectives)]
    if winners:
        best = winners[0]
        print(f"\n{best.candidate.label()} Pareto-dominates "
              f"{torus.candidate.name}: "
              f"thr {best.objectives.throughput:.3f} vs "
              f"{torus.objectives.throughput:.3f}, "
              f"p99 {best.objectives.p99:.1f} vs "
              f"{torus.objectives.p99:.1f}, "
              f"faulted {best.objectives.faulted:.3f} vs "
              f"{torus.objectives.faulted:.3f}")
    else:
        print(f"\nno discovered candidate dominates "
              f"{torus.candidate.name} yet (try more generations)")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(archive.to_json(), f, indent=2)
        print(f"front written to {args.out}")

    if args.require_dominance and not winners:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
