"""Benchmark driver: one section per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only table1,...]
                                            [--json out.json]

Prints `name,us_per_call,derived` CSV rows (benchmarks.util contract);
with --json the same rows are also written machine-readable (the schema
consumed by `benchmarks.check_regression` and committed as
BENCH_baseline.json — see docs/ci.md for the regression-gate policy).
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import traceback

import jax

from repro.compile_cache import configure_compile_cache

from . import (compose_matrix, explore_bench, fig5_8_simulation,
               hetero_links, latency_telemetry, roofline,
               routing_throughput, scenario_sim, sim_throughput,
               table1_distances, table2_lattices, throughput_bounds,
               topology_collectives, transient_sim, util, vc_router)
from .util import header

SECTIONS = {
    "table1": table1_distances.main,
    "table2": table2_lattices.main,
    "routing": routing_throughput.main,
    "throughput": throughput_bounds.main,
    "sim": sim_throughput.main,
    "scenarios": scenario_sim.main,
    "transient": transient_sim.main,
    "latency": latency_telemetry.main,
    "vc": vc_router.main,
    "hetero": hetero_links.main,
    "compose": compose_matrix.main,
    "explore": explore_bench.main,
    "fig5_8": fig5_8_simulation.main,
    "topology": topology_collectives.main,
    "roofline": roofline.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of sections")
    ap.add_argument("--json", default="", metavar="OUT",
                    help="also write rows as JSON (bench-regression gate)")
    args = ap.parse_args()
    names = [s for s in args.only.split(",") if s] or list(SECTIONS)
    # validate section names upfront: a typo must be a clear one-line
    # error, not a generic "section failed" from the broad except below
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        sys.exit(f"unknown section(s): {', '.join(unknown)}; "
                 f"choose from: {', '.join(SECTIONS)}")
    configure_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    header(device)
    failed = []
    for name in names:
        try:
            SECTIONS[name](quick=args.quick)
        except Exception as e:  # noqa: BLE001 — finish remaining sections
            failed.append((name, e))
            traceback.print_exc()
    if args.json:
        doc = util.rows_as_json()
        doc["meta"] = {
            "quick": args.quick,
            "sections": names,
            "python": platform.python_version(),
            "machine": platform.machine(),
            "device": device,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(doc['rows'])} rows to {args.json}",
              file=sys.stderr)
    if failed:
        sys.exit(f"benchmark sections failed: {[n for n, _ in failed]}")


if __name__ == "__main__":
    main()
