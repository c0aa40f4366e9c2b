"""One run of one benchmark cell on the chips of this machine.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  In order:

  1. set-up: the cell's configuration and traffic mix are read by name
     from `BENCHMARK.json`; the first JAX device has to be a TPU and the
     machine must hold the chips the cell asks for (no CPU fallback);
     the persistent compile cache is placed; the program's tables are
     built; one call of the cell's own shape warms every program the
     window will run.  `setup_s` runs from process start to here;
  2. the window: whole calls of the program's public entry, back to
     back; a call that the previous call's length says would end past
     `--seconds` is not started, and at least one call always runs.  The
     traces and compiles that happen inside it are counted (there should
     be none).  With `--trace 1` the window is profiled;
  3. the check, after the window and never timed: the plain reference
     (`chipbench.reference`) recomputes every lane from the seed, and
     each call's outputs must equal it value for value;
  4. the result: the last line of standard output is one JSON object
     (`correct`, `attempted`, `failed`, `metrics`, `device`, with
     `--trace 1` also `breakdown`, and `checks` last); the numbers
     compared, each beside its limit, are also the last lines of
     standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

from . import cell as cellmod  # noqa: E402
from .spec import Benchmark  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclass
class RunRecord:
    """What the per-layer readers read."""
    node_slots: int = 0
    tables_s: float | None = None
    setup_events: list = field(default_factory=list)
    trace: object = None


def check_device(chips: int) -> list:
    """The JAX devices, if the first is a TPU and there are `chips` of
    them; otherwise exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: the first JAX device is "
                     f"{devs[0].platform!r}, not a TPU; no result")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, this "
                     f"machine holds {len(devs)}; no result")
    return devs


def parse(argv):
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0 or a.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return a


@contextlib.contextmanager
def jax_events(log: list, phase: list):
    """Listen to jax.monitoring while the block runs, appending
    (event, seconds, phase[0]) for every event; counts have 0 s."""
    import jax.monitoring as mon

    def duration(event, secs, **_):
        log.append((event, float(secs), phase[0]))

    def count(event, **_):
        log.append((event, 0.0, phase[0]))

    mon.register_event_duration_secs_listener(duration)
    mon.register_event_listener(count)
    try:
        yield
    finally:
        mon.unregister_event_duration_listener(duration)
        mon.unregister_event_listener(count)


def window(call, seconds: float, profile_dir: str | None):
    """Whole calls back to back; returns (outputs per call, start, end)."""
    import jax
    outs = []
    if profile_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("chipbench.call"):
                    a = time.perf_counter()
                    outs.append(call())
                    b = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.between"):
                    if b + (b - a) > t0 + seconds:
                        break
            t1 = b
    finally:
        if profile_dir is not None:
            jax.profiler.stop_trace()
    return outs, t0, t1


def main(argv=None, root: Path = ROOT) -> int:
    args = parse(argv)
    bench = Benchmark(root)
    cell = bench.cell(args.workload)
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: no program under {src}; no result",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    devs = check_device(cell.chips)

    import jax
    from repro.compile_cache import configure_compile_cache
    from repro.core import simulation
    cache_dir = configure_compile_cache()
    log, phase = [], ["setup"]
    rec = RunRecord()
    with jax_events(log, phase):
        t = time.perf_counter()
        tables = simulation.build_tables(cellmod.graph(cell.config))
        rec.tables_s = time.perf_counter() - t
        call = cellmod.program_call(cell.config, cell.mix, tables,
                                    args.seed)
        call()                              # warm-up: compiles or loads
        setup_s = time.perf_counter() - T_START
        phase[0] = "window"
        traces0 = sum(simulation.TRACE_COUNTS.values())
        tmp = (tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace
               else None)
        try:
            outs, w0, w1 = window(call, args.seconds, tmp)
            phase[0] = "after"
            traces = sum(simulation.TRACE_COUNTS.values()) - traces0
            if args.trace:
                from .trace import reduce_trace
                rec.trace = reduce_trace(
                    glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0])
        finally:
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)
    compiles = sum(1 for e, _, p in log
                   if p == "window" and e in COMPILE_EVENTS)
    print(json.dumps({"window": {
        "calls": len(outs), "seconds": w1 - w0,
        "program_traces": traces, "compile_events": compiles,
        "cache_dir": cache_dir}}), flush=True)
    rec.node_slots = cellmod.node_slots(cell.config, cell.mix) * len(outs)
    rec.setup_events = [(e, s) for e, s, p in log if p == "setup"]
    stats = [d.memory_stats() or {} for d in devs[:cell.chips]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    del call, tables
    jax.clear_caches()

    # the check: after the window, on what every timed call returned
    ref = cellmod.reference_records(cell.config, cell.mix, args.seed)
    ref_lanes = cellmod.lanes(ref)
    bad_values = failed = 0
    for out in outs:
        for got, want in zip(cellmod.lanes(out), ref_lanes):
            n = cellmod.compare(got, want)
            bad_values += n
            failed += n > 0
    checks = {"mismatched_values": {"value": bad_values, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct,
              "attempted": len(outs) * len(ref_lanes), "failed": failed}
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = bench.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=rec.trace.busy_s(), window_s=rec.trace.window_s)
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": rec.trace.top_ops(),
            "idle_gaps": rec.trace.idle_gaps()})
    else:
        rate = rec.node_slots / (w1 - w0)
        values = {"node_slots_per_s": rate, "setup_s": setup_s}
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}, device=device)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
