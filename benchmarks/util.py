"""Benchmark harness utilities: timing + the `name,us_per_call,derived` CSV
contract shared by every benchmark module, plus the machine-readable row
store behind `benchmarks.run --json`."""
from __future__ import annotations

import time
from contextlib import contextmanager

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def reset():
    ROWS.clear()


def parse_derived(derived: str) -> dict:
    """'a=1.5;b=2x;c=foo' → {'a': 1.5, 'b': 2.0, 'c': 'foo'} (trailing 'x'
    of speedup values is stripped; unparseable values stay strings)."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            try:
                out[k] = float(v.rstrip("x"))
            except ValueError:
                out[k] = v
    return out


def rows_as_json() -> dict:
    """The run's rows in the schema consumed by benchmarks.check_regression
    (and committed as BENCH_baseline.json)."""
    return {
        "schema": 1,
        "rows": [
            {"name": n, "us_per_call": us, "derived": parse_derived(d)}
            for n, us, d in ROWS
        ],
    }


@contextmanager
def timed(name: str, derived_fn=lambda: ""):
    t0 = time.perf_counter()
    yield
    emit(name, (time.perf_counter() - t0) * 1e6, derived_fn())


def header(device: dict):
    """The device line every row below was measured on, then the CSV
    header."""
    print(f"# device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    print("name,us_per_call,derived", flush=True)
