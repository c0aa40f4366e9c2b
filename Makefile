# Entry points — no PYTHONPATH=src incantations needed (pytest picks up
# src/ via pyproject's pythonpath ini + tests/conftest.py; the benchmark
# driver gets it from this Makefile).
#
# CI (.github/workflows/ci.yml) runs: `make test` + `make bench-smoke` on
# the test matrix, `make bench-check` as the perf-regression gate, and
# `make lint` in the lint job.  Policy details: docs/ci.md.
PY ?= python
BENCH_JSON ?= /tmp/bench_current.json
BENCH_NIGHTLY_JSON ?= /tmp/bench_nightly.json
BENCH_TOLERANCE ?= 0.30
# sections whose numbers the regression gate tracks (routing Mrec/s +
# simulator, scenario-engine & transient-timeline slots/s + the latency
# histogram overhead ratio + the VC router's overhead/saturation rows +
# the heterogeneous-link overhead/express-saturation rows + the
# fault-composition VC-under-schedule/faulted-express rows + the
# topology explorer's candidates/s and front-quality rows);
# keep in sync with BENCH_baseline.json
BENCH_GATE_SECTIONS = routing,sim,scenarios,transient,latency,vc,hetero,compose,explore

.PHONY: test test-fast bench bench-quick bench-routing bench-smoke \
        bench-nightly bench-check bench-baseline lint \
        explore explore-smoke

# --durations surfaces the slowest tests so suite-time regressions are
# visible in every CI log
test:
	$(PY) -m pytest -q --durations=15

# analytic + routing + scenario-unit modules (NO simulator sweeps): the
# integer-matrix/lattice/crystal/symmetry stack, both routing backends,
# the fault-BFS table rebuilds and the fault-schedule epoch compiler —
# everything that runs in seconds without compiling a slot-step program.
# The simulator differential/property suites stay in plain `make test`.
test-fast:
	$(PY) -m pytest -q tests/test_intmat.py tests/test_lattice.py \
	    tests/test_crystals.py tests/test_routing.py \
	    tests/test_routing_engine.py tests/test_symmetry.py \
	    tests/test_fault_bfs.py tests/test_fault_schedule.py \
	    tests/test_propcheck.py tests/test_check_regression.py \
	    tests/test_bench_driver.py tests/test_explore.py

bench:
	PYTHONPATH=src $(PY) -m benchmarks.run

bench-quick:
	PYTHONPATH=src $(PY) -m benchmarks.run --quick

# routing engine throughput only (ISSUE 1 acceptance numbers)
bench-routing:
	PYTHONPATH=src $(PY) -m benchmarks.run --only routing

# fast sanity pass CI runs on every matrix entry: cheap analytic sections
# + the quick simulator / scenario-engine / transient-timeline / latency
# telemetry benchmarks (covers the K-scenario and
# K-schedule one-compile sweeps, the device fault-BFS sweeps and the
# histogram-overhead rows); exercises the whole bench plumbing
bench-smoke:
	PYTHONPATH=src $(PY) -m benchmarks.run --quick \
	    --only table1,table2,throughput,sim,scenarios,transient,latency,vc,hetero,compose,explore

# the nightly CI job: FULL mode, every section (incl. the N=4096
# sweeps), JSON for the
# dated bench-trend artifact (docs/ci.md "Nightly bench trend")
bench-nightly:
	PYTHONPATH=src $(PY) -m benchmarks.run --json $(BENCH_NIGHTLY_JSON)

# perf-regression gate: measure the gated sections twice (quick mode,
# JSON; per-metric best-of — a load spike slows one run, a regression
# slows both) and compare against the committed baseline; >30% fails
bench-check:
	PYTHONPATH=src $(PY) -m benchmarks.run --quick \
	    --only $(BENCH_GATE_SECTIONS) --json $(BENCH_JSON)
	PYTHONPATH=src $(PY) -m benchmarks.run --quick \
	    --only $(BENCH_GATE_SECTIONS) --json $(BENCH_JSON).2
	PYTHONPATH=src $(PY) -m benchmarks.check_regression \
	    --baseline BENCH_baseline.json \
	    --current $(BENCH_JSON) $(BENCH_JSON).2 \
	    --tolerance $(BENCH_TOLERANCE)

# refresh the committed baseline (run on the CI machine class, then commit)
bench-baseline:
	PYTHONPATH=src $(PY) -m benchmarks.run --quick \
	    --only $(BENCH_GATE_SECTIONS) --json BENCH_baseline.json

# closed-loop topology exploration (ISSUE 10): seeded evolutionary
# search over HNF lattices + mixed-radix tori, Pareto front over
# throughput x p99 x faulted capacity with RTT/FCC/BCC + torus pinned.
# `explore` is the full acceptance demo; `explore-smoke` is the CI
# budget (<=8 generations, analytic p99, N <= a few hundred cells) and
# FAILS unless a discovered lattice still dominates the torus baseline.
explore:
	PYTHONPATH=src $(PY) -m repro.explore --require-dominance

explore-smoke:
	PYTHONPATH=src $(PY) -m repro.explore --smoke --require-dominance

# ruff config lives in pyproject.toml [tool.ruff]; skips politely when
# ruff isn't installed (offline containers)
lint:
	@command -v ruff >/dev/null 2>&1 \
	    && ruff check src benchmarks tests \
	    || echo "ruff not installed; skipping lint (CI installs it)"
