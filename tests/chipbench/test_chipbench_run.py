"""The command end to end on the host CPU at a cut size: every cell's
traffic, the result line, the refusals, a cell added from files alone,
and the check refusing a timed path broken underneath."""
from __future__ import annotations

import hashlib
import json

import pytest

from chipbench import cell
from chipbench import run as runmod
from chipbench.spec import Benchmark
from chipbench_testkit import (FAULT_CELLS, FAULT_LOADS, FAULT_SETS, ROOT,
                               add_fault_cell, bench_copy, cut_config,
                               run_cell)

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in DOC["workloads"]]


def _lanes_a_call(workload):
    c = Benchmark(ROOT).cell(workload)
    return cell.node_slots(c.config, c.mix) // (c.config["nodes"]
                                                * c.mix.slots)


SWEEPS = [w for w in WORKLOADS if _lanes_a_call(w) > 1]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def cut_root(tmp_path_factory):
    root = bench_copy(tmp_path_factory.mktemp("bench"))
    for entry in FAULT_CELLS:
        add_fault_cell(root, entry)
    return root


def test_refuses_a_cpu_device(capsys):
    with pytest.raises(SystemExit) as e:
        runmod.main(["--workload", WORKLOADS[0], "--seed", "1",
                     "--seconds", "1"], root=ROOT)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_no_result_without_the_program(tmp_path, capsys):
    root = bench_copy(tmp_path / "bare")
    (root / "src").unlink()
    rc = runmod.main(["--workload", WORKLOADS[0], "--seed", "1",
                      "--seconds", "1"], root=root)
    assert rc != 0 and capsys.readouterr().out == ""


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_at_cut_size(workload, cut_root, monkeypatch, capsys):
    line = run_cell(cut_root, workload, monkeypatch, capsys)
    assert set(line) == LINE_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    e2e = {m["name"] for m in DOC["end_to_end"]
           if workload in m.get("workloads", WORKLOADS)}
    assert set(line["metrics"]) == e2e
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    assert line["checks"]["mismatched_values"] == {"value": 0, "limit": 0}


def test_traced_run_reports_per_layer_metrics(cut_root, monkeypatch,
                                              capsys):
    line = run_cell(cut_root, WORKLOADS[0], monkeypatch, capsys, trace=1)
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert list(line)[-1] == "checks" and line["correct"] is True
    # the host CPU has no device plane: only the host-side readers report
    assert set(line["metrics"]) == {"compile_s", "tables_s"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_needs_only_new_files_and_entries(tmp_path, monkeypatch,
                                                   capsys):
    root = bench_copy(tmp_path / "bench")
    before = _digest(root)
    fcc = json.loads((ROOT / "chipbench/configs/fcc4d-8.json").read_text())
    new_cfg = dict(cut_config(fcc), name="fcc4d-2-new")
    (root / "chipbench/configs/fcc4d-2-new.json").write_text(
        json.dumps(new_cfg))
    (root / "chipbench/traffic/uniform.two-loads.json").write_text(
        json.dumps(dict(entry="simulate_sweep", pattern="uniform",
                        loads=[0.5, 0.9], seeds=1, slots=24, warmup=4,
                        hist_bins=8)))
    (root / "chipbench/metrics/lanes_per_call.py").write_text(
        "def read(run):\n    return run.node_slots / (32 * 24)\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append(dict(
        name="fcc4d-2-new", source="arXiv:1311.2019", reduced=[],
        file="chipbench/configs/fcc4d-2-new.json", why="a new cell"))
    doc["workloads"].append(dict(
        name="fcc2.two-loads", config="fcc4d-2-new",
        traffic="uniform.two-loads", chips=1, why="a new cell"))
    doc["per_layer"].append(dict(
        name="lanes_per_call", unit="lanes", better="higher",
        source="program_counter", layer="entry points",
        moves="node_slots_per_s", workloads=["fcc2.two-loads"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    added = {"BENCHMARK.json", "fcc4d-2-new.json", "uniform.two-loads.json",
             "lanes_per_call.py"}
    for entry in FAULT_CELLS:               # a fault sweep through each entry
        added |= add_fault_cell(root, entry)[1]
    changed = {k for k, v in _digest(root).items() if before.get(k) != v}
    assert changed == {p for p in changed if p.name in added}
    assert len(changed) == len(added)
    line = run_cell(root, "fcc2.two-loads", monkeypatch, capsys, trace=1)
    assert line["correct"] is True and line["attempted"] == 2
    assert line["metrics"]["lanes_per_call"]["value"] == 2.0
    for entry, name in FAULT_CELLS.items():
        line = run_cell(root, name, monkeypatch, capsys)
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] == (len(FAULT_SETS[entry]["sets"])
                                     * len(FAULT_LOADS) * 1)
        assert line["checks"]["mismatched_values"] == {"value": 0,
                                                       "limit": 0}


def _unchanged_step(make):
    def maker(ctx, warmup):
        step = make(ctx, warmup)

        def slot_step(state, tr):
            _, ys = step(state, tr)
            return state, ys
        return slot_step
    return maker


def _altered_finish(finish):
    def wrapped(state, *a, **kw):
        out = finish(state, *a, **kw)
        return dict(out, delivered=out["delivered"] + (state["slot"] == 5))
    return wrapped


def _half_batch(grid):
    def wrapped(out, axes_sizes, *a, **kw):
        res = grid(out, axes_sizes, *a, **kw)
        flat = res.reshape(-1)
        half = flat.size // 2
        flat[half:2 * half] = flat[:half]
        return res
    return wrapped


FAULTS = {
    "state_unchanged": [("_make_slot_step_batched", _unchanged_step),
                        ("_make_slot_step_vc_batched", _unchanged_step)],
    "answer_altered": [("_finish_slot", _altered_finish)],
    "half_batch": [("_result_grid", _half_batch)],
}


# a one-lane call has no half batch to leave out; one chip, no exchange
BROKEN = [(f, w) for f in sorted(FAULTS)
          for w in WORKLOADS + sorted(FAULT_CELLS.values())
          if f != "half_batch" or w in SWEEPS + list(FAULT_CELLS.values())]


@pytest.mark.parametrize("fault,workload", BROKEN)
def test_check_refuses_a_broken_timed_path(fault, workload, cut_root,
                                           monkeypatch, capsys):
    """The run skips the device check and goes on with the program's
    timed path broken underneath; `correct` has to come out false."""
    from repro.core import simulation
    monkeypatch.setattr(simulation, "_RUNNER_CACHE", {})
    for name, breaker in FAULTS[fault]:
        monkeypatch.setattr(simulation, name,
                            breaker(getattr(simulation, name)))
    line = run_cell(cut_root, workload, monkeypatch, capsys)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert line["checks"]["mismatched_values"]["value"] > 0
