"""`BENCHMARK.json` and the files it names: the contract's shape, names
and units, and every configuration, mix and reader found by name."""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

from chipbench import reference
from chipbench.spec import Benchmark
from chipbench_testkit import ROOT

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")

DOC = json.loads((ROOT / "BENCHMARK.json").read_text())
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}
WORKLOADS = [w["name"] for w in DOC["workloads"]]
CONFIGS = [c["name"] for c in DOC["configs"]]


def test_top_level_and_entry_keys():
    assert set(DOC) == TOP
    for key, (must, may) in ENTRY_KEYS.items():
        assert 1 <= len(DOC[key])
        for e in DOC[key]:
            assert must <= set(e) <= must | may, (key, e["name"])


def test_command_paths_and_run_length():
    assert DOC["command"] == ["python3", "-m", "chipbench.run"]
    for p in DOC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert isinstance(DOC["run_seconds"], int)
    assert 1 <= DOC["run_seconds"] <= 51


def _names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in DOC[key]:
            yield e["name"]
    for w in DOC["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in DOC["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert NAME.fullmatch(name), name


@pytest.mark.parametrize("metric", DOC["end_to_end"] + DOC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_units_and_sources(metric):
    assert UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in DOC["end_to_end"]}
        assert set(metric.get("workloads", WORKLOADS)) <= set(WORKLOADS)
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_free_text_is_one_short_line():
    for e in DOC["configs"] + DOC["workloads"]:
        for k in ("why", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and not re.search(r"[\n\t]",
                                                               e[k])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves_by_name(workload):
    b = Benchmark(ROOT)
    cell = b.cell(workload)
    assert cell.chips in (1, 4)
    assert cell.mix.n_lanes() >= 1
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(b.reader(m["name"]))


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_states_the_deployment(config):
    """The file under `paths` names a program constructor whose lattice
    is the stated generator matrix, and the reference rebuilds the
    program's neighbour and routing tables from that matrix alone."""
    from repro import core
    from repro.core.simulation import build_tables
    entry = next(c for c in DOC["configs"] if c["name"] == config)
    assert entry["file"].startswith("chipbench/configs/")
    cfg = Benchmark(ROOT).config(config)
    assert cfg["reduced"] == entry["reduced"]
    g = getattr(core, cfg["topology"]["constructor"])(
        *cfg["topology"]["args"])
    assert g.order == cfg["nodes"]
    assert np.array_equal(g.hermite, cfg["generator_matrix"])
    lat = reference.Lattice(cfg["generator_matrix"])
    t = build_tables(g)
    assert np.array_equal(lat.nbr, t.neighbors)
    assert np.array_equal(lat.rec_a, t.records_a)
    assert np.array_equal(lat.rec_b, t.records_b)


def test_cells_are_unique_pairs():
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in DOC["workloads"]} == set(CONFIGS)
