"""`BENCHMARK.json` and the files it names: the contract's shape, names
and units, and every configuration, mix and reader found by name."""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

from chipbench import cell, reference
from chipbench.spec import Benchmark
from chipbench.traffic import Mix
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


FIGS_5_8 = [[(load, s, li) for s in (0, 1)]
            for li, load in enumerate((0.2, 0.4, 0.6, 0.8, 1.0))]
# each cell's node-slots a call and its (loads × seeds) lane grid as
# (load, seed offset from --seed, load index folded into the key)
PINNED = {
    "fcc8.uniform.sweep": (23592960, FIGS_5_8),
    "t16888-vc2.linkflap": (2097152, [[(0.4, 0, None)]]),
    "t16888.uniform.sweep": (23592960, FIGS_5_8),
    "fcc8.uniform.steady": (33554432, [[(load, 0, li)] for li, load in
                                       enumerate((0.5, 0.6, 0.8, 1.0))]),
}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_cells_keep_their_work_and_lanes(workload):
    c = Benchmark(ROOT).cell(workload)
    seed = 2**31 + 5
    assert cell.node_slots(c.config, c.mix) == PINNED[workload][0]
    assert [[(lane.load, lane.seed - seed, lane.fold) for lane in row]
            for row in c.mix.lanes(seed)] == PINNED[workload][1]


def _sets_config(vcs=2, event=None):
    """Cell 2's configuration with K = 2 link_sets: the pristine set and
    one of `event` (a flap of link (0, 0) by default)."""
    cfg = Benchmark(ROOT).config("torus-16x8x8x8-vc2")
    event = event or dict(link=[0, 0], down_at=64, up_at=160)
    cfg["router"] = dict(cfg["router"], vcs=vcs)
    cfg["faults"] = dict(kind="link_sets", sets=[[], [event]])
    return cfg


def _mix(entry, **kw):
    return Mix.from_dict("m", dict(dict(
        entry=entry, pattern="uniform", loads=[0.4], seeds=1, slots=256,
        warmup=0, hist_bins=64), **kw))


REFUSED = {
    "link_sets at V = 1": (
        lambda: cell.link_sets(_sets_config(vcs=1),
                               _mix("simulate_schedule_sweep")),
        "V = 1: the reference has no faulted V = 1 router"),
    "node event": (
        lambda: cell.link_sets(
            _sets_config(event=dict(node=7, down_at=64, up_at=None)),
            _mix("simulate_schedule_sweep")),
        "node events are refused: the reference has no dead-node"),
    "timed event, scenario entry": (
        lambda: cell.link_sets(_sets_config(),
                               _mix("simulate_scenario_sweep")),
        "every event needs down_at 0 and up_at null"),
    "link_sets, plain sweep": (
        lambda: cell.link_sets(_sets_config(), _mix("simulate_sweep")),
        "and only they sweep a configuration's link_sets"),
    "no set": (
        lambda: cell.link_sets(dict(_sets_config(), faults=dict(
            kind="link_sets", sets=[])), _mix("simulate_schedule_sweep")),
        "link_sets needs at least one set"),
    "unknown entry": (
        lambda: _mix("simulate_explore"),
        "unknown entry 'simulate_explore'; the harness drives"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_refusals_say_why(case):
    make, message = REFUSED[case]
    with pytest.raises(ValueError, match=re.escape(message)):
        make()


def test_link_sets_read_as_events():
    cfg = _sets_config()
    assert cell.link_sets(cfg, _mix("simulate_schedule_sweep")) == [
        [], [((0, 0), 64, 160)]]
    assert cell.node_slots(cfg, _mix("simulate_schedule_sweep",
                                     loads=[0.4, 0.8], seeds=3)) == (
        8192 * 256 * 2 * 2 * 3)
