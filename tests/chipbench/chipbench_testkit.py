"""Shared helpers of the harness's tests: a copy of the benchmark at a
cut size, and one run of a cell through `chipbench.run.main` with the
device check steered to the host CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax

from chipbench import run as runmod

ROOT = Path(__file__).resolve().parents[2]
# the cut size of each topology constructor: same kind, a few dozen nodes
CUT_ARGS = {"FourD_FCC": [2], "Torus": [4, 4, 2, 2]}
CUT_SLOTS = 32


def cut_config(cfg: dict) -> dict:
    from repro import core
    args = CUT_ARGS[cfg["topology"]["constructor"]]
    g = getattr(core, cfg["topology"]["constructor"])(*args)
    out = dict(cfg, topology=dict(cfg["topology"], args=args),
               generator_matrix=g.hermite.tolist(), nodes=g.order)
    faults = cfg.get("faults")
    if faults and faults["kind"] == "link_flap":
        out["faults"] = dict(faults, down_at=8, up_at=20)
    elif faults:
        # link_sets: each link onto the cut lattice, a static event kept
        # static, a timed one into the cut run's 32 slots
        out["faults"] = dict(faults, sets=[[dict(
            ev, link=[ev["link"][0] % g.order, ev["link"][1]],
            down_at=ev["down_at"] and 8,
            up_at=None if ev["up_at"] is None else 20)
            for ev in events] for events in faults["sets"]])
    return out


def cut_mix(mix: dict) -> dict:
    return dict(mix, slots=CUT_SLOTS, warmup=min(mix["warmup"], 8))


def bench_copy(dst: Path, cut: bool = True) -> Path:
    """A checkout at `dst` with the benchmark's files (cut to a few dozen
    nodes and 32 slots when `cut`) and the program linked in."""
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(ROOT / "src")
    if cut:
        for f in (dst / "chipbench" / "configs").glob("*.json"):
            f.write_text(json.dumps(cut_config(json.loads(f.read_text()))))
        for f in (dst / "chipbench" / "traffic").glob("*.json"):
            f.write_text(json.dumps(cut_mix(json.loads(f.read_text()))))
    return dst


def link_sets(*sets) -> dict:
    """A `link_sets` faults entry from sets of (link, down_at, up_at)."""
    return dict(kind="link_sets", sets=[
        [dict(link=link, down_at=down, up_at=up) for link, down, up in s]
        for s in sets])


# K = 3 fault sets on T(4,4,2,2) at V = 2, the pristine lane first
FAULT_SETS = {
    "simulate_scenario_sweep": link_sets(
        [], [([3, 0], 0, None), ([17, 5], 0, None)],
        [([40, 2], 0, None), ([9, 7], 0, None), ([60, 1], 0, None)]),
    "simulate_schedule_sweep": link_sets(
        [], [([3, 0], 4, 18), ([17, 5], 0, None)],
        [([40, 2], 6, None), ([9, 7], 2, 26), ([60, 1], 10, 12)]),
}
FAULT_LOADS = [0.5, 0.9]
FAULT_CELLS = {entry: f"t4422-vc2.{entry.split('_')[1]}"
               for entry in FAULT_SETS}


def add_fault_cell(root: Path, entry: str) -> tuple[str, set[str]]:
    """A cell of K = 3 link_sets driven by `entry`, added to the
    checkout at `root` as one configuration, one mix and one workloads
    entry; returns its name and the names of the files it adds."""
    name = FAULT_CELLS[entry]
    tag = name.split(".")[1]
    cfg_name, mix_name = f"torus-4422-{tag}", f"uniform.{tag}-sets"
    vc2 = json.loads((ROOT / "chipbench/configs/torus-16x8x8x8-vc2.json")
                     .read_text())
    cfg = dict(cut_config(vc2), name=cfg_name, faults=FAULT_SETS[entry])
    (root / f"chipbench/configs/{cfg_name}.json").write_text(
        json.dumps(cfg))
    (root / f"chipbench/traffic/{mix_name}.json").write_text(json.dumps(
        dict(entry=entry, pattern="uniform", loads=FAULT_LOADS, seeds=1,
             slots=CUT_SLOTS, warmup=0, hist_bins=8)))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append(dict(
        name=cfg_name, source="arXiv:1311.2019", reduced=[],
        file=f"chipbench/configs/{cfg_name}.json", why="a fault sweep"))
    doc["workloads"].append(dict(name=name, config=cfg_name,
                                 traffic=mix_name, chips=1,
                                 why="a fault sweep"))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return name, {"BENCHMARK.json", f"{cfg_name}.json", f"{mix_name}.json"}


def run_cell(root: Path, workload: str, monkeypatch, capsys, *,
             seed: int = 2**31 + 7, trace: int = 0) -> dict:
    """One run of `workload` on the host CPU; returns its last line."""
    import repro.compile_cache
    monkeypatch.setattr(runmod, "check_device",
                        lambda chips: jax.devices()[:max(chips, 1)])
    # leave the persistent cache alone: it would follow the test worker
    monkeypatch.setattr(repro.compile_cache, "configure_compile_cache",
                        lambda: "off")
    rc = runmod.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", "0.01", "--trace", str(trace)],
                     root=root)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
