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
    if cfg.get("faults"):
        out["faults"] = dict(cfg["faults"], down_at=8, up_at=20)
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
