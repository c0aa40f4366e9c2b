"""`chip_smoke.py` refuses to run anywhere but on a TPU, and its phases
hold at a tiny size on the CPU (a rehearsal: the device check is steered
here, in the test, and the shapes are cut)."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(out):
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_refuses_the_cpu():
    out = _run(ROOT, SCRIPT)
    _no_result(out)
    assert "not a TPU" in out.stderr


def test_fails_without_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    _no_result(_run(tmp_path, str(lone)))


def test_phases_hold_at_a_tiny_size_on_cpu(monkeypatch, capsys):
    dev = {"platform": "cpu", "kind": "rehearsal", "count": 1}
    monkeypatch.setattr(chip_smoke, "check_device", lambda: dev)
    monkeypatch.setattr(chip_smoke, "configure_compile_cache",
                        lambda: "not configured")
    monkeypatch.setattr(chip_smoke, "PARITY", dict(
        chip_smoke.PARITY, graph=(4, 4, 2, 2), slots=64, warmup=16))
    monkeypatch.setattr(chip_smoke, "MAIN", dict(
        chip_smoke.MAIN, torus=(8, 4, 4, 4), fcc=4, slots=96, warmup=24))
    monkeypatch.setattr(chip_smoke, "COMPOSED", dict(
        chip_smoke.COMPOSED, graph=(4, 4, 2, 2), slots=64, down_at=16,
        up_at=40))
    chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": dev}
    phases = [json.loads(line)["phase"] for line in lines[:-1]]
    assert phases == ["device", "parity", "main", "main", "main",
                      "composed", "cache"]
    gain = json.loads(lines[-4])
    assert gain["crystal_peak"] > gain["torus_peak"]
