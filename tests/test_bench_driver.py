"""The benchmark driver's CLI contract: an unknown --only section name
must be a clear upfront error listing the valid choices (ISSUE 4
satellite) — not a generic "section failed" swallowed by the driver's
keep-going exception handler.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks import util  # noqa: E402


def _main(argv):
    old = sys.argv
    sys.argv = ["benchmarks.run"] + argv
    try:
        bench_run.main()
    finally:
        sys.argv = old


def test_unknown_section_is_a_clear_upfront_error():
    with pytest.raises(SystemExit) as e:
        _main(["--only", "tabel1,routing"])
    msg = str(e.value.code)
    assert "unknown section" in msg and "tabel1" in msg
    assert "routing" not in msg.split("choose from")[0].replace(
        "tabel1,", "")         # only the bad name is reported as unknown
    for valid in ("table1", "sim", "scenarios", "transient"):
        assert valid in msg.split("choose from")[1]


def test_known_sections_still_run(capsys, monkeypatch):
    calls = []
    monkeypatch.setattr(bench_run, "configure_compile_cache",
                        lambda: calls.append(1))
    util.reset()
    _main(["--only", "table1", "--quick"])
    out = capsys.readouterr().out
    assert calls == [1]        # the compile cache is placed before any run
    assert "# device: platform=cpu" in out and "count=" in out
    assert "name,us_per_call,derived" in out
    assert any(r[0].startswith("table1") for r in util.ROWS)


def test_json_meta_records_device(tmp_path, monkeypatch):
    """--json names the device the rows were measured on."""
    import json
    monkeypatch.setattr(bench_run, "configure_compile_cache", lambda: None)
    util.reset()
    out = tmp_path / "rows.json"
    _main(["--only", "table1", "--quick", "--json", str(out)])
    device = json.loads(out.read_text())["meta"]["device"]
    assert device["platform"] == "cpu"
    assert device["count"] >= 1 and device["kind"]
