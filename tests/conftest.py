"""Test-session bootstrap.

1. Puts `src/` on sys.path so `python -m pytest` works from a clean clone
   without the `PYTHONPATH=src` incantation (pyproject.toml's
   `tool.pytest.ini_options.pythonpath` does the same on pytest ≥ 7; this
   is the belt to that suspender).
2. Installs the offline property-testing shim (`tests/_propcheck.py`) under
   the module names `hypothesis` / `hypothesis.strategies` when the real
   package is not importable, so the property-test modules collect and run
   in network-less environments.  When hypothesis *is* installed it is used
   unchanged, under one profile with no per-example deadline: the first
   example of a simulator property JIT-compiles, which no fixed deadline
   survives.
"""
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.abspath(os.path.join(_HERE, os.pardir, "src"))
for p in (_SRC, _HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import _propcheck  # noqa: E402  (needs _HERE on sys.path)

PROPCHECK_ACTIVE = _propcheck.install()

if not PROPCHECK_ACTIVE:
    from hypothesis import settings

    settings.register_profile("repro", deadline=None)
    settings.load_profile("repro")
