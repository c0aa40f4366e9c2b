"""Puts the checkout's root on sys.path, so that the benchmark's harness
(`chipbench`) imports in every test worker."""
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
