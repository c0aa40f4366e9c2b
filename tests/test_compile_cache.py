"""The persistent compilation cache can be placed from outside.

`JAX_COMPILATION_CACHE_DIR`, when set, wins and the program sets nothing;
otherwise the cache sits at the fixed `<checkout>/.jax_cache`.  A second
process compiling the same simulator program against the same directory
loads it instead of compiling it again.
"""
import json
import os
import subprocess
import sys

import jax

from repro import compile_cache
from repro.compile_cache import DEFAULT_DIR, configure_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one small simulator sweep; prints what the compile cost and whether the
# persistent cache served it
_PROBE = """
import json, sys
sys.path.insert(0, "src")
import jax
from jax import monitoring
from repro.compile_cache import configure_compile_cache
from repro.core import Torus
from repro.core.simulation import simulate_sweep
seen = {"backend_compile_s": 0.0, "cache_hits": 0}
def on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        seen["backend_compile_s"] += secs
def on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        seen["cache_hits"] += 1
monitoring.register_event_duration_secs_listener(on_duration)
monitoring.register_event_listener(on_event)
seen["dir"] = configure_compile_cache()
simulate_sweep(Torus(4, 4, 2), "uniform", (0.3, 0.9), slots=48, warmup=8,
               hist_bins=16)
print(json.dumps(seen))
"""


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_DIR == compile_cache.CHECKOUT / ".jax_cache"
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_second_process_loads_from_the_cache(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")

    def probe():
        out = subprocess.run([sys.executable, "-c", _PROBE], cwd=ROOT,
                             env=env, capture_output=True, text=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first = probe()
    assert first["dir"] == str(tmp_path) and first["cache_hits"] == 0
    assert any(tmp_path.iterdir())      # the programs landed there
    second = probe()
    assert second["cache_hits"] >= 1
    assert second["backend_compile_s"] < first["backend_compile_s"]
