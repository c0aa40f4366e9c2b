"""The one traffic generator: a mix file of parameters becomes the
program's call and the reference's lanes.

A mix (`chipbench/traffic/<name>.json`) names the public entry that
drives it and its parameters:

  * ``"entry": "simulate_sweep"`` — ``loads`` × ``seeds`` lanes in one
    device program; lane (l, s) runs base seed ``--seed + s``;
  * ``"entry": "simulate"`` — one lane at ``load`` from ``--seed``;
  * ``"entry": "simulate_scenario_sweep"`` or
    ``"simulate_schedule_sweep"`` — the configuration's K ``link_sets``
    (static patterns, or timelines of link failures and repairs) ×
    ``loads`` × ``seeds`` lanes in one device program; every set runs
    the same (load, seed) lanes of traffic;

plus ``pattern``, ``slots``, ``warmup`` and ``hist_bins``.  Every run of
a cell, whatever its seed, does the same amount of work: the seed
changes which draws are made, never how many.

`lane_keys` derives each lane's PRNG key from the seed exactly as the
program documents it (`fold_in(PRNGKey(seed + 17), l)` for a multi-load
sweep, the unfolded key otherwise), and `draw` makes that lane's traffic
again on the host CPU: injection uniforms, the record coin, the
destination as a delta index and the 8-bit arbitration priorities.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

ENTRIES = ("simulate_sweep", "simulate", "simulate_scenario_sweep",
           "simulate_schedule_sweep")
FAULT_SWEEPS = ENTRIES[2:]       # their lanes are the config's link_sets


@dataclass(frozen=True)
class Lane:
    load: float
    seed: int
    fold: int | None          # load index folded into the key, if any


@dataclass(frozen=True)
class Mix:
    name: str
    entry: str
    pattern: str
    slots: int
    warmup: int
    hist_bins: int
    loads: tuple[float, ...]
    seeds: int

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Mix":
        entry = d["entry"]
        if entry not in ENTRIES:
            raise ValueError(f"mix {name}: unknown entry {entry!r}; the "
                             f"harness drives {', '.join(ENTRIES)}")
        if entry == "simulate":
            loads, seeds = (float(d["load"]),), 1
        else:
            loads, seeds = tuple(float(x) for x in d["loads"]), int(d["seeds"])
        if d["pattern"] != "uniform":
            raise ValueError(f"mix {name}: the generator draws uniform "
                             f"traffic only, not {d['pattern']!r}")
        return cls(name=name, entry=entry, pattern=d["pattern"],
                   slots=int(d["slots"]), warmup=int(d["warmup"]),
                   hist_bins=int(d["hist_bins"]), loads=loads, seeds=seeds)

    def lanes(self, seed: int) -> list[list[Lane]]:
        """(loads × seeds) grid of lanes, in the program's result order."""
        multi = len(self.loads) > 1
        return [[Lane(load, seed + s, li if multi else None)
                 for s in range(self.seeds)]
                for li, load in enumerate(self.loads)]

    def n_lanes(self) -> int:
        return len(self.loads) * self.seeds


def lane_key(lane: Lane):
    import jax
    key = jax.random.PRNGKey(lane.seed + 17)
    return key if lane.fold is None else jax.random.fold_in(key, lane.fold)


@functools.lru_cache(maxsize=None)
def _drawer(slots: int, N: int, PVQ: int):
    import jax
    import jax.numpy as jnp

    def draw(key):
        ku, kd, kc, kp = jax.random.split(jax.random.fold_in(key, 2), 4)
        return dict(
            u=jax.random.uniform(ku, (slots, N)),
            di=jax.random.randint(kd, (slots, N), 1, N),
            coin=(jax.random.uniform(kc, (slots, N)) < 0.5).astype(jnp.int32),
            prio=jax.random.bits(kp, (slots, N, PVQ), jnp.uint8))

    return jax.jit(draw)


def draw(lane: Lane, slots: int, N: int, PVQ: int) -> dict:
    """The lane's pre-drawn traffic as numpy arrays, made on the host
    CPU so that the chip is left alone."""
    import jax
    import numpy as np
    with jax.default_device(jax.devices("cpu")[0]):
        out = _drawer(slots, N, PVQ)(lane_key(lane))
        return {k: np.asarray(v) for k, v in out.items()}
