"""Production mesh construction + the lattice-topology view of each pod.

`make_production_mesh` is a FUNCTION (not module-level state) so importing
this module never touches jax device state.  The physical interconnect of
each pod is modelled as a cubic crystal lattice graph from the paper:
256 chips = BCC(4), 512 = PC(8), 1024 = FCC(8) — the §3.4 power-of-two
upgrade path, which is also our elastic-scaling story.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many real/forced devices exist."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def pod_lattice(num_chips: int):
    """The cubic crystal lattice graph modelling one pod's ICI network."""
    from repro.core import crystal_for_order
    return crystal_for_order(num_chips)


def mesh_summary(mesh) -> str:
    return f"mesh{dict(mesh.shape)} devices={mesh.devices.size}"
