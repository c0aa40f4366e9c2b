"""The slot step's per-slot port lookup: each queue slot reads a per-port
value through its requested port, sentinel port P reading a fill value.
`_port_lookup` does it with a select chain over the P ports; these tests
hold it to the padded `take_along_axis` it replaces, bit for bit, and pin
that neither batched slot step gathers at every queue slot any more.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scenario, Torus
from repro.core.simulation import (_init_state, _make_ctx,
                                   _make_slot_step_batched,
                                   _make_slot_step_vc_batched, _make_traffic,
                                   _port_lookup, build_tables)

ROWS, SLOTS_PER_ROW = 64, 48


def _padded_gather(per_port, fill, port_flat):
    """The lookup as a gather over the table padded with a fill column."""
    padded = jnp.concatenate(
        [per_port, jnp.full((per_port.shape[0], 1), fill, per_port.dtype)],
        axis=1)
    return jnp.take_along_axis(padded, port_flat.astype(jnp.int32), axis=1)


@pytest.mark.parametrize("P", [6, 8, 12])
@pytest.mark.parametrize("dtype", [np.bool_, np.int8, np.int16, np.int32])
def test_port_lookup_equals_padded_gather(dtype, P):
    rng = np.random.default_rng(P * 101 + np.dtype(dtype).itemsize)
    if dtype is np.bool_:
        per_port = rng.random((ROWS, P)) < 0.5
        fill = False
    else:
        info = np.iinfo(dtype)
        per_port = rng.integers(info.min, info.max, (ROWS, P),
                                endpoint=True).astype(dtype)
        fill = info.max
    # every port and the sentinel P, the sentinel in every row
    port_flat = rng.integers(0, P + 1, (ROWS, SLOTS_PER_ROW)).astype(np.int8)
    port_flat[:, 0] = P
    per_port = jnp.asarray(per_port)
    port_flat = jnp.asarray(port_flat)
    got = jax.jit(_port_lookup, static_argnums=1)(per_port, fill, port_flat)
    want = _padded_gather(per_port, fill, port_flat)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


G = Torus(8, 8, 8)
N, P, Q = G.order, 6, 4


def _gather_out_shapes(jaxpr):
    """Output shapes of every `gather` in a jaxpr and its sub-jaxprs."""
    shapes = []

    def walk(jx):
        for e in jx.eqns:
            if e.primitive.name == "gather":
                shapes.extend(tuple(v.aval.shape) for v in e.outvars)
            for p in e.params.values():
                sub = getattr(p, "jaxpr", None)
                if sub is not None:
                    walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)

    walk(jaxpr.jaxpr)
    return shapes


@pytest.mark.parametrize("faulted", [False, True], ids=["trivial", "faulted"])
@pytest.mark.parametrize("V", [1, 2])
def test_slot_step_has_no_per_slot_gather(V, faulted):
    """No gather in the fault-free or faulted slot step is shaped like the
    per-slot lookup, (N, P·V·Q).  (The faulted VC step keeps a gather of
    one record coordinate per slot, (N, P, V, Q, 1), in its fault-aware
    port choice: that reads the packet's own record, not a port table.)"""
    scen = (Scenario.random_link_faults(G, 4, seed=1, policy="adaptive")
            if faulted else Scenario())
    t = build_tables(G)
    ctx = _make_ctx(t, G, "uniform", 0, Q, scen, vcs=V)
    make = _make_slot_step_batched if V == 1 else _make_slot_step_vc_batched
    step = make(ctx, warmup=8)
    state = _init_state(ctx, 0.5, "batched", 32)
    tr = _make_traffic(ctx, state, jax.random.PRNGKey(0), 32)
    tr1 = jax.tree_util.tree_map(lambda a: a[0], tr)
    shapes = _gather_out_shapes(jax.make_jaxpr(step)(state, tr1))
    assert shapes, "the step's node-axis gathers should still be seen"
    per_slot = [s for s in shapes if s == (N, P * V * Q)]
    assert not per_slot, per_slot
