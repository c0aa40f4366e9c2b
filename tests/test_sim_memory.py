"""Jaxpr/memory regression tests for the simulator's segmented-min
arbitration (ISSUE 4): the winner reduce used to broadcast every queue
slot's priority key onto an (N, 2nQ, 2n) one-hot candidate tensor — the
largest per-slot intermediate of the whole program.  These tests pin its
absence at the jaxpr level (no intermediate of that shape, and no
per-slot intermediate at or above its element count) and at the compiled
level (a cost_analysis bytes-accessed budget counted in units of that
tensor), so the blowup cannot silently return.
"""
import jax
import numpy as np
import pytest

from repro.core import Scenario, Torus
from repro.core.simulation import (_get_runner, _init_state, _make_ctx,
                                   _make_slot_step_batched, _make_traffic,
                                   build_tables)

# n=3 (P=6) so the forbidden (N, PQ, P) tensor is strictly bigger than the
# legitimate (N, PQ, n) record view — the size bound below separates them
G = Torus(8, 8, 8)
N, P, Q = G.order, 6, 4
PQ = P * Q
SLOTS = 32


def _slot_step_jaxpr(scenario=None):
    t = build_tables(G)
    ctx = _make_ctx(t, G, "uniform", 0, Q, scenario)
    step = _make_slot_step_batched(ctx, warmup=8)
    state = _init_state(ctx, 0.5, "batched", SLOTS)
    tr = _make_traffic(ctx, state, jax.random.PRNGKey(0), SLOTS)
    tr1 = jax.tree_util.tree_map(lambda a: a[0], tr)
    return jax.make_jaxpr(step)(state, tr1)


def _all_eqn_shapes(jaxpr):
    """Shapes of every intermediate of a jaxpr, descending into sub-jaxprs
    (scan bodies, pjit calls)."""
    shapes = []

    def walk(jx):
        for e in jx.eqns:
            for v in e.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    shapes.append(tuple(aval.shape))
            for p in e.params.values():
                sub = getattr(p, "jaxpr", None)
                if sub is not None:
                    walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)

    walk(jaxpr.jaxpr)
    return shapes


@pytest.mark.parametrize("scen", [None, Scenario.random_link_faults(
    G, 4, seed=1, policy="adaptive")], ids=["trivial", "faulted"])
def test_slot_step_has_no_candidate_tensor(scen):
    """No per-slot intermediate is shaped (N, 2nQ, 2n) — in any axis
    order — and none reaches its element count: the segmented min keeps
    the largest winner-phase tensor at O(N·2nQ)."""
    shapes = _all_eqn_shapes(_slot_step_jaxpr(scen))
    blowup = tuple(sorted((N, PQ, P)))
    offenders = [s for s in shapes if tuple(sorted(s)) == blowup]
    assert not offenders, offenders
    # rec state is (N, P, Q, n) = N·PQ·n elements; the blowup was N·PQ·2n.
    # everything in the slot program must stay strictly below it.
    too_big = [s for s in shapes if int(np.prod(s)) >= N * PQ * P]
    assert not too_big, too_big


def test_compiled_bytes_accessed_pinned():
    """Budget pin on the compiled slot program, derived from shapes: one
    (N, 2nQ, 2n) int16 candidate tensor per slot is the unit.  The whole
    program moves ≈6.2 units per slot (XLA CPU, jax 0.9.0: 29.1 MB for
    this shape); materializing the candidate tensor writes it once and
    reads it once, adding ≥ 2 units — past the 7-unit budget."""
    t = build_tables(G)
    ctx = _make_ctx(t, G, "uniform", 0, Q)
    runner = _get_runner(t, ctx, slots=SLOTS, warmup=8, impl="batched",
                         n_loads=1)
    state = _init_state(ctx, 0.5, "batched", SLOTS)
    comp = runner.lower(state, jax.random.PRNGKey(17)).compile()
    accessed = comp.cost_analysis().get("bytes accessed")
    if accessed is None:  # backend didn't report it — don't silently pass
        pytest.skip("cost_analysis has no 'bytes accessed' on this backend")
    candidate_bytes = N * PQ * P * np.dtype(np.int16).itemsize
    budget = 7 * SLOTS * candidate_bytes
    assert accessed < budget, (accessed, budget)
