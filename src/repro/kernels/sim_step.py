"""Fused §6.2 simulator slot step as a Pallas kernel (`impl="fused"`).

One `pallas_call` per simulated slot fuses the three phases the batched
XLA implementation (`repro.core.simulation._make_slot_step_batched`)
expresses as separate fused families:

  1. **winner arbitration** — the segmented min over encoded priority
     keys (segment id = node·2n + requested port), realized as 2n static
     masked column-min reductions so no `(N, 2nQ, 2n)` candidate tensor
     (and no scatter) ever exists,
  2. **port-level acceptance** — the sequential same-slot space-reuse
     fixed point, unrolled over the 2n port levels on an (N, 2n) carry
     (bitwise the reference sweep's acceptance),
  3. **apply** — the one-hot clears + transit + injection where-chains
     writing the next (rec, birth, port) state.

Validation contract: the kernel runs in interpret mode on the CPU, and
the differential suite validates it against the `reference` oracle;
given identical pre-drawn traffic the fused step is bitwise-equal to
`impl="batched"`.

TPU LOWERING FAILS: compiled for a v5e chip with `interpret=False`,
Mosaic refuses the first in-kernel gather, `sender = nbr[:, opp]` in
phase 1 (`ValueError: Shape mismatch in input, indices and output`,
from `_gather_lowering_rule`); the multi-index gathers
(`flat_rec[sender, in_widx]`, `whas[sender, ports]`) and the
`take_along_axis` in `gather_port` come next.  So
`repro.core.simulation._make_slot_step_fused` raises on a TPU backend
instead of running this kernel, and the chip runs `impl="batched"`.
Porting it means replacing every data-dependent gather (the neighbour
maps are fixed permutations per port, which static shifts may serve).

VIRTUAL CHANNELS — this kernel is V=1-only.  The VC credit-flow router
(``SimConfig(vcs>=2)``) carries an (N, 2n, V, Q) state plus per-(port,
VC) credit counters that this kernel's flat (N, 2nQ) layout does not
model; `repro.core.simulation._get_runner` rejects `impl="fused"` with
`vcs > 1` with a clear error.  Run VC configurations with
`impl="batched"` (vectorized credit router) or `impl="reference"` (the
per-(port, VC) oracle) — see docs/simulator.md, "Virtual channels &
credit flow".

Transient faults (`repro.core.fault_schedule.FaultSchedule`) need NO
kernel changes: the kernel is epoch-oblivious by design.  The fused slot
step in `repro.core.simulation` resolves the current epoch inside the
`lax.scan` carry — gathering that slot's `link_ok` / `dst_live_fixed`
slices from the traced (E, …) stacks, dropping packets enqueued at
just-died nodes, and re-consulting `policy_ports` for stale carried
ports — and hands this kernel exactly the static-shaped per-slot masks
it has always taken.  That keeps the bitwise-parity contract with the
batched step intact under schedules (tests/test_transient_sim.py runs
the scheduled parity cells).

Tiling: the grid walks node tiles of `block_nodes` rows for the heavy
phase-3 writes — the `(tile, 2n, Q, n)` state tensors are the kernel's
big residents, so VMEM holds one tile of them at a time.  Phases 1–2 are
global (arbitration and acceptance couple every node to its neighbours
through the sender/receiver gathers) but touch only (N, 2nQ)-sized
fields, which fit VMEM comfortably for pod-scale N; with the default
`block_nodes=None` (one tile = all nodes) no work is duplicated.  Faults
and policies enter exactly as in the batched path: a `link_ok` mask
excludes dead channels from arbitration and `policy_ports` picks the
carried output port.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.routing_engine import policy_ports


def _first_port(rec):
    """DOR next hop via the simulator's own `_next_port` (shared, not
    duplicated: the rule is under the bitwise-parity contract)."""
    from repro.core.simulation import _next_port
    port, _, _ = _next_port(rec)
    return port.astype(jnp.int32)


def _slot_step_kernel(rec_ref, birth_ref, port_ref, prio_ref, slot_ref,
                      want_ref, tr_r_ref, tr_p_ref, tr_v_ref, nbr_ref,
                      hop_ref, link_ok_ref, dst_live_ref,
                      # outputs
                      nrec_ref, nbirth_ref, nport_ref, deliver_ref, lat_ref,
                      can_ref, drop_ref, depp_ref,
                      *, n: int, N: int, P: int, Q: int, policy: str,
                      trivial: bool, block_nodes: int):
    # CONTRACT: this kernel mirrors `simulation._make_slot_step_batched`
    # phase for phase and must stay BITWISE-equal to it — any change to
    # the winner encoding, acceptance recurrence or apply masks there
    # must land here too (a kernel can't call the XLA step's closures, so
    # the logic is necessarily duplicated).  tests/test_fused_impl.py
    # enforces the equality on every scenario × pattern cell in CI.
    PQ = P * Q
    key_dtype = jnp.int16 if PQ <= 127 else jnp.int32
    BIG = key_dtype(np.iinfo(np.dtype(key_dtype)).max)
    NO_PORT = jnp.int8(P)
    ports = jnp.arange(P)
    ports8 = jnp.arange(P, dtype=jnp.int8)
    qi = jnp.arange(Q)[None, None, :]
    i = pl.program_id(0)
    r0 = i * block_nodes

    rec = rec_ref[...]
    birth = birth_ref[...]
    port = port_ref[...]
    prio = prio_ref[...]
    slot = slot_ref[0]
    nbr = nbr_ref[...]
    link_ok = None if trivial else link_ok_ref[...] != 0

    opp = jnp.arange(P) ^ 1
    sender = nbr[:, opp]                               # (N, P)
    receiver = nbr
    hop = hop_ref[...]                                 # (P, n) unit hops

    occ = birth >= 0
    portv = jnp.where(occ, port, NO_PORT)
    port_flat = portv.reshape(N, PQ)

    def gather_port(per_port, fill, idx):
        padded = jnp.concatenate(
            [per_port, jnp.full((N, 1), fill, per_port.dtype)], axis=1)
        return jnp.take_along_axis(padded, idx.astype(jnp.int32), axis=1)

    # ---- phase 1: winner per (node, out-port), segmented min ----
    rot = (jnp.arange(PQ, dtype=jnp.int32)[None, :] + slot) % PQ
    enc = prio.astype(key_dtype) * key_dtype(PQ) + rot.astype(key_dtype)
    w_enc = jnp.stack(
        [jnp.min(jnp.where(port_flat == ports8[p], enc, BIG), axis=1)
         for p in range(P)], axis=1)                   # (N, P)
    if link_ok is not None:
        w_enc = jnp.where(link_ok, w_enc, BIG)
    whas = w_enc < BIG
    widx = jnp.where(whas,
                     (w_enc.astype(jnp.int32) % PQ - slot) % PQ, 0)
    w_srcq = widx // Q
    is_winner = gather_port(w_enc, BIG, port_flat) == enc

    flat_rec = rec.reshape(N, PQ, n)
    flat_birth = birth.reshape(N, PQ)

    # per-link view at the receiver of in-port p
    in_has = whas[sender, ports]
    in_widx = widx[sender, ports]
    in_rec = flat_rec[sender, in_widx]                 # (N, P, n)
    in_birth = flat_birth[sender, in_widx]
    in_srcq = in_widx // Q
    rec_after = in_rec - hop[None]
    done = jnp.abs(rec_after.astype(jnp.int32)).sum(-1) == 0
    deliver = in_has & done
    turning = in_srcq != ports[None]
    need = jnp.where(turning, 2, 1)
    free0 = Q - occ.sum(axis=2)

    # ---- phase 2: acceptance fixed point, unrolled over port levels ----
    vac = jnp.zeros((N, P), jnp.int32)
    accs = []
    for p in range(P):
        acc_p = in_has[:, p] & ~done[:, p] & (
            free0[:, p] + vac[:, p] >= need[:, p])
        dep_w = (deliver[:, p] | acc_p)[receiver[:, p]] & whas[:, p]
        vac = vac + jnp.where(
            dep_w[:, None] & (w_srcq[:, p][:, None] == ports[None, :]), 1, 0)
        accs.append(acc_p)
    acc = jnp.stack(accs, axis=1)                      # (N, P)
    moved = deliver | acc
    lat = jnp.where(deliver, slot + 1 - in_birth, 0).astype(jnp.int32)

    # ---- phase 3: clears + transit/injection one-hot writes (tiled) ----
    dep_port = moved[receiver, ports] & whas
    dep_slot = is_winner & (gather_port(dep_port.astype(jnp.int8), 0,
                                        port_flat) != 0)
    birth_cleared = jnp.where(dep_slot, -1, flat_birth).reshape(N, P, Q)
    free_mask = birth_cleared < 0
    slot_f = jnp.argmax(free_mask, axis=2)
    slot_l = (Q - 1) - jnp.argmax(free_mask[:, :, ::-1], axis=2)
    if trivial:
        port_in = _first_port(rec_after)
    else:
        port_in = policy_ports(rec_after, link_ok[:, None, :], policy)

    want = want_ref[...] != 0
    tr_p = tr_p_ref[...].astype(jnp.int32)
    tr_v = tr_v_ref[...] != 0
    depcnt = dep_slot.reshape(N, P, Q).sum(axis=2)
    freeq_post = free0 + depcnt - acc
    if trivial:
        drop = jnp.zeros((N,), bool)
        can = want & (jnp.take_along_axis(
            freeq_post, tr_p[:, None], axis=1)[:, 0] >= 2) & tr_v
    else:
        drop = want & ~(dst_live_ref[...] != 0)
        ipc = jnp.minimum(tr_p, P - 1)
        can = (want & ~drop & (jnp.take_along_axis(
            freeq_post, ipc[:, None], axis=1)[:, 0] >= 2)
            & tr_v & (tr_p < P))

    def tile(a):
        return jax.lax.dynamic_slice_in_dim(a, r0, block_nodes, axis=0)

    wmask_t = tile(acc)[:, :, None] & (qi == tile(slot_f)[:, :, None])
    imask_t = (tile(can)[:, None, None]
               & (ports8[None, :, None] == tile(tr_p).astype(jnp.int8)
                  [:, None, None])
               & (qi == tile(slot_l)[:, :, None]))
    # portv (not raw port): free slots carry NO_PORT in the next state,
    # exactly like the batched step's re-bound port array
    rec_t, birth_t, port_t = tile(rec), tile(birth_cleared), tile(portv)
    nrec_ref[...] = jnp.where(
        imask_t[..., None], tile(tr_r_ref[...])[:, None, None, :],
        jnp.where(wmask_t[..., None], tile(rec_after)[:, :, None, :], rec_t))
    nbirth_ref[...] = jnp.where(
        imask_t, slot.astype(birth.dtype),
        jnp.where(wmask_t, tile(in_birth)[:, :, None].astype(birth.dtype),
                  birth_t))
    nport_ref[...] = jnp.where(
        imask_t, tile(tr_p).astype(jnp.int8)[:, None, None],
        jnp.where(wmask_t, tile(port_in)[:, :, None].astype(jnp.int8),
                  port_t))
    deliver_ref[...] = tile(deliver).astype(jnp.int8)
    lat_ref[...] = tile(lat)
    can_ref[...] = tile(can).astype(jnp.int8)
    drop_ref[...] = tile(drop).astype(jnp.int8)
    depp_ref[...] = tile(dep_port).astype(jnp.int8)


def fused_slot_step(rec, birth, port, prio, slot, want, tr_r, tr_p, tr_v,
                    nbr, link_ok=None, dst_live_fixed=None, *,
                    policy: str = "dor", block_nodes: int | None = None,
                    interpret: bool = True):
    """One fused simulator slot: (rec, birth, port) state + this slot's
    pre-drawn traffic → next state and the per-node/per-port outcome
    fields the caller reduces into counters.

    rec: (N, 2n, Q, n); birth: (N, 2n, Q); port: (N, 2n, Q) int8;
    prio: (N, 2nQ) uint8; slot: () int32; want: (N,) bool (injection
    desire incl. backlog); tr_r: (N, n) records; tr_p: (N,) int8 ports;
    tr_v: (N,) bool validity; nbr: (N, 2n) int32.  `link_ok` (N, 2n) and
    `dst_live_fixed` (N,) switch on the scenario path (both or neither).

    Returns (new_rec, new_birth, new_port, deliver, lat, can, drop,
    dep_port) — deliver/can/drop/dep_port as int8 masks, lat as int32
    latency contributions.  Bitwise-equal to the batched slot update.

    CONTRACT (latency telemetry): `lat` is slot+1−birth exactly where
    `deliver` is set and 0 elsewhere, so the wrapper reconstructs each
    delivered packet's birth as slot+1−lat.  The measured-window filter
    (birth ≥ warmup) and the age-bucket histogram both run OUTSIDE the
    kernel on these two outputs — keep them intact when changing the
    kernel, or the wrapper-side telemetry (and its bitwise parity with
    the batched step) silently breaks."""
    N, P, Q, n = rec.shape
    trivial = link_ok is None
    if block_nodes is None or N % block_nodes:
        block_nodes = N
    grid = (N // block_nodes,)
    to8 = lambda a: a.astype(jnp.int8)  # noqa: E731
    hop = np.zeros((P, n), np.int64)
    hop[np.arange(P), np.arange(P) // 2] = 1 - 2 * (np.arange(P) % 2)
    inputs = [rec, birth, port, prio, jnp.asarray(slot, jnp.int32)[None],
              to8(want), tr_r, tr_p.astype(jnp.int8), to8(tr_v), nbr,
              jnp.asarray(hop, rec.dtype),
              (jnp.ones((N, P), jnp.int8) if trivial else to8(link_ok)),
              (jnp.ones((N,), jnp.int8) if trivial
               else to8(dst_live_fixed))]

    def full_spec(a):
        return pl.BlockSpec(a.shape, lambda i, nd=a.ndim: (0,) * nd)

    def node_spec(shape):
        return pl.BlockSpec((block_nodes,) + shape[1:],
                            lambda i, nd=len(shape): (i,) + (0,) * (nd - 1))

    out_shapes = [
        jax.ShapeDtypeStruct(rec.shape, rec.dtype),
        jax.ShapeDtypeStruct(birth.shape, birth.dtype),
        jax.ShapeDtypeStruct(port.shape, jnp.int8),
        jax.ShapeDtypeStruct((N, P), jnp.int8),     # deliver
        jax.ShapeDtypeStruct((N, P), jnp.int32),    # lat
        jax.ShapeDtypeStruct((N,), jnp.int8),       # can
        jax.ShapeDtypeStruct((N,), jnp.int8),       # drop
        jax.ShapeDtypeStruct((N, P), jnp.int8),     # dep_port
    ]
    kern = functools.partial(
        _slot_step_kernel, n=n, N=N, P=P, Q=Q, policy=policy,
        trivial=trivial, block_nodes=block_nodes)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[full_spec(a) for a in inputs],
        out_specs=[node_spec(s.shape) for s in out_shapes],
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*inputs)
