"""Dense SwiGLU MLP and sort-based mixture-of-experts.

The MoE dispatch is capacity-based with a sort/gather formulation so the
compiled FLOPs reflect the *active* expert compute (E·C·D·F), not a dense
one-hot einsum — this is what makes the MODEL_FLOPS / HLO_FLOPs roofline
ratio meaningful for the MoE architectures.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .common import cast_compute, dense_init


class MLPParams(NamedTuple):
    w_gate: jax.Array   # (D, F)
    w_up: jax.Array     # (D, F)
    w_down: jax.Array   # (F, D)


def init_mlp(key, d_model: int, d_ff: int) -> MLPParams:
    k1, k2, k3 = jax.random.split(key, 3)
    return MLPParams(
        w_gate=dense_init(k1, d_model, d_ff),
        w_up=dense_init(k2, d_model, d_ff),
        w_down=dense_init(k3, d_ff, d_model))


def mlp(p: MLPParams, x):
    h = jax.nn.silu(x @ cast_compute(p.w_gate)) * (x @ cast_compute(p.w_up))
    return h @ cast_compute(p.w_down)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

class MoEParams(NamedTuple):
    router: jax.Array        # (D, E)
    w_gate: jax.Array        # (E, D, Fe)
    w_up: jax.Array          # (E, D, Fe)
    w_down: jax.Array        # (E, Fe, D)
    shared: MLPParams | None  # shared experts folded into one wider MLP


def init_moe(key, cfg) -> MoEParams:
    mc = cfg.moe
    d = cfg.d_model
    fe = mc.expert_d_ff or cfg.d_ff
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    E = mc.num_experts
    scale = 1.0 / jnp.sqrt(d)
    shared = None
    if mc.num_shared_experts:
        shared = init_mlp(ks, d, fe * mc.num_shared_experts)
    return MoEParams(
        router=dense_init(kr, d, E, scale=0.02),
        w_gate=jax.random.normal(kg, (E, d, fe), jnp.float32) * scale,
        w_up=jax.random.normal(ku, (E, d, fe), jnp.float32) * scale,
        w_down=jax.random.normal(kd, (E, fe, d), jnp.float32) / jnp.sqrt(fe),
        shared=shared)


def moe_capacity(cfg, num_tokens: int) -> int:
    mc = cfg.moe
    cap = int(mc.capacity_factor * num_tokens * mc.top_k / mc.num_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8


def moe(p: MoEParams, cfg, x):
    """Mixture-of-experts block.  Uses the explicit expert-parallel shard_map
    path when a mesh with a >1 'model' axis is in scope (production), else
    the single-device local path (tests, smoke configs)."""
    from repro.parallel.sharding import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("model", 1) > 1 \
            and cfg.moe.num_experts % mesh.shape["model"] == 0:
        return moe_sharded(p, cfg, x, mesh)
    return moe_local(p, cfg, x)


def _route(p: MoEParams, cfg, xt):
    """Router: top-k gates + Switch-style aux loss.  xt: (T, D)."""
    mc = cfg.moe
    T, E, K = xt.shape[0], mc.num_experts, mc.top_k
    logits = (xt @ cast_compute(p.router)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0 / (T * K))
    aux = E * jnp.sum(me * ce) * mc.router_aux_loss_coef
    return gate_vals, expert_ids, aux


def _dispatch_indices(expert_ids, K: int, C: int):
    """Sort dispatched copies by expert; rank within expert; capacity mask.
    Returns (sorted_expert, token_of, pos_in_expert, keep) each (T·K,)."""
    TK = expert_ids.size
    flat_expert = expert_ids.reshape(-1)
    sort_idx = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[sort_idx]
    pos_in_expert = jnp.arange(TK) - jnp.searchsorted(
        sorted_expert, sorted_expert, side="left")
    token_of = sort_idx // K
    keep = pos_in_expert < C
    return sorted_expert, token_of, pos_in_expert, keep, sort_idx


def _expert_ffn(xe, wg, wu, wd):
    """(E, C, D) × per-expert SwiGLU → (E, C, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, cast_compute(wg)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, cast_compute(wu))
    return jnp.einsum("ecf,efd->ecd", h, cast_compute(wd))


def moe_sharded(p: MoEParams, cfg, x, mesh):
    """Expert-parallel MoE via shard_map.

    Activations are replicated over the 'model' axis (standard TP layout), so
    dispatch is COMM-FREE: each model-rank scatters only the token copies
    bound for its own E/tp experts.  The only collectives are the FSDP
    all-gather of the expert weights (over 'data') and one psum of the
    combined output (over 'model') — exactly the EP traffic a production
    system pays.  Overflow beyond per-rank capacity drops (GShard)."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import current_fsdp_axis, current_rules

    mc = cfg.moe
    B, S_, D = x.shape
    tp = mesh.shape["model"]
    E, K = mc.num_experts, mc.top_k
    E_loc = E // tp
    fsdp_axis = current_fsdp_axis()
    rules = current_rules() or {}
    batch_axes = rules.get("hidden", P(None))[0]  # how x's batch is sharded
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fe = mc.expert_d_ff or cfg.d_ff
    fsdp_on = (fsdp_axis is not None and D % mesh.shape.get(fsdp_axis, 1) == 0
               and mesh.shape.get(fsdp_axis, 1) > 1)
    w_spec = P("model", fsdp_axis if fsdp_on else None, None)

    # local token count per device (batch may be unsharded)
    def _sz(axes):
        n = 1
        if axes is None:
            return 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            n *= mesh.shape[a]
        return n
    T_loc = (B // _sz(batch_axes)) * S_
    C_loc = moe_capacity(cfg, T_loc)

    def local(xl, router, wg, wu, wd):
        rank = jax.lax.axis_index("model")
        if fsdp_on:
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, D)
        gate_vals, expert_ids, aux = _route(
            MoEParams(router, None, None, None, None), cfg, xt)
        sorted_expert, token_of, pos_in_expert, keep, sort_idx = \
            _dispatch_indices(expert_ids, K, C_loc)
        # copies bound for MY experts only
        mine = (sorted_expert >= rank * E_loc) & \
               (sorted_expert < (rank + 1) * E_loc) & keep
        slot = jnp.where(
            mine, (sorted_expert - rank * E_loc) * C_loc + pos_in_expert,
            E_loc * C_loc - 1)
        src = jnp.where(mine[:, None], xt[token_of], jnp.zeros((), xt.dtype))
        xe = jnp.zeros((E_loc * C_loc, D), xt.dtype).at[slot].add(src)
        ye = _expert_ffn(xe.reshape(E_loc, C_loc, D), wg, wu, wd)
        contrib = ye.reshape(E_loc * C_loc, D)
        gathered = jnp.where(mine[:, None], contrib[slot],
                             jnp.zeros((), xt.dtype))
        gates_sorted = gate_vals.reshape(-1)[sort_idx]
        yt = jnp.zeros((T, D), xt.dtype).at[token_of].add(
            gathered * gates_sorted[:, None].astype(xt.dtype))
        yt = jax.lax.psum(yt, "model")          # combine across expert ranks
        # aux is identical on every model rank; gate it to rank 0 before the
        # psum so reverse-mode doesn't over-count its router cotangent tp×
        aux = jax.lax.psum(jnp.where(rank == 0, aux, 0.0), "model")
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return yt.reshape(Bl, Sl, D), aux

    all_axes = tuple(mesh.axis_names)
    x_spec = P(batch_axes, None, None)
    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec,
                  P("model", None, fsdp_axis if fsdp_on else None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p.router, p.w_gate, p.w_up, p.w_down)
    if p.shared is not None:
        y = y + mlp(p.shared, x)
    return y, aux


def moe_local(p: MoEParams, cfg, x):
    """x: (B, S, D) → (y, aux_loss).

    Sort-based dispatch: tokens are ordered by expert id, sliced into
    (E, C, D) with capacity C, processed by a batched per-expert SwiGLU, and
    combined back with the router weights.  Overflow tokens beyond capacity
    are dropped (standard GShard semantics)."""
    mc = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = mc.num_experts, mc.top_k
    C = moe_capacity(cfg, T)

    xt = x.reshape(T, D)
    logits = (xt @ cast_compute(p.router)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)              # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                  # renormalise

    # --- aux load-balancing loss (Switch-style) ---
    me = probs.mean(axis=0)                                      # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(
        1.0 / (T * K))
    aux = E * jnp.sum(me * ce) * mc.router_aux_loss_coef

    # --- dispatch: rank tokens within their expert ---
    flat_expert = expert_ids.reshape(-1)                         # (T*K,)
    sort_idx = jnp.argsort(flat_expert, stable=True)             # group by expert
    sorted_expert = flat_expert[sort_idx]
    # position of each dispatched copy within its expert group
    pos_in_expert = jnp.arange(T * K) - jnp.searchsorted(
        sorted_expert, sorted_expert, side="left")
    token_of = sort_idx // K                                     # source token
    keep = pos_in_expert < C
    # overflow copies are folded onto the last slot with a zero contribution
    slot = jnp.where(keep, sorted_expert * C + pos_in_expert, E * C - 1)

    from repro.parallel.sharding import constrain
    src = jnp.where(keep[:, None], xt[token_of], jnp.zeros((), x.dtype))
    xe = jnp.zeros((E * C, D), x.dtype).at[slot].add(src)
    xe = constrain(xe.reshape(E, C, D), "expert_tokens")

    # --- per-expert SwiGLU (batched einsum over E) ---
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, cast_compute(p.w_gate)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, cast_compute(p.w_up))
    ye = jnp.einsum("ecf,efd->ecd", h, cast_compute(p.w_down))   # (E, C, D)
    ye = constrain(ye, "expert_tokens")

    # --- combine: gather back and weight by gate ---
    gates_sorted = gate_vals.reshape(-1)[sort_idx]
    contrib = ye.reshape(E * C, D)
    gathered = jnp.where(keep[:, None], contrib[slot], jnp.zeros((), x.dtype))
    yt = jnp.zeros((T, D), x.dtype).at[token_of].add(
        gathered * gates_sorted[:, None].astype(x.dtype))

    if p.shared is not None:
        yt = yt + mlp(p.shared, xt)
    return yt.reshape(B, S, D), aux
