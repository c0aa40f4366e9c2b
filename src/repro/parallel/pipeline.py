"""Pipeline parallelism: GPipe-style microbatch schedule over a "pipe" mesh
axis, built from shard_map + lax.ppermute.

Layer-stacked params (L, ...) are sharded over the pipe axis (L/P layers per
stage).  Each tick every stage applies its layers to the activation it
holds and ppermutes the result downstream; microbatch m enters at tick m and
leaves after P−1+m ticks (the usual (P−1)/M bubble).  Differentiable (the
transpose of ppermute is the reverse ppermute), so one jax.grad gives true
pipeline-parallel training.

This is the PP building block exercised in tests on small meshes; the fixed
production meshes of the dry-run use DP×FSDP×TP/EP (DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn, stacked_params, x, mesh, *,
                   num_microbatches: int, axis: str = "pipe"):
    """Run `layer_fn(params_slice, x) -> x` over L stacked layers, pipelined.

    stacked_params: pytree with leading dim L (L % pipe_size == 0)
    x: (B, ...) with B % num_microbatches == 0
    Returns: (B, ...) outputs (replicated over the pipe axis)."""
    nstages = mesh.shape[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % nstages == 0, (L, nstages)
    M = num_microbatches
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])

    def stage(params_local, xs_full):
        rank = jax.lax.axis_index(axis)
        ticks = M + nstages - 1

        def apply_stage(h):
            def body(c, p):
                return layer_fn(p, c), None
            out, _ = jax.lax.scan(body, h, params_local)
            return out

        def tick(carry, t):
            buf = carry                       # activation entering my stage
            feed = xs_full[jnp.clip(t, 0, M - 1)]
            h = jnp.where(rank == 0, feed, buf)
            act = apply_stage(h)
            # pass downstream (stage s -> s+1); last stage's output wraps to
            # 0 but is masked out by the collection logic
            nxt = jax.lax.ppermute(
                act, axis, [(i, (i + 1) % nstages) for i in range(nstages)])
            # collect: on the last stage, tick t emits microbatch t-(P-1)
            emit = act * jnp.where(rank == nstages - 1, 1.0, 0.0).astype(act.dtype)
            return nxt, emit

        _, emitted = jax.lax.scan(tick, jnp.zeros_like(xs_full[0]),
                                  jnp.arange(ticks))
        # emitted[t] valid for t in [P-1, P-1+M) → reorder to microbatch order
        out = jax.lax.dynamic_slice_in_dim(emitted, nstages - 1, M, axis=0)
        # only the last stage emitted nonzero → psum broadcasts it to all
        return jax.lax.psum(out, axis)

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    y = jax.shard_map(
        stage, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(),
        check_vma=False,
    )(stacked_params, xs)
    return y.reshape(B, *x.shape[1:])
