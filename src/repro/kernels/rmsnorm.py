"""Fused RMSNorm kernel: one pass over rows, mean-square + scale in VMEM."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)            # (rows, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, weight, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = True):
    """x: (..., D) → same shape; rows processed in VMEM tiles."""
    orig_shape = x.shape
    D = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= int(s)
    x2 = x.reshape(rows, D)
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        block_rows = 1
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, weight)
    return out.reshape(orig_shape)
