"""Mamba2 SSD intra-chunk kernel (the quadratic hot-spot of the SSD
algorithm).

Grid (BH, num_chunks): each step loads one chunk (Q timesteps) of one
batch·head into VMEM and produces the intra-chunk output y_diag, the chunk's
end-state contribution (P, N), and the chunk's total log-decay.  The cheap
O(nc) inter-chunk recurrence and the rank-1 y_off correction stay in XLA
(see repro.kernels.ops.ssd) — this matches how production SSD kernels split
the work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, sum_ref, *,
                chunk: int):
    x = x_ref[...].astype(jnp.float32)            # (Q, P)
    a = a_ref[...].astype(jnp.float32)            # (1, Q)
    b = b_ref[...].astype(jnp.float32)            # (Q, N)
    c = c_ref[...].astype(jnp.float32)            # (Q, N)

    a_cum = jnp.cumsum(a[0], axis=-1)             # (Q,)
    diff = a_cum[:, None] - a_cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.exp(jnp.where(rows >= cols, diff, NEG_INF))

    scores = jax.lax.dot_general(                 # C Bᵀ (Q, Q)
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(                      # (Q, P)
        scores * L, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    decay = jnp.exp(a_cum[-1] - a_cum)            # (Q,)
    bx = b * decay[:, None]
    state = jax.lax.dot_general(                  # (P, N) = xᵀ (B·decay)
        x, bx, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    st_ref[...] = state.astype(st_ref.dtype)
    sum_ref[...] = a_cum[-1].reshape(1, 1).astype(sum_ref.dtype)


def ssd_intra_chunk(xdt, Adt, Bm, Cm, *, interpret: bool = True):
    """xdt: (BH, nc, Q, P); Adt: (BH, nc, Q); Bm, Cm: (BH, nc, Q, N).
    Returns (y_diag (BH,nc,Q,P), states (BH,nc,P,N), chunk_sum (BH,nc))."""
    BH, nc, Q, P = xdt.shape
    N = Bm.shape[-1]
    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y, st, s = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((None, 1, Q), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, None, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((None, None, Q, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((None, None, P, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((None, None, 1, 1), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, P), xdt.dtype),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(xdt, Adt, Bm, Cm)
    return y, st, s[..., 0, 0]
