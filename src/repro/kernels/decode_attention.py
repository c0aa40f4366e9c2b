"""Single-query attention over a long KV cache (decode_32k / long_500k path).

Grid (BH, kv_blocks): one query row per batch·head, KV streamed through VMEM
in `block_k` tiles; online softmax state in scratch.  Slots beyond the
current `position` are masked (the cache is allocated at max length).  The
query is padded to 8 rows by the ops wrapper to satisfy TPU sublane tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
Q_PAD = 8  # TPU sublane minimum for fp32 tiles


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                   block_k: int, scale: float):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    position = pos_ref[0]

    @pl.when(ki * block_k <= position)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (Q_PAD, hd)
        k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (Q_PAD, bk)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols <= position, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc[...] / l_s[...]).astype(o_ref.dtype)


def decode_attention(q, k, v, position, *, block_k: int = 512,
                     interpret: bool = True):
    """q: (BH, Q_PAD, hd) padded query; k, v: (BH, S_max, hd); position:
    scalar int32 — returns (BH, Q_PAD, hd) (row 0 is the real query)."""
    BH, QP, hd = q.shape
    S = k.shape[1]
    block_k = min(block_k, S)
    assert S % block_k == 0
    grid = (BH, S // block_k)
    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               scale=1.0 / (hd ** 0.5))
    pos = jnp.asarray(position, jnp.int32).reshape(1)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, QP, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, QP, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, QP, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((QP, hd), jnp.float32),
            pltpu.VMEM((QP, 1), jnp.float32),
            pltpu.VMEM((QP, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, q, k, v)
