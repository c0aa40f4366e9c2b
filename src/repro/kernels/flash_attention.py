"""Blocked flash attention for TPU (pl.pallas_call + BlockSpec VMEM tiling).

Canonical TPU formulation: 3D grid (batch·heads, q_blocks, k_blocks); the
innermost grid dimension iterates sequentially on a core, so the online
softmax state (m, l, acc) lives in VMEM scratch and persists across k-blocks.
Block shapes are MXU-aligned (q/k blocks multiples of 128 in production; the
defaults here divide the assigned shapes).  Causal masking skips fully-masked
blocks and applies a triangular mask on the diagonal block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
                  causal: bool, block_q: int, block_k: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    def _compute():
        q = q_ref[...].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(                           # (bq, bk)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_s[...]                                  # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)                 # (bk, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv
        m_s[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal
        @pl.when(ki * block_k <= qi * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc[...] / l_s[...]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = True):
    """q, k, v: (BH, S, hd) → (BH, S, hd).  GQA is folded by the ops wrapper."""
    BH, S, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (BH, S // block_q, S // block_k)
    kernel = functools.partial(
        _flash_kernel, causal=causal, block_q=block_q, block_k=block_k,
        scale=1.0 / (hd ** 0.5))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
