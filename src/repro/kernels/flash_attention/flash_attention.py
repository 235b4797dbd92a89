"""Causal flash attention kernel (train / prefill hot spot).

Standard TPU pallas flash pattern: grid (batch·heads, q_blocks,
k_blocks) with the k dimension innermost — TPU grids execute
sequentially over the last axis, so VMEM scratch (running max m, sum l,
accumulator acc) persists across k blocks of one q block (online
softmax).  BlockSpecs stream (block, head_dim) tiles of Q/K/V from HBM;
VMEM per step ≈ 4 · block · head_dim · 4 B.

Fully-masked k blocks (k_start > q_end) are skipped via ``pl.when``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _flash_kernel(block_q, block_k, scale, causal,
                  q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def flash_attention_bh(q, k, v, *, causal: bool = True,
                       block_q: int = DEFAULT_BLOCK_Q,
                       block_k: int = DEFAULT_BLOCK_K,
                       interpret: Optional[bool] = None):
    """q,k,v: (BH, S, d) — batch·heads flattened. Returns (BH, S, d)."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    scale = 1.0 / math.sqrt(d)
    grid = (bh, pl.cdiv(s, block_q), pl.cdiv(s, block_k))
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_q, block_k, scale, causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
