"""Paged decode attention over RIMMS block tables (serving hot spot).

This is the kernel-level integration of the paper's technique: the KV
cache lives in a page pool handed out by the RIMMS marking systems
(:mod:`repro.core.paged_kv`); per-sequence *block tables* (the
``hete_Data`` resource pointers) drive the kernel's BlockSpec index maps
through **scalar prefetch** — page p of sequence b streams
``k_pages[block_table[b, p]]`` HBM→VMEM with no host-side gather and no
dense copy of the cache.

Grid: (batch, n_pages) with pages innermost; online-softmax scratch
persists across a sequence's pages (TPU grids are sequential over the
trailing axis).  GQA is handled in-kernel (no KV repetition in HBM).
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

NEG_INF = -1e30


def _paged_kernel(page_size, n_kv, group, scale,
                  bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hq = n_kv * group
    q = q_ref[0].astype(jnp.float32).reshape(n_kv, group, -1)  # (Hkv,G,d)
    k = k_ref[0].astype(jnp.float32)  # (page, Hkv, d)
    v = v_ref[0].astype(jnp.float32)
    # batched over kv heads: (Hkv, G, d) x (Hkv, page, d) -> (Hkv, G, page)
    s = jax.lax.dot_general(
        q, k.swapaxes(0, 1),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
    pos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, group, page_size), 2
    )
    valid = pos < len_ref[b]
    s = jnp.where(valid, s, NEG_INF)
    s2 = s.reshape(hq, page_size)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
    pexp = jnp.exp(s2 - m_new)  # (Hq, page)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_ref[:, :1] + jnp.sum(pexp, axis=1, keepdims=True),
        l_ref.shape,
    )
    pv = jax.lax.dot_general(
        pexp.reshape(n_kv, group, page_size), v.swapaxes(0, 1),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # (Hkv, G, d)
    acc_ref[...] = acc_ref[...] * alpha + pv.reshape(hq, -1)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == np_ - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, lengths, *,
                    interpret: Optional[bool] = None):
    """q: (B, Hq, d); k_pages/v_pages: (P, page, Hkv, d);
    block_table: (B, n_pages) int32; lengths: (B,) int32.
    Returns (B, Hq, d)."""
    B, hq, d = q.shape
    P, page, n_kv, _ = k_pages.shape
    group = hq // n_kv
    n_pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda b, p, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, page, n_kv, d),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, page, n_kv, d),
                         lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda b, p, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page, n_kv, group, scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table, lengths, q, k_pages, v_pages)
