"""Chunkwise mLSTM kernel (xLSTM matrix-memory recurrence).

Grid (batch·heads, n_chunks) with chunks innermost: the (m × m) matrix
memory ``C`` and normalizer ``n`` live in VMEM scratch across a
sequence's chunks (TPU grids are sequential over the trailing axis), so
the state never round-trips HBM between chunks — the chunk-boundary
states that XLA's ``associative_scan`` path materializes (O(S/c · m²)
HBM) stay on-chip.

Per chunk (c tokens): intra-chunk quadratic term (c×c MXU matmuls with
cumulative-gate decay), inter-chunk term against the carried state, and
the stabilizer-free sigmoid gating used by the model (see
models/recurrent.py for the numerics note).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _mlstm_kernel(chunk, q_ref, k_ref, v_ref, i_ref, lf_ref, o_ref,
                  C_ref, n_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    q = q_ref[0].astype(jnp.float32)  # (c, m)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    ii = i_ref[0]  # (c, 1)
    lf = lf_ref[0]  # (c, 1)

    # Mosaic lowers no cumsum and no (c, 1) -> (1, c) transpose: both
    # come from f32 matmuls against triangular / identity masks, at
    # HIGHEST precision so the gates are not rounded to bf16.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = col <= row

    def exact_dot(x, y, dims):
        return jax.lax.dot_general(
            x, y, (dims, ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    tril = causal.astype(jnp.float32)
    cum = exact_dot(tril, lf, ((1,), (0,)))  # (c, 1): inclusive cumsum
    cum_t = exact_dot(lf, tril, ((0,), (1,)))  # (1, c): the same, as a row
    ii_t = exact_dot(ii, (row == col).astype(jnp.float32),
                     ((0,), (0,)))  # (1, c)
    last = jnp.sum(lf, axis=0, keepdims=True)  # (1, 1): gate at chunk end

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (c, c)
    A = jnp.where(causal, scores * jnp.exp(cum - cum_t) * ii_t, 0.0)

    C = C_ref[...]
    ecum = jnp.exp(cum)  # (c, 1)
    num = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) + ecum * jax.lax.dot_general(
        q, C, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    den = jnp.sum(A, axis=1, keepdims=True) + ecum * jax.lax.dot_general(
        q, n_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = (num / jnp.maximum(jnp.abs(den), 1.0)).astype(o_ref.dtype)

    # carry the chunk-boundary state forward in VMEM
    w_s = jnp.exp(last - cum) * ii  # (c, 1)
    C_ref[...] = jnp.exp(last) * C + jax.lax.dot_general(
        k * w_s, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    n_ref[...] = jnp.exp(last) * n_ref[...] + jnp.sum(
        k * w_s, axis=0, keepdims=True
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunkwise_bh(q, k, v, i_gate, log_f, *, chunk: int = 64,
                       interpret: Optional[bool] = None):
    """q,k,v: (BH, S, m) with q pre-scaled by 1/sqrt(m);
    i_gate, log_f: (BH, S) fp32.  Returns h: (BH, S, m)."""
    bh, s, m = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    grid = (bh, s // chunk)
    qkv_spec = pl.BlockSpec((1, chunk, m), lambda b, j: (b, j, 0))
    gate_spec = pl.BlockSpec((1, chunk, 1), lambda b, j: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_mlstm_kernel, chunk),
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, gate_spec, gate_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, m), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((m, m), jnp.float32),  # matrix memory C
            pltpu.VMEM((1, m), jnp.float32),  # normalizer n
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v, i_gate[..., None], log_f[..., None])
