"""RG-LRU linear-recurrence kernel (RecurrentGemma prefill hot spot).

h_t = a_t · h_{t-1} + b_t, elementwise over (B, S, D).

XLA's ``associative_scan`` materializes O(log S) intermediate passes over
HBM; this kernel reads a,b once and writes h once — one VMEM-resident
(1, S, 128) lane tile per grid step, sequential fori_loop over time
inside VMEM (the op is memory-bound; arithmetic is negligible).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

LANES = 128


def _rg_lru_kernel(a_ref, b_ref, h0_ref, o_ref, hN_ref):
    S = a_ref.shape[1]

    def body(t, h):  # h: (1, lanes)
        row = pl.ds(t, 1)
        h = a_ref[0, row, :] * h + b_ref[0, row, :]
        o_ref[0, row, :] = h
        return h

    hN_ref[0] = jax.lax.fori_loop(0, S, body, h0_ref[0])


@functools.partial(jax.jit, static_argnames=("block_lanes", "interpret"))
def rg_lru_scan(a, b, h0, *, block_lanes: int = LANES,
                interpret: Optional[bool] = None):
    """a, b: (B, S, D) f32; h0: (B, D) initial state.
    Returns (h_seq (B,S,D), h_final (B,D)).  ``block_lanes`` (a multiple
    of 128 dividing D) tunes lanes per grid step — the recurrence is
    elementwise over lanes, so any tiling is bit-identical (ISSUE 10)."""
    B, S, D = a.shape
    assert block_lanes % LANES == 0 and D % block_lanes == 0, (D, block_lanes)
    grid = (B, D // block_lanes)
    seq_spec = pl.BlockSpec((1, S, block_lanes), lambda i, j: (i, 0, j))
    # States carry a unit axis: a TPU block's last two dims must tile
    # (8, 128) or span the array, and (1, lanes) over (B, D) does neither.
    vec_spec = pl.BlockSpec((1, 1, block_lanes), lambda i, j: (i, 0, j))
    hs, hN = pl.pallas_call(
        _rg_lru_kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, vec_spec],
        out_specs=[seq_spec, vec_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(a, b, h0.reshape(B, 1, D))
    return hs, hN.reshape(B, D)
