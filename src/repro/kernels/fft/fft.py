"""Batched radix-2 Stockham FFT kernel (the paper's FFT accelerator, §4.1).

TPU adaptation of the Xilinx FFT IP / cuFFT stage: one VMEM-resident
batch tile (block_rows × N complex as separate re/im planes), iterative
**Stockham autosort** — no bit-reversal permutation, no gather tables:
each of the log2(N) stages is slice + butterfly + concat, with twiddle
factors computed in-kernel from ``broadcasted_iota`` (cos/sin on the
VPU), so the kernel captures no host constants.

Supports power-of-two N (the paper sweeps 64..2048; tests go to 8192).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BLOCK_ROWS = 8


def _fft_kernel(n, xr_ref, xi_ref, or_ref, oi_ref):
    stages = int(math.log2(n))
    B = xr_ref.shape[0]
    xr = xr_ref[...].reshape(B, 1, n)
    xi = xi_ref[...].reshape(B, 1, n)
    m = n
    for _ in range(stages):
        m2 = m // 2
        ar, br = xr[:, :, :m2], xr[:, :, m2:]
        ai, bi = xi[:, :, :m2], xi[:, :, m2:]
        # Mosaic's iota is integer-only; the cast is exact below 2**24
        k = jax.lax.broadcasted_iota(jnp.int32, (1, 1, m2), 2).astype(
            jnp.float32)
        ang = (-2.0 * math.pi / m) * k
        wr, wi = jnp.cos(ang), jnp.sin(ang)
        sr, si = ar - br, ai - bi
        top_r, top_i = ar + br, ai + bi
        bot_r = sr * wr - si * wi
        bot_i = sr * wi + si * wr
        xr = jnp.concatenate([top_r, bot_r], axis=1)
        xi = jnp.concatenate([top_i, bot_i], axis=1)
        m = m2
    or_ref[...] = xr.reshape(B, n)
    oi_ref[...] = xi.reshape(B, n)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fft_planes(xr, xi, *, block_rows: int = BLOCK_ROWS,
               interpret: Optional[bool] = None):
    """xr, xi: (rows, N) f32 → FFT along axis 1 (rows padded to tiles).
    ``block_rows`` is a pure launch parameter — rows are independent, so
    any tiling produces bit-identical planes (autotuned, ISSUE 10)."""
    rows, n = xr.shape
    spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fft_kernel, n),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, n), jnp.float32)] * 2,
        interpret=resolve_interpret(interpret),
    )(xr, xi)
