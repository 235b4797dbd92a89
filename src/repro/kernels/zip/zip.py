"""ZIP kernel: pointwise complex multiply (the paper's ZIP accelerator,
§4.1 — HLS pointwise vector unit on the ZCU102, cuFFT-style pointwise
stage on the Jetson).

Complex data is carried as separate real/imag planes (TPU VPU has no
complex dtype).  Tiling: (block_rows, 128) f32 tiles in VMEM — lane
dimension 128 to match the VPU registers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BLOCK_ROWS = 256
LANES = 128


def _zip_kernel(ar_ref, ai_ref, br_ref, bi_ref, or_ref, oi_ref):
    ar, ai = ar_ref[...], ai_ref[...]
    br, bi = br_ref[...], bi_ref[...]
    or_ref[...] = ar * br - ai * bi
    oi_ref[...] = ar * bi + ai * br


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def zip_mul_planes(ar, ai, br, bi, *, block_rows: int = BLOCK_ROWS,
                   interpret: Optional[bool] = None):
    """(rows, 128) f32 planes → complex product planes.  ``block_rows``
    is a pure launch parameter (elementwise op → bit-identical tiling,
    autotuned in ISSUE 10)."""
    rows = ar.shape[0]
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _zip_kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * 2,
        interpret=resolve_interpret(interpret),
    )(ar, ai, br, bi)
