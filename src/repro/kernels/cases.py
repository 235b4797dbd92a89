"""Every Pallas kernel at the widths of the workload it serves.

One table for the two places that need the kernels at real size: the
v5e compile test (``tests/test_tpu_compile.py``) and the kernel phase
of ``chip_smoke.py``, which runs each case on the chip against its
``ref.py`` oracle.

* ``fft``/``zip``: the radar SAR phases (512 x 256, then 256 x 512
  complex samples);
* ``flash_attention``/``paged_attention``: yi-9b (32 query heads, 4 KV
  heads, head_dim 128, bf16; 16-token pages);
* ``rg_lru``: recurrentgemma-2b's recurrence width (2560);
* ``mlstm``: xlstm-350m's heads (4 heads of 256, chunk 64);
* ``moe``: Mellum2-12B-A2.5B's routed experts (64 of hidden 2,304 and
  width 896, bf16) for a decode batch of 16, 8 experts a row.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KernelCase", "cases", "max_rel_error"]

F32, BF16, I32 = np.dtype(np.float32), np.dtype(jnp.bfloat16), np.dtype(np.int32)


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel call: ``kernel(*inputs, interpret=...)`` on arrays of
    ``args`` (shape, dtype) pairs, compared with ``reference(*inputs)``
    by max |error| over max |reference| within ``tol``."""

    name: str
    kernel: Callable
    args: Tuple[Tuple[tuple, np.dtype], ...]
    make_inputs: Callable[[np.random.Generator], List[np.ndarray]]
    reference: Callable
    tol: float


def _normal(rng, shape, dtype=F32, scale=1.0):
    return (rng.standard_normal(shape) * scale).astype(dtype)


def _fft_case(rows: int, n: int) -> KernelCase:
    from .fft import ref
    from .fft.fft import fft_planes

    def reference(xr, xi):
        out = np.asarray(ref.fft(jnp.asarray(xr + 1j * xi, jnp.complex64)))
        return out.real, out.imag

    # f32 butterflies: log2(n) roundings of values that grow as sqrt(n)
    return KernelCase(f"fft_{rows}x{n}", fft_planes, (((rows, n), F32),) * 2,
                      lambda rng: [_normal(rng, (rows, n)) for _ in range(2)],
                      reference, 1e-4)


def _zip_case() -> KernelCase:
    from .zip import ref
    from .zip.zip import LANES, zip_mul_planes

    rows = 512 * 256 // LANES

    def reference(ar, ai, br, bi):
        out = np.asarray(ref.zip_mul(jnp.asarray(ar + 1j * ai, jnp.complex64),
                                     jnp.asarray(br + 1j * bi, jnp.complex64)))
        return out.real, out.imag

    # one f32 product and one sum per component
    return KernelCase("zip", zip_mul_planes, (((rows, LANES), F32),) * 4,
                      lambda rng: [_normal(rng, (rows, LANES)) for _ in range(4)],
                      reference, 1e-6)


def _flash_case() -> KernelCase:
    from .flash_attention import ref
    from .flash_attention.flash_attention import flash_attention_bh

    shape = (32, 1024, 128)  # yi-9b: 32 heads of 128, one 1024-token prompt

    # bf16 inputs and output: one bf16 rounding of values of order 1
    return KernelCase("flash_attention", flash_attention_bh,
                      ((shape, BF16),) * 3,
                      lambda rng: [_normal(rng, shape, BF16) for _ in range(3)],
                      ref.attention, 2e-2)


def _paged_case() -> KernelCase:
    from .paged_attention import ref
    from .paged_attention.paged_attention import paged_attention

    B, hq, hkv, d, P, page, npg = 8, 32, 4, 128, 256, 16, 32

    def make(rng):
        tables = np.stack([rng.choice(P, npg, replace=False)
                           for _ in range(B)]).astype(np.int32)
        lengths = rng.integers(1, npg * page + 1, size=(B,)).astype(np.int32)
        return [_normal(rng, (B, hq, d), BF16),
                _normal(rng, (P, page, hkv, d), BF16),
                _normal(rng, (P, page, hkv, d), BF16), tables, lengths]

    # bf16 inputs and output, as for flash attention
    return KernelCase("paged_attention", paged_attention,
                      (((B, hq, d), BF16), ((P, page, hkv, d), BF16),
                       ((P, page, hkv, d), BF16), ((B, npg), I32), ((B,), I32)),
                      make, ref.paged_attention, 2e-2)


def _rg_lru_case() -> KernelCase:
    from .rg_lru import ref
    from .rg_lru.rg_lru import rg_lru_scan

    B, S, D = 2, 256, 2560

    def make(rng):
        return [rng.uniform(0.3, 0.999, (B, S, D)).astype(F32),
                _normal(rng, (B, S, D)), _normal(rng, (B, D))]

    # a sequential f32 recurrence against a log-depth scan: S roundings
    return KernelCase("rg_lru", rg_lru_scan,
                      (((B, S, D), F32), ((B, S, D), F32), ((B, D), F32)),
                      make, ref.rg_lru_scan, 1e-5)


def _mlstm_case() -> KernelCase:
    from .mlstm import ref
    from .mlstm.mlstm import mlstm_chunkwise_bh

    BH, S, m = 8, 256, 256

    def make(rng):
        return [_normal(rng, (BH, S, m), scale=1 / math.sqrt(m)),
                _normal(rng, (BH, S, m), scale=0.3), _normal(rng, (BH, S, m)),
                rng.uniform(0.1, 0.9, (BH, S)).astype(F32),
                np.log(rng.uniform(0.5, 0.95, (BH, S))).astype(F32)]

    # against a float64 per-token recurrence; on a TPU the q.k, A.v and
    # q.C products take the MXU's default one-pass bf16 rounding of their
    # f32 operands (2**-9 each, f32 accumulation), as flash attention's do
    return KernelCase("mlstm", mlstm_chunkwise_bh,
                      (((BH, S, m), F32),) * 3 + (((BH, S), F32),) * 2,
                      make, ref.mlstm_sequential, 1e-2)


def _moe_case() -> KernelCase:
    from .moe import ref
    from .moe.moe import moe_experts

    B, D, F, E, K = 16, 2304, 896, 64, 8

    def make(rng):
        comb = np.zeros((B, E), np.float32)
        for b in range(B):
            comb[b, rng.choice(E, K, replace=False)] = rng.dirichlet(np.ones(K))
        hit = comb.sum(0) > 0
        ids = np.concatenate([np.flatnonzero(hit), np.full(E - hit.sum(), np.flatnonzero(hit)[-1])])
        return [_normal(rng, (B, D), BF16), comb, ids.astype(np.int32),
                np.array([hit.sum()], np.int32),
                _normal(rng, (E, D, F), BF16, 1 / math.sqrt(D)),
                _normal(rng, (E, D, F), BF16, 1 / math.sqrt(D)),
                _normal(rng, (E, F, D), BF16, 1 / math.sqrt(F))]

    # bf16 weights and activations, f32 accumulation; the kernel rounds
    # the SwiGLU product to bf16 before the down projection
    return KernelCase("moe", moe_experts,
                      (((B, D), BF16), ((B, E), F32), ((E,), I32), ((1,), I32),
                       ((E, D, F), BF16), ((E, D, F), BF16), ((E, F, D), BF16)),
                      make, ref.moe_experts, 2e-2)


def cases() -> List[KernelCase]:
    """Every Pallas kernel in this package, once per deployed shape."""
    return [_fft_case(512, 256), _fft_case(256, 512), _zip_case(),
            _flash_case(), _paged_case(), _rg_lru_case(), _mlstm_case(), _moe_case()]


def max_rel_error(got, want) -> float:
    """max |got - want| over max |want|, across every output array."""
    got = got if isinstance(got, (tuple, list)) else (got,)
    want = want if isinstance(want, (tuple, list)) else (want,)
    err = max(float(np.max(np.abs(np.asarray(g, np.float64)
                                  - np.asarray(w, np.float64))))
              for g, w in zip(got, want, strict=True))
    scale = max(float(np.max(np.abs(np.asarray(w, np.float64)))) for w in want)
    return err / scale
