"""The 2FZF chain of the radar apps in float64: each row of n samples
becomes ``ifft(fft(a) * fft(b))``.

``control`` is the same chain one precision step below the complex64
the configuration states: every value that enters or leaves a stage is
rounded to bfloat16 (real and imaginary parts apart).
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

__all__ = ["chain", "control", "rel_error"]


def chain(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``ifft(fft(a) * fft(b))`` along the last axis, in complex128."""
    a = a.astype(np.complex128)
    b = b.astype(np.complex128)
    return np.fft.ifft(np.fft.fft(a, axis=-1) * np.fft.fft(b, axis=-1), axis=-1)


def _bf16(x: np.ndarray) -> np.ndarray:
    def r(v):
        return v.astype(ml_dtypes.bfloat16).astype(np.float64)

    return r(x.real) + 1j * r(x.imag)


def control(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The chain with every stage's inputs and outputs rounded to bfloat16."""
    fa = _bf16(np.fft.fft(_bf16(a), axis=-1))
    fb = _bf16(np.fft.fft(_bf16(b), axis=-1))
    return _bf16(np.fft.ifft(_bf16(fa * fb), axis=-1))


def rel_error(got: np.ndarray, want: np.ndarray) -> float:
    """Largest error of any sample, over the largest reference magnitude."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
