"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel subpackage ships:
  <name>.py — the pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — the jit'd public wrapper (padding, reshapes, vmap)
  ref.py    — the pure-jnp oracle used by the allclose test sweeps

Interpret mode is decided when a kernel is traced, never when this
package is imported (importing must not start a JAX backend, e.g. in a
worker process that has to stay off the chip).  Every kernel takes
``interpret=None``, which :func:`resolve_interpret` turns into the
Pallas interpreter wherever JAX's default backend is not a TPU.
"""

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """A kernel's ``interpret`` argument as traced now: an explicit bool
    wins; ``None`` means interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
