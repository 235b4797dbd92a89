"""JAX call shapes shared by src/ and tests/.

* :func:`make_mesh` builds meshes whose axes are all ``Auto``-typed,
  which is what this codebase's sharding rules assume.
* :func:`cost_analysis` returns ``compiled.cost_analysis()`` as a plain
  dict (``{}`` when the backend reports nothing).

``PartitionSpec`` entries are always emitted in canonical tuple form by
``AxisRules.entry`` / ``resolve_spec`` (see repro.distributed.sharding).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "cost_analysis"]


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict."""
    return dict(compiled.cost_analysis() or {})
