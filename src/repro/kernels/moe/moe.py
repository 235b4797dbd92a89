"""Routed SwiGLU experts for a decode batch, reading only the experts
the router chose (the serving step's sparse MLP).

Grid: one step per expert slot.  ``ids`` (scalar prefetch) lists the
chosen experts first, each once, then repeats the last of them; the
weights' BlockSpec index maps read ``w[ids[e]]`` straight from the
stacked (experts, ...) arrays in HBM, so an expert's weights are
fetched once per call and never copied, and the repeated tail fetches
nothing new.  Every step computes its expert for the whole batch and
scales each row by that row's combine weight for it (0 where the row
did not choose it); the output block stays resident and accumulates in
float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: scoped VMEM for two buffers of one expert's three matrices (24.8 MB
#: at hidden 2,304 and width 896, bf16); a v5e core has 128 MiB
VMEM_LIMIT = 64 << 20


def _moe_kernel(ids_ref, n_ref, x_ref, comb_ref, wg_ref, wu_ref, wd_ref, o_ref):
    e = pl.program_id(0)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(e < n_ref[0])
    def _expert():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
        cols = jax.lax.broadcasted_iota(jnp.int32, comb_ref.shape, 1)
        w = jnp.sum(jnp.where(cols == ids_ref[e], comb_ref[...], 0.0),
                    axis=1, keepdims=True)
        o_ref[...] += w * y


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_experts(x, comb, ids, n_active, w_gate, w_up, w_down, *,
                interpret: Optional[bool] = None):
    """x: (B, D); comb: (B, E) float32 combine weights; ids: (E,) int32
    chosen experts first; n_active: (1,) int32 how many; w_gate/w_up:
    (E, D, F); w_down: (E, F, D).  Returns (B, D) float32."""
    B, D = x.shape
    E, _, F = w_gate.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E,),
        in_specs=[
            pl.BlockSpec((B, D), lambda e, ids, n: (0, 0)),
            pl.BlockSpec((B, E), lambda e, ids, n: (0, 0)),
            pl.BlockSpec((1, D, F), lambda e, ids, n: (ids[e], 0, 0)),
            pl.BlockSpec((1, D, F), lambda e, ids, n: (ids[e], 0, 0)),
            pl.BlockSpec((1, F, D), lambda e, ids, n: (ids[e], 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, D), lambda e, ids, n: (0, 0)),
    )
    return pl.pallas_call(
        _moe_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=resolve_interpret(interpret),
    )(ids, n_active, x, comb, w_gate, w_up, w_down)
