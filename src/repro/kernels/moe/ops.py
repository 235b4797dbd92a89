"""The serving step's sparse MLP: softmax router, top-k with the chosen
weights renormalised to sum to 1, and every row's k experts applied,
with no capacity and nothing dropped."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .moe import moe_experts

__all__ = ["route", "expert_order", "moe_mlp"]


def route(h, router, top_k: int, active):
    """h: (B, D); router: (D, E); active: (B,) bool.  Returns the (B, E)
    float32 combine weights (0 off a row's top-k and on inactive rows)
    and the (E,) int32 count of active rows routed to each expert."""
    logits = jnp.dot(h, router.astype(h.dtype), preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = jnp.where(active[:, None], vals, 0.0)
    rows = jnp.arange(h.shape[0])[:, None]
    comb = jnp.zeros(probs.shape, jnp.float32).at[rows, idx].set(vals)
    hits = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.int32) * active[:, None, None]
    return comb, jnp.sum(hits, axis=(0, 1))


def expert_order(counts):
    """The experts with a routed row, ascending, then the last of them
    repeated to fill ``E`` slots; and how many there are, as (1,)."""
    hit = counts > 0
    n = jnp.sum(hit).astype(jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n - 1, 0)]
    ids = jnp.where(jnp.arange(counts.shape[0]) < n, order, last)
    return ids, n[None]


def moe_mlp(h, params, top_k: int, active):
    """h: (B, D) → ((B, D) in h's dtype, (E,) routed-row counts)."""
    comb, counts = route(h, params["router"], top_k, active)
    ids, n = expert_order(counts)
    y = moe_experts(h, comb, ids, n, params["w_gate"], params["w_in"], params["w_out"])
    return y.astype(h.dtype), counts
