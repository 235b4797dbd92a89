"""Oracle: paged decode attention via dense gather (pure jnp)."""

import math

import jax
import jax.numpy as jnp


def paged_attention(q, k_pages, v_pages, block_table, lengths):
    """Same signature as the kernel; gathers pages densely."""
    B, hq, d = q.shape
    P, page, n_kv, _ = k_pages.shape
    group = hq // n_kv
    n_pages = block_table.shape[1]
    k = k_pages[block_table].reshape(B, n_pages * page, n_kv, d)
    v = v_pages[block_table].reshape(B, n_pages * page, n_kv, d)
    qg = q.reshape(B, n_kv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k.astype(jnp.float32))
    s = s / math.sqrt(d)
    t = jnp.arange(n_pages * page)
    mask = t[None] < lengths[:, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, hq, d).astype(q.dtype)


def paged_window_attention(q, k_pages, v_pages, ring_table, pos, lengths, window):
    """Decode attention of a sliding-window layer over each sequence's
    ring of pages (gathered densely, as :func:`paged_attention`).

    Ring slot ``t`` holds the newest key at a position ``p <= pos`` with
    ``p = t (mod ring)``; a query at ``pos`` attends to the keys at
    ``pos - window + 1 .. pos``.  Rows whose ``lengths`` is 0 are
    inactive and attend to nothing that matters."""
    B, hq, d = q.shape
    P, page, n_kv, _ = k_pages.shape
    group = hq // n_kv
    ring = ring_table.shape[1] * page
    k = k_pages[ring_table].reshape(B, ring, n_kv, d)
    v = v_pages[ring_table].reshape(B, ring, n_kv, d)
    qg = q.reshape(B, n_kv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k.astype(jnp.float32))
    s = s / math.sqrt(d)
    t = jnp.arange(ring)
    back = (pos[:, None] - t[None]) % ring  # how far behind pos the slot's key is
    mask = (back < window) & (back <= pos[:, None]) & (lengths[:, None] > 0)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, hq, d).astype(q.dtype)
