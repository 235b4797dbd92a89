"""Batched serving engine on the RIMMS paged KV pool.

The production mapping of the paper (DESIGN.md §2): the KV cache is one
preallocated device pool; the RIMMS marking systems hand out page
extents; a sequence's pages are one ``fragment()``-style grab; block
tables are the resource pointers consumed by the paged-attention kernel
(ref path on CPU, Pallas kernel on TPU).

Continuous-batching-lite: up to ``max_batch`` slots decode in lock-step;
finished sequences free their pages back to the pool and new requests
are admitted into the freed slots.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.paged_kv import PagedKVPool, init_pool_arrays, write_token
from repro.kernels.moe import ops as moe_ops
from repro.kernels.paged_attention import ref as pa_ref
from repro.models import layers as L

__all__ = ["ServeEngine", "Request", "SUPPORTED_FAMILIES", "chunked_prefill"]

#: full-attention dense decoder families the paged engines support.
SUPPORTED_FAMILIES = ("dense", "vlm")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 4,
                 page_size: int = 16, num_pages: int = 512,
                 max_pages_per_seq: int = 32, allocator: str = "bitset",
                 eos_id: Optional[int] = None):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise ValueError(
                f"serve engine supports full-attention dense decoder "
                f"families {SUPPORTED_FAMILIES}, got {cfg.family!r}"
            )
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_pages = max_pages_per_seq
        self.max_batch = max_batch
        self.eos_id = eos_id
        # scratch=True reserves the sacrificial scratch page inside the
        # pool's own accounting: inactive slots' block tables point at
        # it, so their masked writes never corrupt a live sequence's
        # pages, and no tenant can free it or get billed for it.
        self.pool = PagedKVPool(num_pages=num_pages, page_size=page_size,
                                allocator=allocator, scratch=True)
        self.scratch_page = self.pool.scratch_page
        n_layers = cfg.n_layers
        kv, hd = cfg.n_kv_heads, cfg.head_dim_
        k0, v0 = init_pool_arrays(num_pages, page_size, kv, hd, L.cdtype(cfg))
        self.k_pools = jnp.broadcast_to(k0, (n_layers,) + k0.shape).copy()
        self.v_pools = jnp.broadcast_to(v0, (n_layers,) + v0.shape).copy()
        # slot state (host side — RIMMS metadata lives on host, §3.2.2)
        self.block_tables = np.full(
            (max_batch, max_pages_per_seq), self.scratch_page, np.int32
        )
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros((max_batch,), np.int32)
        self.slot_tok = np.zeros((max_batch,), np.int32)
        self._next_rid = 0
        self.waiting: List[Request] = []
        self._step_fn = jax.jit(functools.partial(_paged_decode_step, cfg))

    # -- request admission --------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 16) -> Request:
        if not prompt:
            raise ValueError("prompt must be non-empty")
        need = -(-(len(prompt) + max_new_tokens) // self.page_size)
        if need > self.max_pages:
            raise ValueError(
                f"request needs {need} pages "
                f"({len(prompt)} prompt + {max_new_tokens} new tokens) "
                f"but max_pages_per_seq is {self.max_pages}"
            )
        req = Request(self._next_rid, list(prompt), max_new_tokens)
        self._next_rid += 1
        self.waiting.append(req)
        return req

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None or not self.waiting:
                continue
            req = self.waiting.pop(0)
            n_tokens = len(req.prompt) + req.max_new_tokens
            table = self.pool.alloc_sequence(req.rid, n_tokens)
            self.block_tables[slot, :] = self.scratch_page
            self.block_tables[slot, : len(table)] = table
            self.slot_req[slot] = req
            chunked_prefill(self._prefill_call, [self.block_tables], slot,
                            [self.scratch_page], req.prompt[:-1], self.max_batch)
            self.slot_pos[slot] = len(req.prompt) - 1
            self.slot_tok[slot] = req.prompt[-1]

    def _prefill_call(self, tables, tokens, pos, lengths) -> None:
        _, self.k_pools, self.v_pools = self._step_fn(
            self.params, self.k_pools, self.v_pools, tables[0], tokens, pos, lengths)

    # -- decode ----------------------------------------------------------------
    def _step(self, tokens: np.ndarray, pos: np.ndarray, active_mask) -> np.ndarray:
        lengths = jnp.asarray(np.where(active_mask, pos + 1, 0), jnp.int32)
        nxt, self.k_pools, self.v_pools = self._step_fn(
            self.params, self.k_pools, self.v_pools,
            jnp.asarray(self.block_tables), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos, jnp.int32), lengths,
        )
        return np.asarray(nxt)

    def step(self) -> int:
        """One lock-step decode over all active slots; returns #active."""
        self._admit()
        active = np.array([r is not None for r in self.slot_req])
        if not active.any():
            return 0
        nxt = self._step(self.slot_tok, self.slot_pos, active)
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            tok = int(nxt[slot])
            req.generated.append(tok)
            self.slot_pos[slot] += 1
            self.slot_tok[slot] = tok
            if len(req.generated) >= req.max_new_tokens or tok == self.eos_id:
                req.done = True
                self.pool.free_sequence(req.rid)
                self.slot_req[slot] = None
                # re-point the idle slot at the scratch page so its
                # masked writes can't land in pages the pool recycles.
                self.block_tables[slot, :] = self.scratch_page
        return int(active.sum())

    def run(self, max_steps: int = 1000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self.waiting:
                break


def chunked_prefill(step, tables: Sequence, slot: int, scratch: Sequence[int],
                    prompt: Sequence[int], rows: int):
    """Teacher-forced prefill of ``prompt`` (token ``i`` at position
    ``i``) into the sequence of ``slot``, through the batch-wide decode
    step: up to ``rows`` prompt tokens a call, one to a row.

    ``tables`` holds one (batch, pages) block table per pool and
    ``scratch`` each pool's scratch page.  Every used row reads and
    writes through ``slot``'s table row in each pool, with ``lengths``
    its position plus one; the rest point at the scratch page with
    ``lengths`` 0.  Each layer of the step writes every row's keys before
    its attention reads them, and a row attends up to its own position,
    so one call is causal prefill of its rows.  A sliding-window pool
    needs ``rows`` at most its ring's slots less the window, so that no
    row overwrites a key an earlier row of the same call still reads.

    ``step(tables, tokens, pos, lengths)`` runs one call and returns its
    (layers, experts) routing counts, or None.  Returns the number of
    calls and their counts summed on the host (None for a dense model):
    an addition on the device would be a program of its own, compiled
    at the first prompt that takes two calls."""
    tables = [np.asarray(t) for t in tables]
    batch = tables[0].shape[0]
    row = np.arange(batch)
    starts = range(0, len(prompt), rows)
    routed = []
    for start in starts:
        chunk = prompt[start:start + rows]
        used = row < len(chunk)
        tokens = np.zeros((batch,), np.int32)
        tokens[:len(chunk)] = chunk
        pos = np.where(used, start + row, 0).astype(np.int32)
        call_tables = [np.where(used[:, None], t[slot], s).astype(np.int32)
                       for t, s in zip(tables, scratch)]
        counts = step(call_tables, tokens, pos,
                      np.where(used, pos + 1, 0).astype(np.int32))
        if counts is not None:
            routed.append(counts)
    if not routed:
        return len(starts), None
    return len(starts), np.sum([np.asarray(c) for c in routed], axis=0, dtype=np.int32)


def _paged_decode_step(cfg, params, k_pools, v_pools, block_tables,
                       tokens, pos, lengths):
    """One batched paged decode step for dense-family configs."""
    x = L.embed_tokens(cfg, params["embed"], tokens[:, None],
                       pos[:, None] if cfg.pos_embed == "learned" else None)
    stack = params["stacks"][0]
    n_layers = jax.tree.leaves(stack)[0].shape[0]
    dims = L.attn_dims(cfg)
    new_k, new_v = [], []
    for li in range(n_layers):
        p = jax.tree.map(lambda a: a[li], stack)["b0"]
        h = L.norm_apply(cfg, p["norm1"], x)
        q, k, v = L._project_qkv(cfg, p["attn"], h, pos[:, None])
        kp = write_token(k_pools[li], block_tables, pos, k[:, 0])
        vp = write_token(v_pools[li], block_tables, pos, v[:, 0])
        new_k.append(kp)
        new_v.append(vp)
        attn = pa_ref.paged_attention(
            q[:, 0].reshape(q.shape[0], dims.n_q, dims.head_dim),
            kp, vp, block_tables, lengths,
        ).reshape(x.shape[0], 1, dims.n_q * dims.head_dim)
        x = x + attn @ p["attn"]["wo"].astype(x.dtype)
        h = L.norm_apply(cfg, p["norm2"], x)
        x = x + L.mlp_apply(cfg, p["mlp"], h)
    x = L.norm_apply(cfg, params["final_norm"], x)
    logits = L.lm_logits(cfg, params["embed"], x)
    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    return nxt, jnp.stack(new_k), jnp.stack(new_v)


def _paged_hybrid_step(cfg, params, k_full, v_full, k_win, v_win, full_tables,
                       win_tables, tokens, pos, lengths):
    """One batched paged decode step for sliding-window / full attention
    layers with sparse-expert MLPs (family ``swa_moe``).

    ``params["layers"]`` holds one dict per layer, so no layer's weights
    are sliced out of a stack.  Full layers keep every key in
    ``k_full``/``v_full`` (one plane per full layer) under
    ``full_tables``; sliding layers keep a ring of pages in
    ``k_win``/``v_win`` under ``win_tables``.  Rows with ``lengths`` 0
    are inactive: their writes land in the scratch pages and the router
    sends them nowhere.  Returns the next tokens, the (layers, experts)
    count of active rows routed to each expert, and the new planes."""
    x = L.embed_tokens(cfg, params["embed"], tokens[:, None])
    dims = L.attn_dims(cfg)
    active = lengths > 0
    ring = win_tables.shape[1] * k_win.shape[2]
    kv_out = {"full_attention": ([], []), "sliding_attention": ([], [])}
    counts = []
    for li, p in enumerate(params["layers"]):
        kind = cfg.layer_types[li]
        h = L.norm_apply(cfg, p["norm1"], x)
        q, k, v = L._project_qkv(cfg, p["attn"], h, pos[:, None], rope=False)
        freqs, scale = L.layer_rope(cfg, kind)
        q = L.apply_rope(q, pos[:, None], cfg.rope_theta, freqs=freqs, scale=scale)
        k = L.apply_rope(k, pos[:, None], cfg.rope_theta, freqs=freqs, scale=scale)
        q = q[:, 0].reshape(q.shape[0], dims.n_q, dims.head_dim)
        new_k, new_v = kv_out[kind]
        i = len(new_k)
        if kind == "full_attention":
            with jax.named_scope("attn_full"):
                kp = write_token(k_full[i], full_tables, pos, k[:, 0])
                vp = write_token(v_full[i], full_tables, pos, v[:, 0])
                attn = pa_ref.paged_attention(q, kp, vp, full_tables, lengths)
        else:
            with jax.named_scope("attn_window"):
                kp = write_token(k_win[i], win_tables, pos % ring, k[:, 0])
                vp = write_token(v_win[i], win_tables, pos % ring, v[:, 0])
                attn = pa_ref.paged_window_attention(q, kp, vp, win_tables, pos,
                                                     lengths, cfg.window)
        new_k.append(kp)
        new_v.append(vp)
        attn = attn.reshape(x.shape[0], 1, dims.n_q * dims.head_dim)
        x = x + attn @ p["attn"]["wo"].astype(x.dtype)
        h = L.norm_apply(cfg, p["norm2"], x)
        with jax.named_scope("moe"):
            y, c = moe_ops.moe_mlp(h[:, 0], p["moe"], cfg.top_k, active)
        x = x + y[:, None]
        counts.append(c)
    x = L.norm_apply(cfg, params["final_norm"], x)
    logits = L.lm_logits(cfg, params["embed"], x)
    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    full, win = kv_out["full_attention"], kv_out["sliding_attention"]
    return (nxt, jnp.stack(counts), jnp.stack(full[0]), jnp.stack(full[1]),
            jnp.stack(win[0]), jnp.stack(win[1]))
