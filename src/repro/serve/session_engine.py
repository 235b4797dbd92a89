"""Continuous-batching LLM serving on the RIMMS Session.

The legacy :class:`~repro.serve.engine.ServeEngine` manages its KV pool
by hand: two bare jax arrays, no quotas, no pressure handling, no
telemetry.  This engine runs the same continuous-batching decode loop
*through* the runtime instead (ROADMAP item 2, the "millions of users"
scenario):

* tenancy acts where a tenant's requests differ: the KV cache is a
  :class:`~repro.core.kv_manager.KVManager` whose page groups are
  Session buffers in the device arena, with per-tenant page quotas
  enforced by the tenant-aware paged pool and at admission; and each
  tenant's SLO is evaluated over the decode steps that carried its rows
  (``qos_report()``);
* an engine step decodes every active slot, whatever its tenant, in one
  ``llm_decode`` task: the rows are independent, so splitting the batch
  by tenant would only repeat the step's host path and its weight reads;
* prefill and decode are distinct registered ops (``llm_prefill``
  throughput-bound, ``llm_decode`` latency-sensitive) with their own QoS
  weights/windows, so placement, staging, spans, and divergence
  telemetry all come from the runtime for free;
* each submission stages only the page groups its block tables
  reference: cold groups become LRU eviction victims under arena
  pressure, spill to host through the existing coherence path
  (dirty write-back), and re-stage transparently on the next decode
  step that touches them — there is no serving-specific copy code.

Token streams are bit-identical to the legacy engine on the same
submission order: both decode every active slot in one step that
writes the same values into the same pages (KV entries are
deterministic, idempotent functions of ``(token, position, params)``,
and every per-row output depends only on that row's inputs plus its
own gathered pages).

A model with sliding-window and full attention layers and sparse-expert
MLPs (family ``swa_moe``) runs through the same loop with two KV
managers: full layers page the whole sequence, sliding layers hold a
fixed ring of pages per sequence.  Its decode and prefill tasks carry
both pools' page groups and block tables, and each also returns the
count of tokens routed to each expert of each layer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.api import OpRegistry, Session
from repro.core.kv_manager import KVManager
from repro.core.paged_kv import ring_pages
from repro.core.telemetry import latency_report
from repro.core.trace import NULL_REGION
from repro.models import layers as L

from .engine import (SUPPORTED_FAMILIES, Request, _paged_decode_step, _paged_hybrid_step,
                     chunked_prefill)

__all__ = ["SessionServeEngine", "TenantRequest", "SESSION_FAMILIES"]

#: families the Session engine serves: the paged engines' dense ones,
#: and sliding-window / full attention with sparse experts
SESSION_FAMILIES = SUPPORTED_FAMILIES + ("swa_moe",)


@dataclasses.dataclass
class TenantRequest(Request):
    tenant: str = "default"


@functools.lru_cache(maxsize=None)
def _jit_grouped_step(cfg: ArchConfig, n_groups: int):
    """One batched decode step over a compacted pool of ``n_groups``
    page groups: concat → legacy step → split, jitted as one unit.
    Cached per (config, group count) so every engine instance — and
    every run in a benchmark — shares compilations.  The function's name
    names the program in a device trace (``jit_serve_step``)."""

    def serve_step(params, k_groups, v_groups, block_tables, tokens, pos, lengths):
        k_pool = jnp.concatenate(k_groups, axis=1)
        v_pool = jnp.concatenate(v_groups, axis=1)
        nxt, k_pool, v_pool = _paged_decode_step(
            cfg, params, k_pool, v_pool, block_tables, tokens, pos, lengths
        )
        gp = k_groups[0].shape[1]
        cuts = [gp * i for i in range(1, n_groups)]
        return (nxt, tuple(jnp.split(k_pool, cuts, axis=1)),
                tuple(jnp.split(v_pool, cuts, axis=1)))

    return jax.jit(serve_step)


@functools.lru_cache(maxsize=None)
def _jit_hybrid_step(cfg: ArchConfig, n_full: int, n_win: int):
    """:func:`_jit_grouped_step` for family ``swa_moe``: the full and the
    window pool each concatenated from their groups, one step, split
    back; also returns the step's (layers, experts) routing counts."""

    def serve_step(params, kf, vf, kw, vw, full_tables, win_tables, tokens, pos,
                   lengths):
        nxt, counts, *pools = _paged_hybrid_step(
            cfg, params, jnp.concatenate(kf, axis=1), jnp.concatenate(vf, axis=1),
            jnp.concatenate(kw, axis=1), jnp.concatenate(vw, axis=1),
            full_tables, win_tables, tokens, pos, lengths)
        out = []
        for pool, n, groups in zip(pools, (n_full, n_full, n_win, n_win), (kf, vf, kw, vw)):
            cuts = [groups[0].shape[1] * i for i in range(1, n)]
            out.append(tuple(jnp.split(pool, cuts, axis=1)))
        return (nxt, counts, *out)

    return jax.jit(serve_step)


class SessionServeEngine:
    """Session-backed continuous-batching engine.

    Drop-in for :class:`~repro.serve.engine.ServeEngine` plus tenancy:
    ``submit(prompt, max_new_tokens, tenant=...)`` queues a request for
    a tenant; ``step()`` admits waiting requests (prefill tasks under
    the throughput-bound ``prefill`` client) and runs one lock-step
    decode over every active slot as one task of the latency-sensitive
    ``decode`` client (``decode_weight``, ``decode_window``).

    With no ``session`` the engine owns a fresh emulated SoC whose
    single device arena (``arena_bytes``) backs the KV groups —
    shrinking it below the total KV footprint makes cold sequences spill
    to host through the runtime's eviction path.  ``prefetch`` is off on
    the owned session: the closed decode loop serializes on its own
    results, and unprefetched staging keeps the replayed modeled gates
    byte-deterministic.
    """

    def __init__(self, cfg: ArchConfig, params, *, session: Optional[Session] = None,
                 max_batch: int = 4, page_size: int = 16, num_pages: int = 512,
                 max_pages_per_seq: int = 32, pages_per_group: int = 8,
                 allocator: str = "bitset", eos_id: Optional[int] = None,
                 arena_bytes: int = 64 << 20, platform: Optional[str] = None,
                 kv_owner: str = "kv-cache",
                 decode_weight: float = 4.0, decode_window: int = 4,
                 prefill_weight: float = 1.0, prefill_window: int = 8):
        if cfg.family not in SESSION_FAMILIES:
            raise ValueError(
                f"session serve engine supports the dense decoder families "
                f"{SUPPORTED_FAMILIES} and {SESSION_FAMILIES[len(SUPPORTED_FAMILIES):]}, "
                f"got {cfg.family!r}"
            )
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_pages = max_pages_per_seq
        self.max_batch = max_batch
        self.eos_id = eos_id

        self._registry = OpRegistry()
        self._register_kernels()
        if session is None:
            session = Session.emulated(
                platform, policy="rimms", scheduler="heft", n_cpu=0,
                accelerators=("gpu0",), registry=self._registry,
                prefetch=False, arena_bytes=arena_bytes,
            )
            self._owns_session = True
        else:
            # Rebind (not missing_only): the kernels close over *this*
            # engine's params — one serving engine per session at a time.
            self._registry.install(session.runtime,
                                   extend_supports=("cpu", "gpu"))
            self._owns_session = False
        self.session = session
        self.hybrid = cfg.family == "swa_moe"
        kinds = cfg.layer_types[:cfg.n_layers] if self.hybrid else ()
        self.kv = KVManager(
            session, n_layers=kinds.count("full_attention") if self.hybrid else cfg.n_layers,
            kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim_, num_pages=num_pages,
            page_size=page_size, pages_per_group=pages_per_group,
            dtype=L.cdtype(cfg), allocator=allocator, owner=kv_owner,
        )
        #: one KV manager per layer type (full first), and their tables
        self.kvs = [self.kv]
        if self.hybrid:
            ring = ring_pages(cfg.window, page_size)
            window_pages = max_batch * ring + 1  # every slot's ring and the scratch page
            self.kv_window = KVManager(
                session, n_layers=kinds.count("sliding_attention"),
                kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim_,
                num_pages=window_pages, page_size=page_size,
                pages_per_group=window_pages, dtype=L.cdtype(cfg),
                allocator=allocator, owner=f"{kv_owner}-window", ring=ring,
            )
            self.kvs.append(self.kv_window)
            self.window_tables = np.full(
                (max_batch, ring), self.kv_window.scratch_page, np.int32)
            self._ring_tokens = ring * page_size
            #: routing of the last ``step()``: ``"prefill"``, a (prompt
            #: length, (layers, experts) counts) pair per prompt taken
            #: in; ``"decode"``, the counts of its decode task
            self.last_routing = {"prefill": [], "decode": None}
        #: prompt tokens one prefill call takes in, a row each; with window
        #: rings, no more than a ring's slots less the window, so that no
        #: row of a call overwrites a key an earlier row still reads
        self.prefill_rows = (min(max_batch, self._ring_tokens - cfg.window)
                             if self.hybrid else max_batch)
        self._prefill_client = session.client(
            "prefill", weight=prefill_weight, window=prefill_window)
        self._decode_client = session.client(
            "decode", weight=decode_weight, window=decode_window)
        self._tenants: set[str] = set()
        #: each decode task's stream node and the tenants it carried rows of
        self._decode_charges: List[Tuple[int, Tuple[str, ...]]] = []

        self.block_tables = np.full(
            (max_batch, max_pages_per_seq), self.kv.scratch_page, np.int32)
        self.tables = [self.block_tables] + ([self.window_tables] if self.hybrid else [])
        self._pending_prefill = []  # (prompt length, routing-count future)
        self.slot_req: List[Optional[TenantRequest]] = [None] * max_batch
        self.slot_pos = np.zeros((max_batch,), np.int32)
        self.slot_tok = np.zeros((max_batch,), np.int32)
        self._next_rid = 0
        self.waiting: List[TenantRequest] = []

    # -- kernels -------------------------------------------------------------
    def _run_step(self, tables, groups, tokens, pos, lengths):
        """One step program over every pool: ``tables`` one block table
        per pool, ``groups`` its K groups then its V groups.  Returns
        the next tokens, the routing counts (None for a dense model) and
        the new groups in the order given."""
        if not self.hybrid:
            (k_groups, v_groups), = groups
            nxt, k_groups, v_groups = _jit_grouped_step(self.cfg, len(k_groups))(
                self.params, k_groups, v_groups, tables[0], tokens, pos, lengths)
            return nxt, None, [(k_groups, v_groups)]
        (kf, vf), (kw, vw) = groups
        nxt, counts, kf, vf, kw, vw = _jit_hybrid_step(self.cfg, len(kf), len(kw))(
            self.params, kf, vf, kw, vw, tables[0], tables[1], tokens, pos, lengths)
        return nxt, counts, [(kf, vf), (kw, vw)]

    @staticmethod
    def _split_groups(bufs, n_groups):
        """Kernel inputs (each pool's K groups, then its V groups) as a
        (K groups, V groups) pair per pool."""
        out, i = [], 0
        for n in n_groups:
            out.append((tuple(bufs[i:i + n]), tuple(bufs[i + n:i + 2 * n])))
            i += 2 * n
        return out

    def _register_kernels(self) -> None:
        def decode_kernel(ins, *, mask, n_groups):
            tokens, pos = ins[0], ins[1]
            tables = ins[2:2 + len(n_groups)]
            groups = self._split_groups(ins[2 + len(n_groups):], n_groups)
            lengths = jnp.where(
                jnp.asarray(mask, bool), jnp.asarray(pos) + 1, 0
            ).astype(jnp.int32)
            nxt, counts, groups = self._run_step(
                tables, groups, jnp.asarray(tokens, jnp.int32),
                jnp.asarray(pos, jnp.int32), lengths)
            routed = () if counts is None else (counts,)
            return (nxt, *routed, *(g for kv in groups for g in kv[0] + kv[1]))

        def prefill_kernel(ins, *, slot, prompt, scratch, n_groups):
            tables = ins[:len(n_groups)]
            groups = self._split_groups(ins[len(n_groups):], n_groups)

            def call(tables, tokens, pos, lengths):
                nonlocal groups
                _, counts, groups = self._run_step(tables, groups, tokens, pos, lengths)
                return counts

            calls, routed = chunked_prefill(call, tables, slot, scratch, prompt,
                                            self.prefill_rows)
            metrics = self.session.metrics
            metrics.counter("serve/prefill_calls").inc(calls)
            metrics.counter("serve/prefill_tokens").inc(len(prompt))
            return (*(g for kv in groups for g in kv[0] + kv[1]),
                    *(() if routed is None else (jnp.asarray(routed),)))

        from repro.core.api import op

        op("llm_decode", kinds=("cpu", "gpu"), registry=self._registry,
           replace=True)(decode_kernel)
        op("llm_prefill", kinds=("cpu", "gpu"), registry=self._registry,
           replace=True)(prefill_kernel)

    # -- tenants -------------------------------------------------------------
    def tenant(self, name: str, *, quota_pages: Optional[int] = None,
               slo_latency_s: Optional[float] = None,
               slo_target: Optional[float] = None):
        """Register (or update) a tenant: a Session client that holds its
        latency objective, plus an optional KV page quota."""
        cl = self.session.client(name, slo_latency_s=slo_latency_s,
                                 slo_target=slo_target)
        self._tenants.add(name)
        if quota_pages is not None:
            for kv in self.kvs:  # the quota holds in each pool
                kv.set_quota(name, quota_pages)
        return cl

    # -- request admission ---------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               tenant: str = "default") -> TenantRequest:
        if not prompt:
            raise ValueError("prompt must be non-empty")
        need = -(-(len(prompt) + max_new_tokens) // self.page_size)
        if need > self.max_pages:
            raise ValueError(
                f"request needs {need} pages "
                f"({len(prompt)} prompt + {max_new_tokens} new tokens) "
                f"but max_pages_per_seq is {self.max_pages}"
            )
        if tenant not in self._tenants:
            self.tenant(tenant)
        req = TenantRequest(self._next_rid, list(prompt), max_new_tokens,
                            tenant=tenant)
        self._next_rid += 1
        self.waiting.append(req)
        return req

    def _region(self, cat: str):
        """The tracer's region for one engine phase (``step`` or
        ``admit``), or the shared null context when tracing is off."""
        tracer = self.session.context.tracer
        return NULL_REGION if tracer is None else tracer.region(cat, cat, "serve")

    def _admit(self) -> None:
        from repro.core.allocator import AllocError
        from repro.core.qos import QuotaExceeded

        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None:
                continue
            req = None
            # FIFO with quota skip: a tenant over its KV quota defers
            # (stays queued) without blocking other tenants' admissions.
            for i, cand in enumerate(self.waiting):
                n_tokens = len(cand.prompt) + cand.max_new_tokens
                try:
                    tables = self._alloc(cand, n_tokens)
                except QuotaExceeded:
                    self.session.metrics.counter(
                        "serve_quota_deferrals").inc()
                    continue
                except AllocError:
                    # Shared pool exhausted: clean admission backpressure
                    # (head-of-line, order-preserving), not corruption.
                    self.session.metrics.counter(
                        "serve_pool_backpressure").inc()
                    return
                req = self.waiting.pop(i)
                break
            if req is None:
                return
            for kv, bt, table in zip(self.kvs, self.tables, tables):
                bt[slot, :] = kv.scratch_page
                bt[slot, : len(table)] = table
            self.slot_req[slot] = req
            if len(req.prompt) > 1:
                self._submit_prefill(slot, req)
            self.slot_pos[slot] = len(req.prompt) - 1
            self.slot_tok[slot] = req.prompt[-1]

    def _alloc(self, req: TenantRequest, n_tokens: int) -> List[np.ndarray]:
        """Pages of every pool for ``req``, or none of them: a pool that
        refuses (quota or exhaustion) hands back what the others gave."""
        from repro.core.allocator import AllocError
        from repro.core.qos import QuotaExceeded

        tables = []
        try:
            for kv in self.kvs:
                tables.append(kv.alloc(req.rid, n_tokens, tenant=req.tenant))
        except (QuotaExceeded, AllocError):
            for kv in self.kvs[:len(tables)]:
                kv.free(req.rid)
            raise
        return tables

    def _pool_inputs(self, client):
        """Block tables (as Session buffers of ``client``, to free after
        submission) and KV group buffers of every pool, the group count
        per pool, and each pool's scratch page in the kernel-side view,
        for the slots' current tables."""
        tbs, bufs, n_groups, scratch = [], [], [], []
        for kv, bt in zip(self.kvs, self.tables):
            groups = kv.referenced_groups(bt)
            tables = kv.compact_tables(bt, groups)
            tb = self.session.malloc(tables.shape, np.int32, client=client)
            tb.data[...] = tables
            tbs.append(tb)
            bufs += kv.buffers(groups)
            n_groups.append(len(groups))
            scratch.append(int(kv.compact_tables(np.int32(kv.scratch_page), groups)))
        return tbs, bufs, tuple(n_groups), tuple(scratch)

    def _routing_buffer(self, client):
        return self.session.malloc((self.cfg.n_layers, self.cfg.n_experts), np.int32,
                                   client=client)

    def _submit_prefill(self, slot: int, req: TenantRequest) -> None:
        tbs, bufs, n_groups, scratch = self._pool_inputs(self._prefill_client)
        routed = [self._routing_buffer(self._prefill_client)] if self.hybrid else []
        futs = self._prefill_client.submit(
            "llm_prefill", [*tbs, *bufs], out=list(bufs) + routed,
            name=f"prefill#{req.rid}",
            slot=slot, prompt=tuple(req.prompt[:-1]), scratch=scratch,
            n_groups=n_groups,
        )
        for tb in tbs:
            self.session.free(tb)  # deferred to the prefill's completion
        if self.hybrid:
            self._pending_prefill.append((len(req.prompt), futs[-1]))

    # -- decode --------------------------------------------------------------
    def _decode_substep(self, mask: np.ndarray, client) -> np.ndarray:
        """One ``llm_decode`` task under ``client`` over the slots in
        ``mask``; returns the next token of every slot.  A row outside
        ``mask`` gets no token worth reading, yet still writes keys,
        computed without attention, at its position in its own pages:
        ``_step`` passes every active slot."""
        sess = self.session
        tok = sess.malloc((self.max_batch,), np.int32, client=client)
        tok.data[...] = self.slot_tok
        pos = sess.malloc((self.max_batch,), np.int32, client=client)
        pos.data[...] = self.slot_pos
        tbs, bufs, n_groups, _ = self._pool_inputs(client)
        nxt = sess.malloc((self.max_batch,), np.int32, client=client)
        routed = [self._routing_buffer(client)] if self.hybrid else []
        futs = client.submit(
            "llm_decode", [tok, pos, *tbs, *bufs], out=[nxt, *routed, *bufs],
            mask=tuple(bool(m) for m in mask), n_groups=n_groups,
        )
        for b in (tok, pos, *tbs):
            sess.free(b)
        rows = np.flatnonzero(mask)
        sess.metrics.counter("serve/decode_calls").inc()
        sess.metrics.counter("serve/decode_rows").inc(len(rows))
        out = futs[0].result()
        self._decode_charges.append(
            (futs[0].node, tuple(dict.fromkeys(self.slot_req[s].tenant for s in rows))))
        sess.free(nxt)
        if routed:
            self.last_routing["decode"] = futs[1].result().copy()
            sess.free(routed[0])
        return out

    def step(self) -> int:
        """One lock-step decode over all active slots, as one task;
        returns #active."""
        with self._region("step"):
            return self._step()

    def _step(self) -> int:
        if self.hybrid:
            self.last_routing = {"prefill": [], "decode": None}
        with self._region("admit"):
            self._admit()
        active = np.array([r is not None for r in self.slot_req])
        if not active.any():
            self._finish_step()
            return 0
        n_active = int(active.sum())
        metrics = self.session.metrics
        if self.hybrid:
            self._count_pages(active)
        nxt = self._decode_substep(active, self._decode_client)
        for slot in np.flatnonzero(active):
            req = self.slot_req[slot]
            tok = int(nxt[slot])
            req.generated.append(tok)
            metrics.counter("serve_tokens_generated").inc()
            self.slot_pos[slot] += 1
            self.slot_tok[slot] = tok
            if (len(req.generated) >= req.max_new_tokens
                    or tok == self.eos_id):
                req.done = True
                for kv, bt in zip(self.kvs, self.tables):
                    kv.free(req.rid)
                    bt[slot, :] = kv.scratch_page
                self.slot_req[slot] = None
                metrics.counter("serve_requests_completed").inc()
        self._finish_step()
        return n_active

    def _count_pages(self, active: np.ndarray) -> None:
        """Counters of the pages live sequences hold in each pool (added
        once per decoding step, so over a window they integrate to
        page-steps) and of ring wraps at the positions written now."""
        metrics = self.session.metrics
        for name, kv in (("full", self.kv), ("window", self.kv_window)):
            metrics.counter(f"kv/{name}/pages_held").inc(kv.used_pages - 1)  # less scratch
        ring = self._ring_tokens
        wraps = sum(1 for s in np.flatnonzero(active)
                    if self.slot_pos[s] > 0 and self.slot_pos[s] % ring == 0)
        if wraps:
            metrics.counter("kv/window/ring_wraps").inc(wraps)

    def _finish_step(self) -> None:
        """Publish the pool gauges; for a sparse-expert model, collect the
        step's prefill routing and count what the router did."""
        self.kv.publish_metrics()
        if not self.hybrid:
            return
        metrics = self.session.metrics
        for n_prompt, fut in self._pending_prefill:
            self.last_routing["prefill"].append((n_prompt, fut.result().copy()))
            self.session.free(fut)
            # prompt positions 1 .. n_prompt - 2 were written by the prefill
            wraps = max(0, n_prompt - 2) // self._ring_tokens
            if wraps:
                metrics.counter("kv/window/ring_wraps").inc(wraps)
        self._pending_prefill = []
        groups = [c for _, c in self.last_routing["prefill"]]
        if self.last_routing["decode"] is not None:
            groups.append(self.last_routing["decode"])
        for counts in groups:
            for layer, row in enumerate(counts):
                metrics.counter(f"moe/{layer}/tokens_routed").inc(int(row.sum()))
                metrics.counter(f"moe/{layer}/experts_hit").inc(int((row > 0).sum()))

    def run(self, max_steps: int = 10000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self.waiting:
                break

    # -- reporting / lifecycle ----------------------------------------------
    def qos_report(self):
        """The session's deterministic QoS replay — latency percentiles,
        SLO burn rates, fairness, metrics — with each tenant charged the
        replayed latency of every decode task that carried one of its
        rows."""
        self.session.barrier()
        rep = self.session.qos_report()
        finish, release = rep["finish_model"], rep["release_model"]
        lat: Dict[str, List[float]] = {}
        for node, tenants in self._decode_charges:
            for name in tenants:
                lat.setdefault(name, []).append(finish[node] - release[node])
        clients = rep["qos"]["clients"]
        percentiles, slo = latency_report(
            lat, {name: clients[name] for name in self._tenants})
        rep["latency_percentiles"].update(percentiles)
        rep["slo"].update(slo)
        return rep

    def close(self) -> None:
        if self._owns_session and not self.session.closed:
            self.session.close()

    def __enter__(self) -> "SessionServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
