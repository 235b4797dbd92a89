"""Capacity-pressure subsystem (ISSUE 2): transparent eviction,
spill-to-host write-back, pin/protect semantics, spill counters, and the
stream executor's persistent worker pool + capacity-aware prefetch."""

import numpy as np
import pytest

from repro.core.allocator import AllocError
from repro.core.hete import HeteContext, MemorySpace, hete_sync
from repro.core.locations import HOST, Location
from session_tasks import submit_tasks

ACC = Location("device", "acc0")


def make_ctx(capacity=4096, tracking="flag", allocator="nextfit"):
    ctx = HeteContext(tracking=tracking)
    ctx.register_space(MemorySpace(
        ACC, capacity=capacity, allocator=allocator,
        ingest=lambda a: a.copy(), egress=lambda a: np.asarray(a),
    ))
    return ctx


# ---------------------------------------------------------------------------
# eviction engine
# ---------------------------------------------------------------------------


def test_pinned_exhaustion_raises_allocerror():
    """Eviction retries until only pinned bytes remain, then surfaces a
    genuine AllocError naming the pinned working set."""
    ctx = make_ctx(capacity=4096)
    a = ctx.malloc((2048,), np.uint8)
    b = ctx.malloc((2048,), np.uint8)
    ctx.ensure(a, ACC)
    ctx.ensure(b, ACC)
    c = ctx.malloc((2048,), np.uint8)
    with a.pinned(ACC), b.pinned(ACC):
        with pytest.raises(AllocError, match="pinned"):
            ctx.ensure(c, ACC)
    ctx.ensure(c, ACC)  # pins released → one victim spills, c fits
    assert ctx.ledger.total_evictions == 1


def test_unpin_without_pin_raises():
    ctx = make_ctx()
    hd = ctx.malloc((16,), np.uint8)
    with pytest.raises(ValueError):
        ctx.unpin(hd, ACC)


def test_clean_eviction_copies_nothing():
    """A clean replica (flag at host) is dropped without any write-back
    copy; only the re-ensure pays a host→device transfer."""
    ctx = make_ctx(capacity=4096)
    a = ctx.malloc((4096,), np.uint8)
    a.data[:] = 7
    ctx.ensure(a, ACC)  # 1 copy host→acc; flag stays HOST (read)
    assert ctx.ledger.total_copies == 1
    b = ctx.malloc((4096,), np.uint8)
    ctx.ensure(b, ACC)  # evicts a (clean): no write-back copy
    snap = ctx.ledger.snapshot()
    assert snap["total_evictions"] == 1
    assert snap["writeback_bytes"] == 0
    assert snap["total_copies"] == 2  # just the two host→acc stagings
    assert snap["spill_stall_s"] == 0.0


def test_dirty_eviction_writes_back_and_roundtrips():
    """Evicted-then-re-ensured buffer round-trips bit-identically, and
    the ledger shows exactly the expected copies: host→acc staging,
    acc→host write-back, host→acc re-fetch."""
    ctx = make_ctx(capacity=4096)
    rng = np.random.default_rng(0)
    a = ctx.malloc((4096,), np.uint8)
    a.data[:] = rng.integers(0, 255, 4096, dtype=np.uint8)
    v = ctx.ensure(a, ACC)
    payload = (np.asarray(v) ^ 0xFF).astype(np.uint8)
    ctx.mark_written(a, ACC, payload)  # device owns the only valid copy
    assert ctx.ledger.total_copies == 1

    b = ctx.malloc((4096,), np.uint8)
    ctx.ensure(b, ACC)  # forces eviction of dirty a → write-back
    snap = ctx.ledger.snapshot()
    assert snap["total_evictions"] == 1
    assert snap["writeback_bytes"] == 4096
    assert snap["by_pair"]["device:acc0->host:cpu"] == 1
    assert snap["spill_stall_s"] > 0.0
    assert a.last_location == HOST and ACC not in a.copies

    ctx.free(b)
    back = ctx.ensure(a, ACC)  # re-ensure: host→acc re-fetch
    np.testing.assert_array_equal(np.asarray(back), payload)
    np.testing.assert_array_equal(a.data, payload)
    assert ctx.ledger.snapshot()["by_pair"]["host:cpu->device:acc0"] == 3


def test_dirty_fragment_writeback_keeps_parent_coherent():
    """Evicting a parent whose *fragments* were written on the device
    must gather through the zero-copy host views: parent bytes coherent,
    fragment aliasing preserved."""
    ctx = make_ctx(capacity=4096)
    parent = ctx.malloc((1024,), np.float32)  # 4096 B
    parent.data[:] = 1.0
    frags = parent.fragment(256)
    v0 = ctx.ensure(frags[0], ACC)
    ctx.mark_written(frags[0], ACC, np.asarray(v0) * 5.0)
    v2 = ctx.ensure(frags[2], ACC)
    ctx.mark_written(frags[2], ACC, np.asarray(v2) * 9.0)

    other = ctx.malloc((1024,), np.float32)
    ctx.ensure(other, ACC)  # evicts parent: per-fragment write-back
    snap = ctx.ledger.snapshot()
    assert snap["total_evictions"] == 1
    assert snap["writeback_bytes"] == 2 * 256 * 4  # only dirty fragments

    # parent host bytes coherent, views still aliased
    np.testing.assert_allclose(parent.data[:256], 5.0)
    np.testing.assert_allclose(parent.data[256:512], 1.0)
    np.testing.assert_allclose(parent.data[512:768], 9.0)
    for f in frags:
        assert f.last_location == HOST and ACC not in f.copies
    np.testing.assert_allclose(hete_sync(frags[2], context=ctx), 9.0)
    # fragment views still write through to the parent
    frags[1].data[:] = 3.0
    np.testing.assert_allclose(parent.data[256:512], 3.0)


def test_lru_victim_order_with_access_clock():
    """Least-recently-touched resident is evicted first; a flag-hit read
    counts as a touch."""
    ctx = make_ctx(capacity=8192)
    a = ctx.malloc((4096,), np.uint8)
    b = ctx.malloc((4096,), np.uint8)
    ctx.ensure(a, ACC)
    ctx.ensure(b, ACC)
    # touch a *after* b so b becomes the LRU victim
    ctx.mark_written(a, ACC, np.ones((4096,), np.uint8))
    ctx.ensure(a, ACC)  # flag hit → access-clock touch
    c = ctx.malloc((4096,), np.uint8)
    ctx.ensure(c, ACC)
    assert ACC not in b.copies      # b evicted
    assert ACC in a.copies          # a survived (recently touched)
    assert ctx.ledger.snapshot()["writeback_bytes"] == 0  # b was clean


def test_explicit_evict_api():
    ctx = make_ctx(capacity=8192)
    a = ctx.malloc((4096,), np.uint8)
    ctx.ensure(a, ACC)
    assert ctx.evict(a, ACC) is True
    assert ctx.evict(a, ACC) is False  # not resident any more
    arena = ctx.spaces[ACC].arena
    assert arena.used_bytes == 0
    with a.pinned(ACC):
        ctx.ensure(a, ACC)
        assert ctx.evict(a, ACC) is False  # pinned


def test_eviction_under_cached_tracking_drops_replica():
    ctx = make_ctx(capacity=4096, tracking="cached")
    a = ctx.malloc((4096,), np.uint8)
    a.data[:] = 3
    ctx.ensure(a, ACC)
    b = ctx.malloc((4096,), np.uint8)
    ctx.ensure(b, ACC)  # evicts a's replica
    assert ACC not in a.valid_at
    v = ctx.ensure(a, ACC)  # must re-copy, not serve the dropped replica
    np.testing.assert_array_equal(np.asarray(v), a.data)


def test_clean_eviction_does_not_revalidate_stale_host_copy():
    """Regression: evicting a clean replica while a *third* location owns
    the flag must not add HOST to valid_at — the host bytes are stale."""
    ACC2 = Location("device", "acc1")
    ctx = make_ctx(capacity=4096, tracking="cached")
    ctx.register_space(MemorySpace(
        ACC2, capacity=1 << 20, allocator="nextfit",
        ingest=lambda a: a.copy(), egress=lambda a: np.asarray(a),
    ))
    a = ctx.malloc((4096,), np.uint8)
    ctx.ensure(a, ACC)  # clean replica on ACC
    ctx.mark_written(a, ACC2, np.full((4096,), 9, np.uint8))  # ACC2 owns
    ctx.ensure(a, ACC)  # re-replicate on ACC (cached keeps both)
    b = ctx.malloc((4096,), np.uint8)
    ctx.ensure(b, ACC)  # evicts a's CLEAN ACC replica (flag on ACC2)
    assert HOST not in a.valid_at  # host still stale, not revalidated
    np.testing.assert_array_equal(ctx.sync(a), 9)  # pulls from ACC2


def test_protected_bytes_deferred_under_prefetch_guard():
    """Inside prefetch_guard, protected (queued-reader) bytes are not
    evictable: the reservation defers instead of spilling them."""
    from repro.core.hete import PrefetchDeferred

    ctx = make_ctx(capacity=4096)
    a = ctx.malloc((4096,), np.uint8)
    ctx.ensure(a, ACC)
    ctx.protect(a, ACC)
    b = ctx.malloc((4096,), np.uint8)
    with ctx.prefetch_guard():
        with pytest.raises(PrefetchDeferred):
            ctx.ensure(b, ACC)
    assert ctx.ledger.snapshot()["prefetch_deferrals"] == 1
    ctx.unprotect(a, ACC)
    ctx.ensure(b, ACC)  # demand staging may now evict a
    assert ACC not in a.copies


def test_allocator_tags_name_residents():
    ctx = make_ctx(capacity=8192)
    a = ctx.malloc((4096,), np.uint8)
    ctx.ensure(a, ACC)
    arena = ctx.spaces[ACC].arena
    assert list(arena.tags().values()) == [id(a)]


# ---------------------------------------------------------------------------
# runtime + executor integration
# ---------------------------------------------------------------------------


def _pressure_session(arena_bytes):
    from repro.apps.radar import make_session

    return make_session(policy="rimms", scheduler="round_robin", n_cpu=0,
                        accelerators=("gpu0",), arena_bytes=arena_bytes)


def _radar_tasks(ctx, ways=4, n=512, seed=0):
    from repro.apps.radar import _parallel_fzf

    return _parallel_fzf(ctx, ways, n, use_fragment=True, seed=seed)


@pytest.mark.parametrize("one_at_a_time", [True, False],
                         ids=["one_at_a_time", "concurrent"])
def test_pipeline_bit_identical_under_pressure(one_at_a_time):
    """A radar pipeline whose working set exceeds the arena completes
    with outputs bit-identical to an unconstrained run — driven one task
    at a time, and concurrently (prefetch + protection)."""
    ways, n = 4, 512
    parent_bytes = ways * n * 8  # complex64
    outs, reps = {}, {}
    for arena in (64 << 20, 3 * parent_bytes):
        with _pressure_session(arena) as s:
            pts, tasks = _radar_tasks(s.context, ways, n)
            submit_tasks(s, tasks, one_at_a_time=one_at_a_time)
            outs[arena] = hete_sync(pts["out"][0], context=s.context).copy()
            reps[arena] = s.report()
            snap = s.ledger.snapshot()
            # all protection claims released once the stream drains
            assert not s.context._protected
        s.runtime.close()
    assert snap["total_evictions"] > 0  # the tight arena, last
    assert snap["writeback_bytes"] > 0  # dirty bytes spilled to the host
    np.testing.assert_array_equal(*outs.values())
    # spill stalls surfaced in the replayed timeline
    assert reps[64 << 20]["spill_stall_model_s"] == 0.0
    assert reps[3 * parent_bytes]["spill_stall_model_s"] > 0.0


def test_worker_pool_persists_across_sessions():
    import threading

    from repro.core.api import Session
    from repro.core.runtime import Runtime, make_emulated_soc

    pes, ctx = make_emulated_soc(n_cpu=0, accelerators=("gpu0",))
    rt = Runtime(pes, ctx, policy="rimms", scheduler="round_robin")
    _, tasks1 = _radar_tasks(ctx, 2, 256, seed=1)
    with Session(rt) as s:
        submit_tasks(s, tasks1)
    pool = rt._worker_pool
    assert pool is not None and pool.runs_served == 1
    before = threading.active_count()
    _, tasks2 = _radar_tasks(ctx, 2, 256, seed=2)
    with Session(rt) as s:
        submit_tasks(s, tasks2)
    assert rt._worker_pool is pool and pool.runs_served == 2
    assert threading.active_count() == before  # no new threads spun up
    rt.close()
    assert rt._worker_pool is None
