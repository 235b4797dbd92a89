"""Streaming session API (ISSUE 4): op registry, incremental DAG,
buffer futures, concurrent submitters, exception propagation, lifecycle,
and bit-identical equivalence with the stream driven one task at a
time."""

import threading

import numpy as np
import pytest

from repro.apps.radar import make_session, submit_2fzf
from repro.apps.synthetic import build_fork_join, submit_fork_join
from repro.core import api as rimms
from repro.core.graph import GraphBuilder, build_graph
from repro.core.hete import AllocError, HeteContext, hete_sync
from repro.core.runtime import Runtime, Task, make_emulated_soc
from session_tasks import submit_tasks


# ---------------------------------------------------------------------------
# op registry
# ---------------------------------------------------------------------------


def test_op_decorator_registers_per_kind_variants():
    reg = rimms.OpRegistry()

    @rimms.op("scale", kinds=("cpu", "gpu"), registry=reg)
    def scale(ins, *, k=2.0):
        return ins[0] * k

    assert reg.kinds("scale") == ["cpu", "gpu"]
    assert reg.get("scale", "cpu") is scale
    assert reg.ops() == ["scale"]
    # the function stays directly callable
    np.testing.assert_allclose(scale([np.ones(4)], k=3.0), 3.0)


def test_op_double_registration_rejected_unless_replace():
    reg = rimms.OpRegistry()

    @rimms.op("f", kinds=("cpu",), registry=reg)
    def f1(ins):
        return ins[0]

    with pytest.raises(ValueError, match="already registered"):
        @rimms.op("f", kinds=("cpu",), registry=reg)
        def f2(ins):
            return ins[0]

    @rimms.op("f", kinds=("cpu",), registry=reg, replace=True)
    def f3(ins):
        return ins[0]

    assert reg.get("f", "cpu") is f3


def test_registry_install_missing_only_keeps_manual_kernels():
    rt = Runtime(*make_emulated_soc(accelerators=("gpu0",)), policy="rimms")
    rimms.default_registry.install(rt)
    sentinel = lambda ins: ins[0]
    rt.register_kernel("fft", "cpu", sentinel)
    rimms.default_registry.install(rt, missing_only=True)
    assert rt._kernels[("fft", "cpu")] is sentinel


def test_session_runs_custom_op_on_general_purpose_pes():
    """A custom @op variant is usable through a session without touching
    make_emulated_soc's op lists: install extends general-purpose PE
    kinds' supports."""
    reg = rimms.OpRegistry()

    @rimms.op("triple", kinds=("cpu",), registry=reg)
    def triple(ins):
        return ins[0] * 3

    with rimms.Session.emulated(accelerators=(), n_cpu=1,
                                scheduler="round_robin",
                                registry=reg) as s:
        x = s.malloc((8,), np.float32)
        x.data[:] = 2.0
        y = s.submit("triple", [x])
        np.testing.assert_allclose(y.result(), 6.0)


# ---------------------------------------------------------------------------
# incremental DAG builder
# ---------------------------------------------------------------------------


def _mk(ctx, n=16):
    return ctx.malloc((n,), np.complex64)


def test_graph_builder_matches_batch_build_graph():
    """Incremental add() produces exactly the DAG batch build_graph
    does (same edge set) on a fork-join with fragments."""
    ctx = HeteContext()
    parent = ctx.malloc((32,), np.complex64)
    parent.fragment(16)
    a, l, r, o = (_mk(ctx) for _ in range(4))
    tasks = [
        Task("fft", [a], [l]),
        Task("fft", [a], [r]),
        Task("zip", [l, r], [o]),
        Task("fft", [o], [parent[0]]),
        Task("fft", [o], [parent[1]]),
        Task("fft", [parent], [a]),  # reads both fragments, WAR on t0/t1
    ]
    batch = build_graph(tasks)
    builder = GraphBuilder()
    for t in tasks:
        builder.add(t)
    incremental = builder.graph()
    assert batch.edges() == incremental.edges()
    assert batch.critical_path_len == incremental.critical_path_len


def test_graph_builder_tracks_versions_and_last_writer():
    ctx = HeteContext()
    a, b = _mk(ctx), _mk(ctx)
    builder = GraphBuilder()
    assert builder.version_of(b) == 0
    assert builder.last_writer(b) is None
    builder.add(Task("fft", [a], [b]))
    assert builder.version_of(b) == 1
    assert builder.last_writer(b) == 0
    builder.add(Task("ifft", [a], [b]))  # rewrite bumps the version
    assert builder.version_of(b) == 2
    assert builder.last_writer(b) == 1
    # fragments version their parent root
    parent = ctx.malloc((32,), np.complex64)
    parent.fragment(16)
    builder.add(Task("fft", [a], [parent[1]]))
    assert builder.version_of(parent) == 1
    assert builder.last_writer(parent[0]) == 2


# ---------------------------------------------------------------------------
# session: correctness + equivalence with batch modes (acceptance)
# ---------------------------------------------------------------------------


def test_session_radar_chain_matches_numpy():
    with make_session(accelerators=("gpu0", "gpu1")) as s:
        bufs = submit_2fzf(s, 256, seed=7)
        want = np.fft.ifft(
            np.fft.fft(bufs["a"].data) * np.fft.fft(bufs["b"].data)
        ).astype(np.complex64)
        np.testing.assert_allclose(bufs["out"].result(), want, atol=1e-4)


def test_session_bit_identical_to_one_task_at_a_time_on_forkjoin():
    """Acceptance: the streaming session path produces bit-identical
    outputs and per-pair copy counts to the same fork-join built as a
    task list and driven one task at a time, under the rimms policy +
    static round_robin placement."""
    kw = dict(ways=4, n=1024, depth=2, seed=3)
    s = make_session(policy="rimms", scheduler="round_robin",
                     n_cpu=0, accelerators=("gpu0", "gpu1"))
    futs = submit_fork_join(s, **kw)
    out_stream = futs["out"].result().copy()
    s.barrier()
    snap_stream = s.ledger.snapshot()
    s.close()
    s.runtime.close()

    with make_session(policy="rimms", scheduler="round_robin",
                      n_cpu=0, accelerators=("gpu0", "gpu1")) as ref:
        bufs, tasks = build_fork_join(ref.context, **kw)
        submit_tasks(ref, tasks, one_at_a_time=True)
        out_ref = hete_sync(bufs["out"], context=ref.context).copy()
        snap_ref = ref.ledger.snapshot()
    ref.runtime.close()

    assert np.array_equal(out_stream, out_ref)
    assert snap_stream["by_pair"] == snap_ref["by_pair"]
    assert snap_stream["total_copies"] == snap_ref["total_copies"]


def test_concurrent_clients_bit_identical_to_one_task_at_a_time():
    """Four client threads streaming pinned 2FZF chains against one
    session produce the outputs and per-pair copy counts of the same
    chains built as one task list and driven one task at a time."""
    from repro.apps.radar import build_2fzf

    clients, chains, n, accs = 4, 2, 1024, ("gpu0", "gpu1")

    def seed(c, k):
        return 1000 + c * 97 + k

    s = make_session(policy="rimms", scheduler="round_robin", n_cpu=0,
                     accelerators=accs)
    outs, errors = {}, []

    def client(c):
        try:
            futs = [submit_2fzf(s, n, pins=(accs[c % 2],) * 4, seed=seed(c, k),
                                tag=f"_c{c}k{k}")["out"]
                    for k in range(chains)]
            outs[c] = [f.result(timeout=60) for f in futs]
        except BaseException as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    s.barrier()
    streamed = np.stack([np.stack(outs[c]) for c in range(clients)])
    snap_stream = s.ledger.snapshot()
    s.close()
    s.runtime.close()

    with make_session(policy="rimms", scheduler="round_robin", n_cpu=0,
                      accelerators=accs) as ref:
        rows, tasks = [], []
        for c in range(clients):
            row = []
            for k in range(chains):
                bufs, chain = build_2fzf(ref.context, n,
                                         pins=(accs[c % 2],) * 4,
                                         seed=seed(c, k))
                tasks += chain
                row.append(bufs["out"])
            rows.append(row)
        submit_tasks(ref, tasks, one_at_a_time=True)
        want = np.stack([np.stack([hete_sync(hd, context=ref.context)
                                   for hd in row]) for row in rows])
        snap_ref = ref.ledger.snapshot()
    ref.runtime.close()

    assert np.array_equal(streamed, want)
    assert snap_stream["by_pair"] == snap_ref["by_pair"]


def test_session_heft_windowed_placement_correct_and_multi_pe():
    with make_session(scheduler="heft", n_cpu=0,
                      accelerators=("gpu0", "gpu1")) as s:
        futs = submit_fork_join(s, ways=4, n=2048, depth=2, seed=1)
        out = futs["out"].result()
        assert np.all(np.isfinite(out))
        s.barrier()
        rep = s.report()
    assert rep["n_tasks"] == rep["n_completed"]
    used = {pe for _, pe in s.runtime.task_log}
    assert used == {"gpu0", "gpu1"}
    assert rep["makespan_model"] > 0


def test_session_report_replay_is_deterministic():
    """Same submissions → exactly the same replayed modeled makespan,
    run to run (the bench_stream gate depends on this)."""
    makespans = []
    for _ in range(2):
        with make_session(scheduler="round_robin", n_cpu=0,
                          accelerators=("gpu0", "gpu1")) as s:
            submit_fork_join(s, ways=4, n=1024, depth=2, seed=5)
            s.barrier()
            makespans.append(s.report()["makespan_model"])
    assert makespans[0] == makespans[1]


# ---------------------------------------------------------------------------
# session: concurrency + out-of-order completion
# ---------------------------------------------------------------------------


def test_concurrent_submitter_threads():
    """Multi-tenant streaming: N client threads submit radar chains
    against ONE session; every client's output matches numpy."""
    s = make_session(scheduler="round_robin", n_cpu=0,
                     accelerators=("gpu0", "gpu1"))
    results, errors = {}, []

    def client(i):
        try:
            bufs = submit_2fzf(s, 128, seed=i, tag=f"_c{i}",
                               pins=(f"gpu{i % 2}",) * 4)
            got = bufs["out"].result(timeout=60)
            want = np.fft.ifft(
                np.fft.fft(bufs["a"].data) * np.fft.fft(bufs["b"].data)
            ).astype(np.complex64)
            results[i] = (got, want)
        except BaseException as e:  # pragma: no cover - diagnostic
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == 8
    for got, want in results.values():
        np.testing.assert_allclose(got, want, atol=1e-4)
    s.barrier()
    assert s.report()["n_completed"] == 8 * 4
    s.close()


def test_out_of_order_completion_and_result():
    """A short independent chain completes (and resolves) while a long
    chain is still streaming; waiting on futures in reverse submission
    order works."""
    with make_session(scheduler="round_robin", n_cpu=0,
                      accelerators=("gpu0", "gpu1")) as s:
        long = submit_fork_join(s, ways=8, n=4096, depth=3, seed=2)
        short = submit_2fzf(s, 64, seed=9, tag="_s")
        short_out = short["out"].result(timeout=60)  # before the long chain
        long_out = long["out"].result(timeout=120)
        want = np.fft.ifft(
            np.fft.fft(short["a"].data) * np.fft.fft(short["b"].data)
        ).astype(np.complex64)
        np.testing.assert_allclose(short_out, want, atol=1e-4)
        assert np.all(np.isfinite(long_out))


def test_resubmitted_buffer_result_waits_for_latest_writer():
    """result() synchronizes the buffer: after resubmitting the same
    buffer as an output, it resolves to the newest submitted content."""
    with make_session(accelerators=("gpu0",), n_cpu=0,
                      scheduler="round_robin") as s:
        x = s.malloc((64,), np.complex64)
        x.data[:] = 1.0
        f1 = s.submit("fft", [x])
        f2 = s.submit("ifft", [f1], out=f1)  # overwrite f1's buffer
        np.testing.assert_allclose(f2.result(), x.data, atol=1e-4)
        assert f1.version == 1 and f2.version == 2
        # f1's handle now resolves to the rewritten (latest) bytes too
        np.testing.assert_allclose(f1.result(), x.data, atol=1e-4)


# ---------------------------------------------------------------------------
# session: exception propagation
# ---------------------------------------------------------------------------


def _boom_registry():
    reg = rimms.OpRegistry()

    @rimms.op("good", kinds=("cpu",), registry=reg)
    def good(ins):
        return ins[0] * 2

    @rimms.op("boom", kinds=("cpu",), registry=reg)
    def boom(ins):
        raise RuntimeError("kernel exploded")

    return reg


def test_exception_propagates_through_future_result():
    with rimms.Session.emulated(accelerators=(), n_cpu=1,
                                scheduler="round_robin",
                                registry=_boom_registry()) as s:
        x = s.malloc((8,), np.float32)
        y = s.submit("boom", [x])
        with pytest.raises(RuntimeError, match="kernel exploded"):
            y.result(timeout=30)
        assert isinstance(y.exception(), RuntimeError)
        # observed via result(): the exiting barrier must not re-raise


def test_failure_fails_dependent_subtree_but_not_independent_chains():
    s = rimms.Session.emulated(accelerators=(), n_cpu=1,
                               scheduler="round_robin",
                               registry=_boom_registry())
    x = s.malloc((8,), np.float32)
    x.data[:] = 1.0
    bad = s.submit("boom", [x])
    dependent = s.submit("good", [bad])
    independent = s.submit("good", [x])
    with pytest.raises(RuntimeError, match="kernel exploded"):
        dependent.result(timeout=30)
    np.testing.assert_allclose(independent.result(timeout=30), 2.0)
    # both failures observed through results → barrier is clean
    s.barrier()
    # the stream keeps flowing after a failure
    again = s.submit("good", [independent])
    np.testing.assert_allclose(again.result(timeout=30), 4.0)
    s.close()


def test_deep_dependent_chain_fails_without_recursion_blowup():
    """A failure at the head of a deeper-than-recursion-limit admitted
    chain must cascade iteratively: every dependent fails, the barrier
    raises (once), and the worker thread survives."""
    import sys

    depth = sys.getrecursionlimit() + 200
    s = rimms.Session.emulated(accelerators=(), n_cpu=1,
                               scheduler="round_robin",
                               registry=_boom_registry())
    x = s.malloc((4,), np.float32)
    cur = s.submit("boom", [x])
    for _ in range(depth):
        cur = s.submit("good", [cur])
    with pytest.raises(RuntimeError, match="kernel exploded"):
        cur.result(timeout=60)
    s.barrier()  # cascade observed through the tail future
    rep = s.report()
    assert rep["n_failed"] == depth + 1
    # the stream (and its PE worker) is still alive after the cascade
    ok = s.submit("good", [x])
    assert ok.result(timeout=30) is not None
    s.close()


def test_scalar_output_shape_is_respected():
    """out_shape=() (a 0-d scalar buffer) must not be discarded as
    falsy in favour of the input's shape."""
    reg = rimms.OpRegistry()

    @rimms.op("total", kinds=("cpu",), registry=reg)
    def total(ins):
        return np.float32(ins[0].sum())

    with rimms.Session.emulated(accelerators=(), n_cpu=1,
                                scheduler="round_robin",
                                registry=reg) as s:
        x = s.malloc((8,), np.float32)
        x.data[:] = 2.0
        f = s.submit("total", [x], out_shape=(), out_dtype=np.float32)
        assert f.shape == ()
        np.testing.assert_allclose(f.result(timeout=30), 16.0)


def test_barrier_raises_unobserved_failure_once():
    s = rimms.Session.emulated(accelerators=(), n_cpu=1,
                               scheduler="round_robin",
                               registry=_boom_registry())
    x = s.malloc((8,), np.float32)
    s.submit("boom", [x])
    with pytest.raises(RuntimeError, match="kernel exploded"):
        s.barrier()
    s.barrier()  # observed now: second barrier is clean
    s.close()


def test_bad_pin_fails_future_not_submitter():
    with rimms.Session.emulated(accelerators=("gpu0",),
                                scheduler="round_robin") as s:
        x = s.malloc((8,), np.complex64)
        y = s.submit("fft", [x], pin="no_such_pe")
        with pytest.raises(KeyError):
            y.result(timeout=30)


def test_unknown_op_fails_future():
    with rimms.Session.emulated(accelerators=("gpu0",),
                                scheduler="heft") as s:
        x = s.malloc((8,), np.complex64)
        y = s.submit("no_such_op", [x])
        with pytest.raises(LookupError):
            y.result(timeout=30)


# ---------------------------------------------------------------------------
# session: buffer lifecycle (free-after-last-use)
# ---------------------------------------------------------------------------


def test_free_after_last_use_defers_until_stream_drains():
    with make_session(accelerators=("gpu0",), n_cpu=0,
                      scheduler="round_robin") as s:
        x = s.malloc((1 << 16,), np.complex64)
        x.data[:] = 1.0
        y = s.submit("fft", [x])
        freed_now = x.free()  # may still be read by the in-flight fft
        out = y.result(timeout=60)
        s.barrier()
        assert x.hete.freed  # released after its last reader completed
        assert np.all(np.isfinite(out))
        assert not freed_now or x.hete.freed


def test_free_idle_buffer_is_immediate_and_double_free_raises():
    with make_session(accelerators=("gpu0",)) as s:
        x = s.malloc((64,), np.complex64)
        assert s.free(x) is True
        assert x.hete.freed
        with pytest.raises(AllocError, match="double hete_free"):
            s.free(x)


def test_submit_after_close_raises():
    s = make_session(accelerators=("gpu0",))
    s.close()
    with pytest.raises(RuntimeError, match="closed"):
        s.malloc((8,))
    with pytest.raises(RuntimeError, match="closed"):
        s.submit("fft", [np.zeros(8, np.complex64)])


def test_numpy_inputs_are_adopted():
    with make_session(accelerators=("gpu0",), n_cpu=0,
                      scheduler="round_robin") as s:
        sig = (np.arange(64) % 7).astype(np.complex64)
        f = s.submit("fft", [sig])
        np.testing.assert_allclose(
            f.result(timeout=30), np.fft.fft(sig).astype(np.complex64),
            atol=1e-4)
