"""Process PE-worker backend (ISSUE 7): backend selection, shared-memory
host arenas, thread↔process bit-identity + copy-count parity, worker
failure containment, subprocess lifecycle, platform presets, and
closed-loop think time in the QoS replay."""

import os
import time

import numpy as np
import pytest

import repro.apps.elemwise  # noqa: F401  registers numpy-only test ops
from repro.core import runtime as runtime_mod
from repro.core.api import Session
from repro.core.pworker import ProcessWorker, WorkerDied
from repro.core.qos import ClientState, QoSManager
from repro.core.runtime import (
    BACKENDS, platform_names, register_platform, resolve_backend,
)
from repro.core.shm import SharedHostArena, describe_array, resolve_handle


def _session(backend, **kwargs):
    kwargs.setdefault("policy", "rimms")
    kwargs.setdefault("scheduler", "round_robin")
    kwargs.setdefault("n_cpu", 1)
    kwargs.setdefault("accelerators", ("gpu0",))
    return Session.emulated(backend=backend, **kwargs)


def _close(session):
    session.close()
    session.runtime.close()


def _run_chain(backend):
    """scale→square→csum across cpu0 and gpu0; returns (out, by_pair)."""
    s = _session(backend)
    try:
        a = s.malloc((256,), np.float64)
        b = s.submit("scale", [a], factor=3.0, pin="gpu0")
        c = s.submit("square", [b], pin="cpu0")
        d = s.submit("csum", [c], pin="gpu0")
        out = np.array(d.result(timeout=180))
        return out, s.ledger.snapshot()["by_pair"]
    finally:
        _close(s)


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------


def test_resolve_backend_choices():
    assert resolve_backend(None) == "thread"
    assert resolve_backend("thread") == "thread"
    assert resolve_backend("process") == "process"
    assert resolve_backend("auto") in ("thread", "process")


def test_resolve_backend_auto_rule():
    expect = "process" if ((os.cpu_count() or 1) > 1) else None
    resolved = resolve_backend("auto")
    if expect == "process":
        assert resolved == "process"
    else:
        # single CPU: auto is process only if >1 jax device
        import jax

        assert resolved == ("process" if len(jax.devices()) > 1
                            else "thread")


def test_resolve_backend_auto_is_thread_on_tpu(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto") == "thread"
    assert resolve_backend("process") == "process"  # an explicit choice stands


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_process_backend_keeps_accelerators_on_a_tpu(monkeypatch, platform):
    """Host-format accelerator payloads (run by workers) only on a lone
    CPU device: a TPU's accelerators always hold its device arrays."""
    import jax

    from repro.core.locations import Location

    host_payloads = platform == "cpu" and len(jax.devices()) == 1
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    _, ctx = runtime_mod.make_emulated_soc(
        n_cpu=1, accelerators=("gpu0",), backend="process")
    try:
        space = ctx.spaces[Location("device", "gpu0")]
        assert space.proc_exec is host_payloads
        moved = space.ingest(np.ones(4))
        assert isinstance(moved, jax.Array) is not host_payloads
    finally:
        ctx.host_arena.destroy()


@pytest.mark.parametrize("parent_value", ["tpu", None])
def test_workers_start_with_cpu_only_jax(monkeypatch, parent_value):
    from repro.core.pworker import start_off_chip

    if parent_value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent_value)
    seen = {}

    class _Proc:
        def start(self):
            seen["child"] = os.environ.get("JAX_PLATFORMS")

    start_off_chip(_Proc())
    assert seen["child"] == "cpu"
    assert os.environ.get("JAX_PLATFORMS") == parent_value


def test_unknown_backend_rejected_with_choices():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("celery")
    with pytest.raises(ValueError) as ei:
        resolve_backend("celery")
    for choice in BACKENDS:
        assert choice in str(ei.value)


def test_session_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        _session("fork")


def test_session_exposes_backend_and_report():
    s = _session("thread")
    try:
        assert s.backend == "thread"
        assert s.report()["backend"] == "thread"
    finally:
        _close(s)


# ---------------------------------------------------------------------------
# shared-memory host arena
# ---------------------------------------------------------------------------


def test_arena_roundtrip_and_describe():
    arena = SharedHostArena(1 << 16)
    try:
        arr = arena.zeros((32,), np.float64)
        assert arr is not None and not arr.any()
        arr[:] = np.arange(32)
        h = describe_array(arr)
        assert h is not None and h[0] == arena.name
        view = resolve_handle(h)
        assert np.array_equal(view, arr)
        assert not view.flags.writeable
        heap = np.arange(8.0)  # not arena-backed → no handle
        assert describe_array(heap) is None
    finally:
        arena.destroy()


def test_arena_gc_returns_extents():
    arena = SharedHostArena(1 << 16)
    try:
        arr = arena.empty((1024,), np.float64)  # 8 KiB
        assert arr is not None
        used = arena.used_bytes()
        assert used >= 8192
        del arr
        assert arena.used_bytes() < used
    finally:
        arena.destroy()


def test_arena_full_falls_back_to_none():
    arena = SharedHostArena(1 << 12)  # 4 KiB
    try:
        assert arena.zeros((1 << 20,), np.float64) is None
        assert arena.copy_in(np.zeros(1 << 20)) is None
        assert arena.zeros((16,), np.float64) is not None
    finally:
        arena.destroy()
        arena.destroy()  # idempotent


# ---------------------------------------------------------------------------
# thread ↔ process parity (runs on any core count; 1-core is just slow)
# ---------------------------------------------------------------------------


def test_process_backend_bit_identical_to_thread():
    out_t, pairs_t = _run_chain("thread")
    out_p, pairs_p = _run_chain("process")
    assert np.array_equal(out_t, out_p)
    assert pairs_t == pairs_p


def test_process_backend_worker_lifecycle():
    s = _session("process")
    a = s.malloc((64,), np.float64)
    out = s.submit("scale", [a], factor=2.0, pin="gpu0").result(timeout=180)
    assert np.array_equal(np.asarray(out), np.zeros(64))
    pool = s.runtime._process_pool
    assert pool is not None
    pids = pool.pids()
    assert "gpu0" in pids
    procs = pool.procs()
    assert all(p.is_alive() for p in procs)
    _close(s)
    deadline = time.monotonic() + 10
    while any(p.is_alive() for p in procs) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not any(p.is_alive() for p in procs), "close() must reap workers"


def test_process_backend_kernel_error_propagates():
    s = _session("process")
    try:
        a = s.malloc((8,), np.float64)
        with pytest.raises(RuntimeError, match="boom kernel always fails"):
            s.submit("boom", [a], pin="gpu0").result(timeout=180)
    finally:
        _close(s)


def test_process_backend_worker_death_is_clean_error():
    s = _session("process")
    try:
        a = s.malloc((8,), np.float64)
        with pytest.raises(WorkerDied, match="exit code 17"):
            s.submit("die", [a], pin="gpu0").result(timeout=180)
        # the pool replaces the dead worker: later tasks still run
        out = s.submit("scale", [a], factor=1.0, pin="gpu0").result(
            timeout=180)
        assert np.array_equal(np.asarray(out), np.zeros(8))
    finally:
        _close(s)


def test_unpicklable_kernel_clear_error():
    w = ProcessWorker("t0")
    try:
        with pytest.raises(RuntimeError, match="module-level kernel"):
            w.ensure_kernel(("nope", "cpu"), lambda ins: ins[0])
    finally:
        w.shutdown()


@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="overlap needs >1 core")
def test_process_backend_overlaps_sleep_kernels():
    s = _session("process", n_cpu=1, accelerators=("gpu0", "gpu1"))
    try:
        bufs = [s.malloc((8,), np.float64) for _ in range(2)]
        for pe, b in zip(("gpu0", "gpu1"), bufs):  # warm both workers
            s.submit("scale", [b], factor=1.0, pin=pe).result(timeout=180)
        t0 = time.perf_counter()
        futs = [s.submit("snooze", [b], seconds=0.4, pin=pe)
                for pe, b in zip(("gpu0", "gpu1"), bufs)]
        for f in futs:
            f.result(timeout=180)
        wall = time.perf_counter() - t0
        assert wall < 0.72, f"no overlap: two 0.4s sleeps took {wall:.2f}s"
    finally:
        _close(s)


def test_process_backend_traced_run_lints_clean():
    from repro.core.trace import trace, trace_lint

    s = _session("process")
    try:
        with trace(s.context) as tc:
            a = s.malloc((64,), np.float64)
            out = s.submit("scale", [a], factor=2.0, pin="gpu0").result(
                timeout=180)
            assert np.asarray(out).shape == (64,)
            s.barrier()
        doc = tc.export()
        assert trace_lint(doc) == []
        worker_spans = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "X"
            and (e.get("args") or {}).get("backend") == "process"
        ]
        assert worker_spans, "no forwarded worker spans in trace"
    finally:
        _close(s)


# ---------------------------------------------------------------------------
# platform presets
# ---------------------------------------------------------------------------


def test_builtin_platforms_registered():
    names = platform_names()
    for preset in ("emulated_soc", "pcie_tree", "nvlink_mesh",
                   "host_bridged_fpga"):
        assert preset in names


def test_session_emulated_platform_shorthand():
    s = Session.emulated("pcie_tree", policy="rimms",
                         accelerators=("gpu0", "gpu1"))
    try:
        assert s.backend == "thread"
    finally:
        _close(s)


def test_unknown_platform_lists_presets():
    with pytest.raises(ValueError, match="unknown platform"):
        Session.emulated("my_quantum_soc")


def test_register_platform_custom_and_duplicate():
    name = "test_soc_pr7"
    register_platform(name, arena_bytes=1 << 20, replace=True)
    assert name in platform_names()
    with pytest.raises(ValueError):
        register_platform(name)
    register_platform(name, arena_bytes=2 << 20, replace=True)


# ---------------------------------------------------------------------------
# closed-loop think time (QoS replay)
# ---------------------------------------------------------------------------


def test_client_state_think_time_validation():
    assert ClientState("c").think_s == 0.0
    assert ClientState("c", think_s=0.25).think_s == 0.25
    with pytest.raises(ValueError):
        ClientState("c", think_s=-1.0)


def test_qos_client_think_time_param():
    qos = QoSManager()
    qos.client("a", think_s=0.5)
    assert qos.params()["clients"]["a"]["think_s"] == 0.5
    qos.client("a", think_s=0.0)
    assert qos.params()["clients"]["a"]["think_s"] == 0.0
    with pytest.raises(ValueError):
        qos.client("b", think_s=-0.1)


def test_session_think_time_stretches_replay():
    """With closed-loop think time a client re-submits only after its
    think delay, so the QoS-replayed makespan grows by ~chains*think_s
    (``report()`` stays QoS-blind; ``qos_report()`` re-enacts
    admission)."""
    def run(think_s):
        s = _session("thread", n_cpu=0, accelerators=("gpu0",))
        try:
            cl = s.client("c0", window=1, think_s=think_s)
            for k in range(4):
                a = s.malloc((64,), np.float64)
                cl.submit("scale", [a], factor=2.0, pin="gpu0",
                          name=f"t{k}").result(timeout=180)
            s.barrier()
            return s.qos_report()["makespan_model"]
        finally:
            _close(s)

    base = run(0.0)
    slow = run(0.01)
    assert slow >= base + 0.025, (
        f"think_s=10ms over 4 sequential tasks should stretch the "
        f"QoS-replayed makespan by >=25ms (got {base:.6f} -> {slow:.6f})"
    )
