"""Launch batching on a PE worker: the ready tasks queued together on an
in-process accelerator PE share one kernel launch — each task's kernel
dispatched, one wait for the device — whatever their ops, shapes and
params."""

import threading

import numpy as np
import pytest

from repro.apps import radar
from repro.core import api as rimms
from repro.core.hete import hete_sync

C64 = np.complex64


@pytest.fixture(scope="module")
def frame():
    """One SAR frame at scale 8 through a Session: its two phase outputs
    and the session's launch counters."""
    with radar.make_session(scheduler="round_robin", backend="thread") as s:
        bufs, tasks = radar.build_sar(s.context, scale=8, seed=3)
        for t in tasks:
            s.submit(t.op, t.inputs, out=t.outputs, name=t.name)
        s.barrier()
        outs = [hete_sync(bufs[p]["out"][0], context=s.context).copy()
                for p in ("phase1", "phase2")]
        counts = {k: v["value"] for k, v in s.metrics.snapshot().items()
                  if k.startswith("launch/")}
    s.runtime.close()
    return outs, counts


def test_sar_frame_bitwise_equal_to_one_task_per_launch(frame):
    """The serial path launches one task at a time, on the same
    placement."""
    rt, ctx = radar.make_runtime(policy="rimms", scheduler="round_robin",
                                 backend="thread")
    bufs, tasks = radar.build_sar(ctx, scale=8, seed=3)
    rt._run_impl(tasks)
    for p, batched in zip(("phase1", "phase2"), frame[0]):
        np.testing.assert_array_equal(
            hete_sync(bufs[p]["out"][0], context=ctx), batched)
    rt.close()


def test_launch_counters_per_pe(frame):
    _, counts = frame
    assert counts["launch/gpu0/tasks"] > counts["launch/gpu0/launches"]
    # the CPU PE works on the host: one task a launch
    assert counts["launch/cpu0/tasks"] == counts["launch/cpu0/launches"] > 0
    # every task of the frame ran in exactly one launch
    assert counts["launch/gpu0/tasks"] + counts["launch/cpu0/tasks"] == 3072 // 8


def _gated_session(registry):
    """A traced session on one accelerator PE whose first task holds the
    worker until ``gate`` is set, so the tasks submitted meanwhile queue
    up together."""
    gate, held = threading.Event(), threading.Event()

    @rimms.op("hold", kinds=("gpu",), registry=registry)
    def hold(ins):
        held.set()
        gate.wait(60)
        return ins[0]

    s = rimms.Session.emulated(n_cpu=0, accelerators=("gpu0",),
                               scheduler="round_robin", backend="thread",
                               registry=registry, trace=True)
    x = s.malloc((8,), C64)
    x.data[:] = 1
    s.submit("hold", [x])
    assert held.wait(60)
    return s, gate


def _ins(s, values, n=8):
    bufs = []
    for v in values:
        b = s.malloc((n,), C64)
        b.data[:] = v
        bufs.append(b)
    return bufs


def _launches(s, names):
    """The launch id of each named task's compute span (one each)."""
    spans = [e for e in s.context.tracer.wall_events()
             if e[0] == "X" and e[2] == "compute" and e[1] in names]
    assert sorted(e[1] for e in spans) == sorted(names)
    return {e[6]["launch"] for e in spans}


class _FailsOnWait:
    def block_until_ready(self):
        raise ValueError("bad sample")


@pytest.mark.parametrize("where", ["dispatch", "wait"])
def test_failing_task_in_a_batch_fails_alone(where):
    """A kernel that raises for one task, or whose output fails the wait
    for the device, fails that task and its subtree; its launch-mates
    complete, and no task runs twice."""
    reg = rimms.OpRegistry()

    def triple(ins):
        if float(ins[0][0].real) == 13.0:
            if where == "dispatch":
                raise ValueError("bad sample")
            return _FailsOnWait()
        return ins[0] * 3

    reg.register("triple", "gpu", triple)
    reg.register("inc", "gpu", lambda ins: ins[0] + 1)
    s, gate = _gated_session(reg)
    try:
        names = [f"t{k}" for k in range(4)]
        futs = [s.submit("triple", [b], name=name)
                for b, name in zip(_ins(s, [1, 13, 2, 5]), names)]
        child = s.submit("inc", [futs[1]])
        gate.set()
        for f, v in zip(futs, [1, None, 2, 5]):
            if v is None:
                with pytest.raises(ValueError, match="bad sample"):
                    f.result(timeout=60)
            else:
                np.testing.assert_array_equal(f.result(timeout=60),
                                              np.full(8, 3 * v, C64))
        with pytest.raises(ValueError, match="bad sample"):
            child.result(timeout=60)
        snap = s.metrics.snapshot()
        # the hold task's launch, then one launch of four that completed three
        assert snap["launch/gpu0/launches"]["value"] == 2
        assert snap["launch/gpu0/tasks"]["value"] == 1 + 3
        assert len(_launches(s, names)) == 1
    finally:
        gate.set()
        s.close()
        s.runtime.close()


@pytest.mark.parametrize("case", ["mixed_ops_and_shapes", "task_params"])
def test_ready_tasks_share_a_launch(case):
    reg = rimms.OpRegistry()

    def scale(ins, k=2.0):
        return ins[0] * k

    reg.register("scale", "gpu", scale)
    reg.register("inc", "gpu", lambda ins: ins[0] + 1)
    s, gate = _gated_session(reg)
    try:
        if case == "task_params":
            futs = [s.submit("scale", [b], k=float(v), name=f"t{v}")
                    for v, b in zip(range(1, 5), _ins(s, range(1, 5)))]
            want = [np.full(8, v * v, C64) for v in range(1, 5)]
        else:
            (a, b), (c, d) = _ins(s, [1, 2]), _ins(s, [3, 4], n=16)
            futs = [s.submit("scale", [a], name="t1"), s.submit("inc", [b], name="t2"),
                    s.submit("scale", [c], name="t3"), s.submit("inc", [d], name="t4")]
            want = [np.full(8, 2, C64), np.full(8, 3, C64),
                    np.full(16, 6, C64), np.full(16, 5, C64)]
        gate.set()
        for f, w in zip(futs, want):
            np.testing.assert_array_equal(f.result(timeout=60), w)
        snap = s.metrics.snapshot()
        assert snap["launch/gpu0/launches"]["value"] == 2
        assert snap["launch/gpu0/tasks"]["value"] == 5
        assert len(_launches(s, [f"t{k}" for k in range(1, 5)])) == 1
        # the launch's compute spans overlap on the PE's track by design
        assert rimms.trace_lint(s.export_trace()) == []
    finally:
        gate.set()
        s.close()
        s.runtime.close()


def test_batches_under_capacity_pressure():
    """The ``test_pressure`` set-up (radar chains over fragmented buffers
    on one accelerator whose arena holds three of them) through a
    session: launches stop growing before they would evict, so none
    raises ``AllocError``, and the outputs match a roomy arena's."""
    ways, n = 4, 512
    outs = {}
    for arena in (64 << 20, 3 * ways * n * 8):
        with radar.make_session(n_cpu=0, scheduler="round_robin",
                                backend="thread", arena_bytes=arena) as s:
            pts, tasks = radar._parallel_fzf(s.context, ways, n, use_fragment=True)
            for t in tasks:
                s.submit(t.op, t.inputs, out=t.outputs, name=t.name)
            s.barrier()
            outs[arena] = hete_sync(pts["out"][0], context=s.context).copy()
            evictions = s.ledger.total_evictions
        s.runtime.close()
    assert evictions > 0
    np.testing.assert_array_equal(*outs.values())
