"""Tracing + metrics subsystem (ISSUE 6): ring buffers, Perfetto
export, histograms, and the trace_lint invariant checker."""

import json
import threading

import numpy as np

from repro.apps.radar import build_2fzf, make_session, submit_2fzf
from repro.core import api as rimms
from repro.core.hete import HeteContext
from repro.core.trace import (
    MODEL_PID,
    WALL_PID,
    Histogram,
    MetricsRegistry,
    TraceCollector,
    global_collector,
    trace,
    trace_lint,
)
from session_tasks import submit_tasks


# ---------------------------------------------------------------------------
# collector mechanics
# ---------------------------------------------------------------------------


def test_ring_buffer_bounds_memory_and_counts_drops():
    tc = TraceCollector(capacity_per_thread=4)
    for i in range(10):
        tc.instant(f"e{i}", "test", "t")
    assert tc.event_count() == 4
    assert tc.drops() == 6
    # drops surface as a lint violation: the trace is incomplete
    assert any("dropped" in v for v in trace_lint(tc.export()))


def test_disabled_collector_records_nothing():
    tc = TraceCollector()
    tc.pause()
    tc.instant("e", "test", "t")
    tc.span("s", "test", "t", 0.0, 1.0)
    tc.transfer("ctx0", "host", "gpu0", 128, 0.1)
    assert tc.event_count() == 0
    tc.resume()
    tc.instant("e", "test", "t")
    assert tc.event_count() == 1


def test_per_thread_rings_need_no_lock_on_hot_path():
    tc = TraceCollector(capacity_per_thread=1 << 12)
    n, threads = 1000, 4

    def emit(k):
        for i in range(n):
            tc.instant(f"t{k}.{i}", "test", f"thr:{k}")

    ts = [threading.Thread(target=emit, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert tc.event_count() == n * threads
    assert tc.drops() == 0


def test_export_structure_is_perfetto_loadable():
    tc = TraceCollector()
    t0 = tc.now()
    tc.span("work", "compute", "pe:gpu0", t0, t0 + 0.001, {"task": "work"})
    tc.instant("evict", "memory", "mem:gpu0", {"nbytes": 64})
    doc = tc.export()
    json.dumps(doc)  # must be JSON-serializable
    evs = doc["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert WALL_PID in pids
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"pe:gpu0", "mem:gpu0"} <= names
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["dur"] > 0 and xs[0]["cat"] == "compute"
    insts = [e for e in evs if e["ph"] == "i"]
    assert insts and all(e["s"] == "t" for e in insts)
    assert doc["rimms"]["drops"] == 0


def test_modeled_and_wall_land_in_separate_process_groups():
    s = make_session(scheduler="round_robin", accelerators=("gpu0",))
    ctx = s.context
    with trace(context=ctx) as tc:
        _, tasks = build_2fzf(ctx, 64, pins=("gpu0",) * 4)
        submit_tasks(s, tasks)
        s.close()  # pushes the stream's modeled timeline
        doc = tc.export()
    s.runtime.close()
    by_pid = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            by_pid.setdefault(e["pid"], set()).add(e["cat"])
    assert "compute" in by_pid[WALL_PID]
    assert "compute" in by_pid[MODEL_PID]
    assert trace_lint(doc) == []
    assert ctx.tracer is None  # detached on exit


def test_global_trace_attaches_new_contexts():
    assert global_collector() is None
    with trace() as tc:
        assert global_collector() is tc
        ctx = HeteContext()
        assert ctx.tracer is tc
    assert global_collector() is None


def test_eviction_instants_under_pressure():
    import numpy as np_
    from repro.core.hete import HeteContext, MemorySpace, hete_malloc
    from repro.core.locations import Location

    acc = Location("device", "acc0")
    with trace() as tc:
        ctx = HeteContext(tracking="flag")
        ctx.register_space(MemorySpace(
            acc, capacity=4096, allocator="nextfit",
            ingest=lambda a: a.copy(), egress=lambda a: np_.asarray(a),
        ))
        for _ in range(4):
            hd = hete_malloc((512,), np_.float32, context=ctx)
            v = ctx.ensure(hd, acc)
            ctx.mark_written(hd, acc, v + 1.0)
        doc = tc.export()
    assert ctx.ledger.total_evictions > 0
    evicts = [e for e in doc["traceEvents"]
              if e.get("ph") == "i" and e.get("name") in ("evict", "spill_to_peer")]
    assert len(evicts) == ctx.ledger.total_evictions
    assert all(e["cat"] == "memory" for e in evicts)
    assert trace_lint(doc) == []


# ---------------------------------------------------------------------------
# session end-to-end
# ---------------------------------------------------------------------------


def test_session_trace_end_to_end(tmp_path):
    sess = make_session(trace=True)
    try:
        submit_2fzf(sess, 64)
        sess.barrier()
        rep = sess.qos_report()
        pct = rep["latency_percentiles"]
        assert pct, "per-client percentiles missing"
        for stats in pct.values():
            assert 0.0 < stats["p50"] <= stats["p95"] <= stats["p99"]
            assert stats["count"] > 0
        assert rep["metrics"]["submits"]["value"] == 4
        sess.close()
        path = tmp_path / "session.json"
        doc = sess.export_trace(str(path))
        assert path.exists()
        assert trace_lint(str(path)) == []
        cats = {e.get("cat") for e in doc["traceEvents"]}
        # full lifecycle: submit -> qos -> stage -> compute -> transfer
        assert {"submit", "qos", "stage", "compute", "transfer"} <= cats
        tenant_tracks = [
            e for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
            and "tenant:" in e["args"]["name"]
        ]
        assert tenant_tracks
    finally:
        sess.runtime.close()


def test_session_export_without_tracer_raises():
    sess = make_session()
    try:
        submit_2fzf(sess, 64)
        sess.barrier()
        try:
            sess.export_trace()
            raise AssertionError("expected RuntimeError")
        except RuntimeError:
            pass
    finally:
        sess.close()
        sess.runtime.close()


def test_trace_reexported_through_api():
    assert rimms.trace is trace
    assert rimms.trace_lint is trace_lint


# ---------------------------------------------------------------------------
# trace_lint negative cases
# ---------------------------------------------------------------------------


def _doc(events, rimms_meta=None):
    return {"traceEvents": events, "rimms": rimms_meta or {}}


def test_lint_flags_negative_duration():
    doc = _doc([{"ph": "X", "name": "bad", "cat": "compute",
                 "pid": 1, "tid": 1, "ts": 5.0, "dur": -1.0}])
    assert any("negative duration" in v for v in trace_lint(doc))


def test_lint_flags_overlapping_compute_spans():
    doc = _doc([
        {"ph": "M", "name": "thread_name", "pid": 2, "tid": 1,
         "args": {"name": "run0/pe:gpu0"}},
        {"ph": "X", "name": "a", "cat": "compute", "pid": 2, "tid": 1,
         "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "cat": "compute", "pid": 2, "tid": 1,
         "ts": 5.0, "dur": 10.0},
    ])
    assert any("overlap" in v for v in trace_lint(doc))
    # stage spans may overlap (prefetch/double-buffering): not flagged
    doc_stage = _doc([
        {"ph": "X", "name": "a", "cat": "stage", "pid": 2, "tid": 1,
         "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "cat": "stage", "pid": 2, "tid": 1,
         "ts": 5.0, "dur": 10.0},
    ])
    assert trace_lint(doc_stage) == []


def test_lint_flags_ledger_mismatch():
    meta = {"ledgers": {"ctx0": {"per_link": {
        "host->gpu0": {"copies": 2, "bytes": 256, "modeled_s": 0.0}},
        "bytes_moved": 256}}}
    # only one traced copy of 128 B against a ledger claiming 2/256
    doc = _doc([
        {"ph": "i", "name": "copy", "cat": "transfer", "pid": 1, "tid": 1,
         "ts": 0.0, "s": "t",
         "args": {"ctx": "ctx0", "src": "host", "dst": "gpu0",
                  "nbytes": 128, "epoch": 0}},
    ], meta)
    assert any("conservation" in v for v in trace_lint(doc))


def test_lint_flags_compute_before_staging_done():
    doc = _doc([
        {"ph": "M", "name": "thread_name", "pid": 2, "tid": 1,
         "args": {"name": "run0/pe:gpu0:stage"}},
        {"ph": "M", "name": "thread_name", "pid": 2, "tid": 2,
         "args": {"name": "run0/pe:gpu0"}},
        {"ph": "X", "name": "t", "cat": "stage", "pid": 2, "tid": 1,
         "ts": 0.0, "dur": 10.0, "args": {"node": 0}},
        {"ph": "X", "name": "t", "cat": "compute", "pid": 2, "tid": 2,
         "ts": 5.0, "dur": 10.0, "args": {"node": 0}},
    ])
    assert any("causality" in v for v in trace_lint(doc))


def test_lint_conservation_nets_out_preattach_baseline():
    s = make_session(scheduler="round_robin", accelerators=("gpu0",))
    ctx = s.context
    _, tasks = build_2fzf(ctx, 64, pins=("gpu0",) * 4)
    submit_tasks(s, tasks)  # untraced copies accumulate first
    with trace(context=ctx) as tc:
        _, tasks2 = build_2fzf(ctx, 64, pins=("gpu0",) * 4, seed=1)
        submit_tasks(s, tasks2)
        s.close()  # pushes the stream's modeled timeline
        assert trace_lint(tc.export()) == []
    s.runtime.close()


def test_lint_conservation_across_ledger_reset():
    s = make_session(scheduler="round_robin", accelerators=("gpu0",))
    ctx = s.context
    with trace(context=ctx) as tc:
        _, tasks = build_2fzf(ctx, 64, pins=("gpu0",) * 4)
        submit_tasks(s, tasks)
        ctx.ledger.reset()  # opens a fresh conservation epoch
        _, tasks2 = build_2fzf(ctx, 64, pins=("gpu0",) * 4, seed=1)
        submit_tasks(s, tasks2)
        s.close()  # pushes the stream's modeled timeline
        assert trace_lint(tc.export()) == []
    s.runtime.close()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_percentiles_match_numpy_within_bucket_error():
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=-8.0, sigma=1.5, size=5000)
    h = Histogram("lat")
    for x in xs:
        h.record(float(x))
    for q in (50, 95, 99):
        got = h.percentile(q)
        want = float(np.percentile(xs, q))
        assert abs(got - want) / want < 0.03, (q, got, want)
    assert h.count == len(xs)
    assert abs(h.mean - xs.mean()) / xs.mean() < 1e-9


def test_histogram_edge_cases():
    h = Histogram()
    # empty histogram: no samples -> percentile is None, not a raise
    assert h.percentile(50) is None and h.mean == 0.0
    h.record(0.0)
    h.record(-1.0)
    assert h.percentile(99) == 0.0  # non-positive values -> zero bucket
    h2 = Histogram()
    h2.record(4.2)
    assert h2.percentile(50) == 4.2  # single sample clamps to min/max


def test_metrics_registry_create_or_get_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a").inc(3)
    assert reg.counter("a").value == 3  # same instrument back
    reg.gauge("g").set(1.5)
    reg.histogram("h").record(2.0)
    try:
        reg.gauge("a")
        raise AssertionError("expected TypeError")
    except TypeError:
        pass
    snap = reg.snapshot()
    assert snap["a"] == {"type": "counter", "value": 3}
    assert snap["g"]["value"] == 1.5
    assert snap["h"]["count"] == 1
    assert reg.histograms() == [("h", reg.histogram("h"))]


# ---------------------------------------------------------------------------
# regions: one span in the ring and on the profiler's clock
# ---------------------------------------------------------------------------

RIMMS_CATS = ("submit", "qos", "stage", "copy", "compute", "writeback", "step",
              "admit")


def _profiled(tmp_path, work):
    """Run ``work()`` under the JAX profiler; return the host events named
    ``rimms.*`` as {line index: [(start, end, name, stats)]}."""
    import glob
    import os
    from urllib.parse import unquote

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for k, ln in enumerate(plane.lines):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    {k: unquote(v) if isinstance(v, str) else v for k, v in e.stats})
                   for e in ln.events if e.name.startswith("rimms.")]
            if evs:
                lines[(plane.name, k)] = sorted(evs, key=lambda x: (x[0], -x[1]))
    return lines


def _serve_and_radar(tc):
    """A tiny serving engine (step, admit, prefill and decode tasks) and a
    radar session with prefetch, both traced into ``tc``."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.session_engine import SessionServeEngine

    cfg = dataclasses.replace(get_config("llama3_8b").smoke(), dtype="float32")
    params = build_model(cfg).init(jax.random.key(1))
    with SessionServeEngine(cfg, params, max_batch=2, page_size=8, num_pages=16,
                            max_pages_per_seq=4, pages_per_group=8) as eng:
        eng.session.context.set_tracer(tc)
        eng.submit([3, 5, 7], 3, tenant="a")
        eng.run()
    eng.session.runtime.close()
    sess = make_session(trace=tc, accelerators=("gpu0",))
    try:
        submit_2fzf(sess, 64)
        sess.barrier()
    finally:
        sess.close()
        sess.runtime.close()


def _inside(inner, outers):
    return any(s <= inner[0] and inner[1] <= e for s, e, _, _ in outers)


def test_regions_reach_the_profiler_nested_per_thread_with_stats(tmp_path):
    tc = TraceCollector()
    lines = _profiled(tmp_path, lambda: _serve_and_radar(tc))
    events = [ev for evs in lines.values() for ev in evs]
    names = {ev[2] for ev in events}
    assert names == {f"rimms.{c}" for c in RIMMS_CATS}
    for s, e, name, stats in events:
        assert e >= s
        if name in ("rimms.stage", "rimms.compute", "rimms.writeback"):
            assert {"task", "op", "pe"} <= set(stats), (name, stats)
        elif name in ("rimms.submit", "rimms.qos"):
            assert {"task", "op", "client"} <= set(stats), (name, stats)
        elif name == "rimms.copy":
            assert set(stats) == {"src", "dst", "nbytes"} and stats["nbytes"] > 0
    assert any(st.get("prefetch") == 1 for _, _, n, st in events if n == "rimms.stage")
    # task names keep their "#" through the profiler's encoding
    assert any(st["task"].startswith("prefill#") for _, _, n, st in events
               if n == "rimms.compute")
    ops = {st["op"] for _, _, n, st in events if n == "rimms.compute"}
    assert {"llm_prefill", "llm_decode"} <= ops
    for evs in lines.values():
        by = {}
        for ev in evs:
            by.setdefault(ev[2], []).append(ev)
        # nesting within one thread's line
        for q in by.get("rimms.qos", []):
            assert _inside(q, by["rimms.submit"])
        for a in by.get("rimms.admit", []):
            assert _inside(a, by["rimms.step"])
        # a PE thread runs one phase at a time (a line is one thread id,
        # which a later session's thread may reuse)
        phases = sorted(by.get("rimms.compute", []) + by.get("rimms.writeback", []))
        assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
    # the prefill is submitted from inside admission, on the caller's thread
    prefill = [ev for ev in events if ev[2] == "rimms.submit"
               and ev[3]["op"] == "llm_prefill"]
    admits = [ev for ev in events if ev[2] == "rimms.admit"]
    assert prefill and all(_inside(p, admits) for p in prefill)


def test_ring_and_profiler_hold_the_same_spans(tmp_path):
    tc = TraceCollector()
    lines = _profiled(tmp_path, lambda: _serve_and_radar(tc))
    prof = {}
    for evs in lines.values():
        for _, _, name, _ in evs:
            prof[name] = prof.get(name, 0) + 1
    ring = {}
    for ph, _, cat, _, _, _, _ in tc.wall_events():
        if ph == "X" and cat in RIMMS_CATS:
            ring[f"rimms.{cat}"] = ring.get(f"rimms.{cat}", 0) + 1
    assert ring == prof
    # every copy the ledger recorded has its span
    transfers = [e for e in tc.wall_events() if e[2] == "transfer"]
    assert ring["rimms.copy"] == len(transfers) > 0
    assert trace_lint(tc.export()) == []


def test_tracing_off_builds_no_annotation_and_writes_no_event(monkeypatch):
    import jax.profiler

    built = []
    real = jax.profiler.TraceAnnotation

    def counting(*args, **kwargs):
        built.append(args[0])
        return real(*args, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    sess = make_session(accelerators=("gpu0",))
    paused = TraceCollector()
    try:
        assert sess.context.tracer is None
        submit_2fzf(sess, 64)
        sess.barrier()
        # a paused collector: attached, but every boundary is a no-op
        paused.pause()
        sess.context.set_tracer(paused)
        submit_2fzf(sess, 64, seed=1)
        sess.barrier()
    finally:
        sess.close()
        sess.runtime.close()
    assert built == []
    assert paused.wall_events() == []
    # the patch is live: a traced session builds its annotations with it
    on = make_session(trace=True, accelerators=("gpu0",))
    try:
        submit_2fzf(on, 64)
        on.barrier()
    finally:
        on.close()
        on.runtime.close()
    assert {f"rimms.{c}" for c in ("submit", "qos", "stage", "compute",
                                    "writeback")} <= set(built)


def test_lint_cli_passes_a_session_trace_and_fails_a_broken_one(tmp_path):
    """``python -m repro.core.trace`` exits 0 on a session's exported
    trace, 1 on one whose spans break an invariant."""
    from repro.core.trace import _main as lint_cli

    good = tmp_path / "good.json"
    with make_session(scheduler="round_robin", accelerators=("gpu0",),
                      trace=True) as s:
        submit_2fzf(s, 64, pins=("gpu0",) * 4)
    s.export_trace(str(good))
    s.runtime.close()
    assert lint_cli([str(good)]) == 0
    doc = json.loads(good.read_text())
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    span["dur"] = -1.0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    assert lint_cli([str(good), str(bad)]) == 1
