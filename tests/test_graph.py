"""Task-graph construction + the stream executor: dependency
correctness, equivalence with the stream driven one task at a time
(bitwise outputs, identical copy counts), and HEFT-lite placement."""

import numpy as np
import pytest

from repro.apps.radar import build_2fzf, build_3zip, build_pd, build_sar, make_session
from repro.apps.synthetic import build_fork_join
from repro.core.graph import CostModel, build_graph
from repro.core.hete import HeteContext, hete_sync
from repro.core.runtime import Task
from session_tasks import submit_tasks


# ---------------------------------------------------------------------------
# DAG construction
# ---------------------------------------------------------------------------


def _mk(ctx, n=16):
    return ctx.malloc((n,), np.complex64)


def test_raw_edges_linear_chain():
    ctx = HeteContext()
    a, b, c = _mk(ctx), _mk(ctx), _mk(ctx)
    g = build_graph([
        Task("fft", [a], [b], name="t0"),
        Task("ifft", [b], [c], name="t1"),
    ])
    assert g.edges() == [(0, 1)]
    assert g.critical_path_len == 2


def test_raw_fork_and_join_edges():
    ctx = HeteContext()
    a, l, r, o = (_mk(ctx) for _ in range(4))
    g = build_graph([
        Task("fft", [a], [l]),
        Task("fft", [a], [r]),
        Task("zip", [l, r], [o]),
    ])
    assert g.edges() == [(0, 2), (1, 2)]
    assert g.critical_path_len == 2
    assert len(g.roots()) == 2


def test_war_edge_on_overwrite():
    ctx = HeteContext()
    a, b, x = _mk(ctx), _mk(ctx), _mk(ctx)
    g = build_graph([
        Task("zip", [a, b], [x], name="reader"),
        Task("fft", [a], [a], name="overwriter"),  # in-place: WAR on reader
    ])
    assert (0, 1) in g.edges()


def test_waw_edge_between_writers():
    ctx = HeteContext()
    a, x = _mk(ctx), _mk(ctx)
    g = build_graph([
        Task("fft", [a], [x]),
        Task("ifft", [a], [x]),  # rewrites x: WAW
    ])
    assert (0, 1) in g.edges()


def test_fragments_alias_parent_but_not_siblings():
    ctx = HeteContext()
    parent = ctx.malloc((32,), np.complex64)
    parent.fragment(16)
    other = _mk(ctx, 16)
    tasks = [
        Task("fft", [other], [parent[0]], name="w_frag0"),
        Task("fft", [other], [parent[1]], name="w_frag1"),
        Task("fft", [parent], [other], name="r_parent"),  # reads whole parent
    ]
    g = build_graph(tasks)
    edges = g.edges()
    assert (0, 2) in edges and (1, 2) in edges  # parent read sees both writes
    assert (0, 1) not in edges  # sibling fragments are independent


def test_parent_write_orders_before_fragment_read():
    ctx = HeteContext()
    parent = ctx.malloc((32,), np.complex64)
    parent.fragment(16)
    other = _mk(ctx, 32)
    o2 = _mk(ctx, 16)
    g = build_graph([
        Task("fft", [other], [parent], name="w_parent"),
        Task("fft", [parent[1]], [o2], name="r_frag1"),
    ])
    assert (0, 1) in g.edges()


def test_independent_tasks_have_no_edges():
    ctx = HeteContext()
    bufs = [_mk(ctx) for _ in range(4)]
    g = build_graph([
        Task("fft", [bufs[0]], [bufs[1]]),
        Task("fft", [bufs[2]], [bufs[3]]),
    ])
    assert g.n_edges == 0
    assert g.critical_path_len == 1


# ---------------------------------------------------------------------------
# Stream executor: equivalence with the stream driven one task at a time
# ---------------------------------------------------------------------------


def _sar_outs(pts):
    return [pts["phase1"]["out"][0], pts["phase2"]["out"][0]]


# name -> (build(ctx) -> (buffers, tasks), buffers -> output buffers,
#          CPU PEs, accelerators)
WORKLOADS = {
    "2fzf": (lambda c: build_2fzf(c, 256, seed=7), lambda b: [b["out"]],
             1, ("gpu0",)),
    "forkjoin": (lambda c: build_fork_join(c, ways=4, n=1024, depth=2, seed=3),
                 lambda b: [b["out"]], 0, ("gpu0", "gpu1")),
    "3zip": (lambda c: build_3zip(c, 256, seed=11), lambda b: [b["out"]],
             0, ("gpu0", "gpu1")),
    "pd_fragmented": (lambda c: build_pd(c, ways=4, n=64, use_fragment=True),
                      lambda p: [p["out"][0]], 0, ("gpu0", "gpu1")),
    "sar_scale8": (lambda c: build_sar(c, scale=8, seed=3), _sar_outs,
                   1, ("gpu0",)),
}


@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch", "no_prefetch"])
@pytest.mark.parametrize("policy", ["rimms", "reference"])
@pytest.mark.parametrize("workload", list(WORKLOADS))
def test_stream_matches_one_task_at_a_time(workload, policy, prefetch):
    """A Session with everything on (concurrent PE workers, launch
    batching, prefetch when asked) produces bitwise-identical outputs
    and identical per-pair copy counts to the same Session driven one
    task at a time, on the same static round-robin placement."""
    build, outputs, n_cpu, accs = WORKLOADS[workload]
    outs, snaps, launches = {}, {}, {}
    for one in (False, True):
        with make_session(policy=policy, scheduler="round_robin",
                          n_cpu=n_cpu, accelerators=accs,
                          prefetch=prefetch) as s:
            bufs, tasks = build(s.context)
            submit_tasks(s, tasks, one_at_a_time=one)
            snaps[one] = s.ledger.snapshot()
            outs[one] = [hete_sync(hd, context=s.context).copy()
                         for hd in outputs(bufs)]
            launches[one] = {k: v["value"]
                             for k, v in s.metrics.snapshot().items()
                             if k.startswith("launch/")}
        s.runtime.close()
    for got, want in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(got, want)
    assert snaps[False]["by_pair"] == snaps[True]["by_pair"]
    assert snaps[False]["total_copies"] == snaps[True]["total_copies"]
    # the reference really launched one task at a time
    for pe in accs:
        assert (launches[True][f"launch/{pe}/launches"]
                == launches[True][f"launch/{pe}/tasks"])


def test_stream_fragmented_pd():
    """Pulse-Doppler with fragment() (§3.2.3) runs correctly through a
    session: every way's IFFT(FFT(a)*FFT(b)) matches numpy."""
    with make_session(policy="rimms", scheduler="round_robin", n_cpu=0,
                      accelerators=("gpu0", "gpu1")) as s:
        points, tasks = build_pd(s.context, ways=4, n=64, use_fragment=True)
        submit_tasks(s, tasks)
        for i in range(4):
            a = points["a"][1][i].data.copy()
            b = points["b"][1][i].data.copy()
            want = np.fft.ifft(np.fft.fft(a) * np.fft.fft(b)).astype(np.complex64)
            got = hete_sync(points["out"][1][i], context=s.context)
            np.testing.assert_allclose(got, want, atol=1e-4)
    s.runtime.close()


def test_stream_empty_task_list():
    with make_session(policy="rimms") as s:
        submit_tasks(s, [])
        rep = s.report()
    s.runtime.close()
    assert rep["n_tasks"] == 0 and rep["makespan_model"] == 0.0


def test_stream_raises_on_bad_pin_of_dependent_task():
    """Regression: a scheduling error for a *non-root* task (raised while
    completing its dependency) must surface, not hang the stream."""
    s = make_session(policy="rimms", scheduler="heft", accelerators=("gpu0",))
    a, b, c = (_mk(s.context, 32) for _ in range(3))
    tasks = [
        Task("fft", [a], [b], name="ok"),
        Task("ifft", [b], [c], pin="no_such_pe", name="bad_pin"),
    ]
    with pytest.raises(KeyError):
        submit_tasks(s, tasks)
    s.close()
    s.runtime.close()


# ---------------------------------------------------------------------------
# Scheduling: HEFT-lite
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("one_at_a_time", [False, True],
                         ids=["concurrent", "one_at_a_time"])
def test_heft_stream_produces_correct_results(one_at_a_time):
    with make_session(policy="rimms", scheduler="heft", n_cpu=1,
                      accelerators=("gpu0", "gpu1")) as s:
        bufs, tasks = build_2fzf(s.context, 128, seed=9)
        submit_tasks(s, tasks, one_at_a_time=one_at_a_time)
        want = np.fft.ifft(
            np.fft.fft(bufs["a"].data) * np.fft.fft(bufs["b"].data)
        ).astype(np.complex64)
        np.testing.assert_allclose(
            hete_sync(bufs["out"], context=s.context), want, atol=1e-4)
    s.runtime.close()


def test_timeline_gantt_renders():
    with make_session(policy="rimms", scheduler="round_robin", n_cpu=0,
                      accelerators=("gpu0", "gpu1")) as s:
        _, tasks = build_fork_join(s.context, ways=2, n=512, depth=1)
        submit_tasks(s, tasks)
        txt = s.report()["timeline"].gantt(40)
    s.runtime.close()
    assert "gpu0" in txt and "gpu1" in txt and "#" in txt


def test_cost_model_learns_from_observations():
    cm = CostModel()
    prior = cm.estimate("fft", "acc", 1 << 20)
    cm.observe("fft", "acc", 1 << 20, 0.5)  # much slower than prior
    assert cm.estimate("fft", "acc", 1 << 20) > prior
    assert cm.prior_estimate("fft", "acc", 1 << 20) == pytest.approx(prior)


def test_upward_ranks_decrease_along_chain():
    ctx = HeteContext()
    a, b, c, d = (_mk(ctx) for _ in range(4))
    g = build_graph([
        Task("fft", [a], [b]),
        Task("fft", [b], [c]),
        Task("fft", [c], [d]),
    ])
    g.compute_ranks(lambda t: 1.0, lambda t: 0.1)
    ranks = [n.rank for n in g.nodes]
    assert ranks[0] > ranks[1] > ranks[2]
