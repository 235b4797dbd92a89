"""The reader of ``tasks_per_launch.radar`` and ``launch_cost.launch_facts``:
on hand-built spans, on spans without launch ids, and on a whole traced
radar run on the CPU."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import launch_cost  # noqa: E402
import run  # noqa: E402


def reader():
    return run.load_module(BENCH / "metrics" / "tasks_per_launch.radar.py",
                           "bench_metric_tasks_per_launch_radar")


def compute(task, track, **args):
    return ("X", task, "compute", track, 0.0, 1e-3, {"task": task, **args})


def test_tasks_over_distinct_launches():
    spans = [compute("a", "pe:gpu0", launch=1, batch=3),
             compute("b", "pe:gpu0", launch=1, batch=3),
             compute("c", "pe:gpu0", launch=1, batch=3),
             compute("d", "pe:gpu0", launch=2, batch=1),
             compute("e", "pe:cpu0", launch=3, batch=1),
             ("X", "f", "stage", "pe:gpu0:stage", 0.0, 1e-3,
              {"task": "f", "prefetch": 1})]
    assert reader().read({"acc": "gpu0", "spans": spans}) == pytest.approx(2.0)


def test_no_launch_ids_nothing_to_read():
    """A program whose compute spans carry no launch id, and a run
    without spans: nothing to read, and no raise."""
    old = [compute("a", "pe:gpu0"), compute("b", "pe:gpu0")]
    assert reader().read({"acc": "gpu0", "spans": old}) is None
    assert reader().read({"acc": "gpu0", "spans": None}) is None


def test_launch_facts_by_size_and_thread_share():
    def span(task, cat, track, t0, dur, **args):
        return ("X", task, cat, track, t0, dur, {"task": task, **args})

    spans = [span("a", "stage", "pe:gpu0:stage", 0.0, 1.0),
             span("a", "compute", "pe:gpu0", 1.0, 2.0, launch=1, batch=2),
             span("b", "compute", "pe:gpu0", 1.0, 3.0, launch=1, batch=2),
             span("c", "compute", "pe:gpu0", 5.0, 1.0, launch=2, batch=1),
             span("c", "writeback", "pe:gpu0", 6.0, 1.0),
             span("d", "stage", "pe:gpu0:stage", 7.0, 1.0, prefetch=1),
             span("e", "compute", "pe:cpu0", 0.0, 9.0, launch=3, batch=1)]
    facts = launch_cost.launch_facts(spans, "gpu0", 10.0)
    assert (facts["launches"], facts["tasks"]) == (2, 3)
    assert facts["tasks_per_launch"] == pytest.approx(1.5)
    assert facts["launch_ms_by_tasks"] == {
        1: {"launches": 1, "median_ms": pytest.approx(1e3)},
        2: {"launches": 1, "median_ms": pytest.approx(3e3)}}
    assert facts["thread_share_pct"] == pytest.approx(
        {"compute": 40.0, "stage": 10.0, "writeback": 10.0, "none": 40.0})


def test_on_a_traced_radar_run():
    import cells

    spec = cells.tiny("sar-mixed")
    drv_mod = run.load_module(BENCH / "drivers" / "radar.py", "bench_driver_radar")
    drv = drv_mod.Driver(spec["config"], spec["traffic"], cells.SEED, trace=True)
    drv.setup()
    drv.run_window(1.0)
    facts = drv.facts()
    drv.release()
    assert reader().read(facts) >= 1.0
    # every task the accelerator ran still has its own compute span
    acc = [e for e in facts["spans"] if e[2] == "compute" and e[3] == "pe:gpu0"]
    assert len({e[1] for e in acc}) == len(acc)
    launches = launch_cost.launch_facts(facts["spans"], "gpu0", facts["span_window_s"])
    assert launches["tasks"] == len(acc)
    assert sum(launches["thread_share_pct"].values()) >= 100.0 - 1e-6
