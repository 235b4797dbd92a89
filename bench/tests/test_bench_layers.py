"""The split of the device's idle time by runtime layer, and the readers of
the program's own spans: on hand-made intervals and facts, on a whole
radar run on the CPU, and on a small trace recorded on a TPU v5 lite
(one SAR frame cut to 48 tasks, with the program's ``rimms.*`` spans)."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import layer_idle as li  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = BENCH / "tests" / "data" / "sar_small_rimms.xplane.pb"
NS = 1e-9


def reader(name):
    return run.load_module(BENCH / "metrics" / f"{name}.py",
                           "bench_metric_" + name.replace(".", "_"))


# -- the split on hand-made intervals -------------------------------------------


def test_gaps_and_intersection():
    assert li.gaps([(10, 20), (15, 30), (50, 60)], 0, 55) == [(0, 10), (30, 50)]
    assert li.gaps([], 0, 5) == [(0, 5)]
    assert li.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert li.intersect([(0, 10)], []) == []


def test_nested_spans_take_the_innermost():
    spans = [(0, 100, "stage:fft"), (20, 40, "copy")]
    got = li.split_idle(spans, [(30, 50)], 0, 120)
    assert got == pytest.approx({"stage:fft": 70 * NS, "copy": 10 * NS,
                                 li.NO_TASK: 20 * NS})
    assert sum(got.values()) == pytest.approx(100 * NS)


def test_a_span_open_across_a_device_op():
    spans = [(0, 100, "compute:fft")]
    assert li.split_idle(spans, [(40, 60)], 0, 100) == pytest.approx(
        {"compute:fft": 80 * NS})
    assert li.split(spans, [(40, 60)]) == pytest.approx({"compute:fft": 20 * NS})


def test_two_threads_split_the_same_idle_time_their_own_way():
    busy, lo, hi = [(60, 70)], 0, 100
    a = li.split_idle([(0, 50, "stage:zip")], busy, lo, hi)
    b = li.split_idle([(25, 75, "compute:zip")], busy, lo, hi)
    assert a == pytest.approx({"stage:zip": 50 * NS, li.NO_TASK: 40 * NS})
    assert b == pytest.approx({"compute:zip": 40 * NS, li.NO_TASK: 50 * NS})
    assert sum(a.values()) == pytest.approx(sum(b.values()))


def test_total_sums_a_category_over_its_ops():
    split = {"stage:fft": 1.0, "stage:zip": 2.0, "copy": 4.0, "compute:fft": 8.0}
    assert li.total(split, "stage", "copy") == 7.0
    assert li.total(split, "writeback") == 0.0


# -- the readers on hand-built facts --------------------------------------------


def span(cat, t0, dur, **args):
    return ("X", args.get("task", cat), cat, "track", t0, dur, args)


RING = {
    "frames": 2, "span_window_s": 10.0,
    "spans": [span("submit", 0.0, 3.0, task="a"), span("qos", 0.5, 2.0, task="a"),
              span("submit", 4.0, 1.0, task="b"), span("qos", 4.5, 0.25, task="b"),
              span("copy", 5.0, 0.5), span("copy", 6.0, 0.25),
              span("stage", 5.0, 2.0, task="a")],
}


def test_ring_readers():
    assert reader("qos_wait_share.radar").read(RING) == pytest.approx(22.5)
    assert reader("submit_us_per_task.radar").read(RING) == pytest.approx(0.875e6)
    assert reader("copy_ms_per_frame.radar").read(RING) == pytest.approx(375.0)


def test_ring_readers_find_nothing_without_the_spans():
    """A program that records neither submit nor copy spans, and the
    qos wait as an instant only: nothing to read, and no raise."""
    old = {"frames": 2, "span_window_s": 10.0,
           "spans": [span("stage", 5.0, 2.0, task="a")]}
    for name in ("qos_wait_share.radar", "submit_us_per_task.radar",
                 "copy_ms_per_frame.radar"):
        assert reader(name).read(old) is None
        assert reader(name).read({"frames": 2, "spans": None}) is None


LAYER = {
    "window_s": 10.0, "busy_s": 4.0, "idle_s": 6.0,
    "pes": {
        "gpu0": {"idle": {"stage:fft": 1.0, "copy": 0.5, "compute:fft": 2.0,
                          "writeback:fft": 0.25, li.NO_TASK: 2.25},
                 "busy": {"compute:fft": 4.0}, "idle_in_steps": {},
                 "count": {"compute:fft": 10}},
        "cpu0": {"idle": {"stage:fft": 6.0}, "busy": {}, "idle_in_steps": {},
                 "count": {"compute:fft": 10}},
    },
}
SERVE = {
    "window_s": 10.0, "busy_s": 4.0, "idle_s": 6.0,
    "pes": {"gpu0": {"idle": {"compute:llm_decode": 0.03, li.NO_TASK: 5.97},
                     "busy": {"compute:llm_prefill": 3.0, "compute:llm_decode": 1.0},
                     "idle_in_steps": {li.NO_TASK: 0.5, "stage:llm_decode": 0.1},
                     "count": {"compute:llm_decode": 6, "compute:llm_prefill": 1}}},
}


@pytest.mark.parametrize("name, facts, want", [
    ("idle_in_stage_share.radar", {"acc": "gpu0", "trace": {"rimms": LAYER}}, 15.0),
    ("idle_in_compute_share.radar", {"acc": "gpu0", "trace": {"rimms": LAYER}}, 20.0),
    ("idle_in_writeback_share.radar", {"acc": "gpu0", "trace": {"rimms": LAYER}}, 2.5),
    ("prefill_busy_share.serve", {"trace": {"rimms": SERVE}}, 75.0),
    ("idle_in_compute_ms.serve", {"trace": {"rimms": SERVE}}, 5.0),
    ("idle_outside_tasks_ms.serve", {"decode_steps": 4, "trace": {"rimms": SERVE}}, 125.0),
])
def test_layer_readers(name, facts, want):
    assert reader(name).read(facts) == pytest.approx(want)
    # the trace's reduction without the layer facts: nothing to read
    assert reader(name).read({**facts, "trace": {"busy_s": 1.0}}) is None


# -- a whole radar run on the CPU -------------------------------------------------


def test_ring_readers_on_a_traced_radar_run():
    import cells

    spec = cells.tiny("sar-mixed")
    drv_mod = run.load_module(BENCH / "drivers" / "radar.py", "bench_driver_radar")
    drv = drv_mod.Driver(spec["config"], spec["traffic"], cells.SEED, trace=True)
    drv.setup()
    drv.run_window(1.0)
    facts = drv.facts()
    drv.release()
    share = reader("qos_wait_share.radar").read(facts)
    assert 0.0 <= share < 100.0
    assert reader("submit_us_per_task.radar").read(facts) > 0.0
    # every copy the ledger counted has a copy span
    assert sum(1 for e in facts["spans"] if e[2] == "copy") == facts["copies"]
    assert reader("copy_ms_per_frame.radar").read(facts) > 0.0


# -- the trace recorded on the chip --------------------------------------------------


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(DATA))


def test_the_program_spans_are_on_the_trace(trace):
    lo, hi = next((s, e) for s, e, n in tr.host_spans(trace) if n == tr.WINDOW)
    lines = li.rimms_lines(trace, lo, hi)
    cats = {cat for evs in lines.values() for _, _, cat, _ in evs}
    assert {"submit", "qos", "stage", "copy", "compute", "writeback"} <= cats
    compute = [st for evs in lines.values() for _, _, cat, st in evs if cat == "compute"]
    assert {st["pe"] for st in compute} == {"cpu0", "gpu0"}
    assert {st["op"] for st in compute} == {"fft", "ifft", "zip"}
    # the profiler reads a stat that looks like a number as one: task "0.10" is 0.1
    assert all(isinstance(st["task"], float) for st in compute)


def test_the_accelerator_line_is_found_and_its_split_adds_up(trace):
    lf = li.layer_facts(trace)
    assert set(lf["pes"]) == {"cpu0", "gpu0"}
    gpu = lf["pes"]["gpu0"]
    assert set(gpu["count"]) >= {"compute:fft", "compute:ifft", "compute:zip"}
    assert sum(gpu["count"][k] for k in gpu["count"] if k.startswith("compute")) == 24
    r = tr.reduce_trace(trace)
    idle = r["window_s"] - r["busy_s"]
    assert lf["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert sum(gpu["idle"].values()) == pytest.approx(idle, rel=0.01)
    assert sum(gpu["busy"].values()) == pytest.approx(r["busy_s"], rel=0.01)
    # the device works only while the accelerator PE's thread is in a task
    assert gpu["busy"].get(li.NO_TASK, 0.0) < 0.5 * r["busy_s"]
