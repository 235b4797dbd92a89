"""The trace reduction on a small trace recorded on a TPU v5 lite: one
SAR frame cut to 48 tasks, under a ``bench.window`` annotation."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

DATA = BENCH / "tests" / "data" / "sar_small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(DATA))


def test_finds_the_one_tpu_and_its_operations(trace):
    ops = tr.device_ops(trace)
    assert list(ops) == ["/device:TPU:0"]
    evs = ops["/device:TPU:0"]
    assert len(evs) == 198
    assert {name.split("/")[0] for _, _, name in evs} == {"jit__jfft", "jit__jifft", "jit__jzip"}
    assert all(e > s for s, e, _ in evs)


def test_harness_spans(trace):
    names = [name for _, _, name in tr.host_spans(trace)]
    assert names == ["bench.window", "bench.build", "bench.submit", "bench.readback"]


def test_reduction_adds_up(trace):
    r = tr.reduce_trace(trace)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.045737066)
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = sum(v for _, v in r["idle_gaps"])
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"], rel=1e-9)
    assert r["idle_gaps"][0][0] == "bench.readback"
    assert r["device_ops"][0][0].startswith("jit__jfft/%fusion")
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] + 1e-12


def test_union_merges_and_clips():
    assert tr.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [(1, 4), (5, 10)]
    assert tr.union([(0, 1)], 2, 3) == []


def test_idle_gaps_go_to_the_innermost_span():
    label = tr._Labeller([(0, 100, "outer"), (10, 20, "a"), (30, 60, "b"),
                          (40, 50, "c"), (200, 210, "d")])
    assert [label(t) for t in (5, 15, 35, 45, 55, 70, 150, 205, 300)] == [
        "outer", "a", "b", "c", "b", "outer", "outside harness spans", "d",
        "outside harness spans"]
