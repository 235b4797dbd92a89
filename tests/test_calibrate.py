"""Measured calibration + autotuning (ISSUE 10): table persistence and
merge, CostModel integration, deterministic variant dispatch, tuned
Pallas variants' bit-identity, and the process-backend calibration path
with cross-process metric drain."""

import json

import numpy as np
import pytest

import repro.apps.elemwise as elemwise
from repro.core.api import OpRegistry, Session
from repro.core.calibrate import (
    DEFAULT_VARIANT, FORMAT, CalibrationTable, calibrate,
    resolve_calibration,
)
from repro.core.graph import CostModel


# module-level kernels: the process backend ships fns by pickle
# reference, and the registry rejects closures changing between variants
def _double(ins):
    return np.asarray(ins[0]) * 2.0


def _double_alt(ins):
    return (np.asarray(ins[0]) * 2.0) + 0.0


def _make_f64(rng, nbytes):
    return [rng.standard_normal(max(nbytes // 8, 1))]


# ---------------------------------------------------------------------------
# CalibrationTable persistence + merge
# ---------------------------------------------------------------------------


def test_table_save_load_roundtrip(tmp_path):
    t = CalibrationTable()
    t.record("fft", "default", "cpu", 1 << 20, 1e-3)
    t.record("fft", "block64", "cpu", 1 << 20, 5e-4, identical=True)
    t.set_winner("fft", "cpu", 1 << 20, "block64", speedup=2.0,
                 median_s=5e-4)
    t.meta["host"] = "testbox"
    t.divergence = {"cells": {}}
    path = tmp_path / "calib.json"
    t.save(str(path))

    doc = json.loads(path.read_text())
    assert doc["format"] == FORMAT

    back = CalibrationTable.load(str(path))
    assert back.state() == t.state()
    assert back.best_variant("fft", "cpu", 1 << 20) == "block64"
    assert back.meta["host"] == "testbox"
    assert back.divergence == {"cells": {}}


def test_table_load_rejects_unknown_format(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"format": "rimms-calib-v999"}))
    with pytest.raises(ValueError, match="format"):
        CalibrationTable.load(str(path))


def test_table_merge_count_weights_cells_and_keeps_best_winner():
    a = CalibrationTable()
    b = CalibrationTable()
    a.record("zip", "default", "cpu", 4096, 1e-3)
    b.record("zip", "default", "cpu", 4096, 3e-3)
    a.set_winner("zip", "cpu", 4096, "default", speedup=1.0, median_s=1e-3)
    b.set_winner("zip", "cpu", 4096, "fast", speedup=1.5, median_s=2e-3)
    a.merge(b)
    cell = a.cell("zip", "cpu", 4096)
    assert cell["count"] == 2
    assert abs(cell["median_s"] - 2e-3) < 1e-12  # count-weighted mean
    # b's winner is SLOWER (2e-3 > 1e-3): the existing winner stays
    assert a.winner("zip", "cpu", 4096)["variant"] == "default"

    c = CalibrationTable()
    c.set_winner("zip", "cpu", 4096, "fast", speedup=4.0, median_s=25e-5)
    a.merge(c.state())  # merge accepts a raw state dict too
    assert a.winner("zip", "cpu", 4096)["variant"] == "fast"


def test_resolve_calibration_forms(tmp_path, monkeypatch):
    assert resolve_calibration(None) is None
    t = CalibrationTable()
    assert resolve_calibration(t) is t
    path = tmp_path / "c.json"
    t.record("fft", "default", "cpu", 1024, 1e-4)
    t.save(str(path))
    assert len(resolve_calibration(str(path))) == 1
    # "auto": empty table when the env var points nowhere...
    monkeypatch.delenv("RIMMS_CALIBRATION", raising=False)
    assert len(resolve_calibration("auto")) == 0
    # ...and the file's contents when it does
    monkeypatch.setenv("RIMMS_CALIBRATION", str(path))
    assert len(resolve_calibration("auto")) == 1


# ---------------------------------------------------------------------------
# CostModel integration
# ---------------------------------------------------------------------------


def test_cost_model_uses_measured_cell_and_falls_back_on_missing():
    t = CalibrationTable()
    nb = 1 << 20
    t.record("fft", "default", "gpu", nb, 2e-3)
    cm = CostModel(calibration=t)
    # measured bucket: linear interpolation off the measured cell
    measured = cm.prior_estimate("fft", "gpu", nb)
    assert abs(measured - 2e-3) < 1e-9
    # missing bucket (different size class) → the historical prior
    prior = CostModel().prior_estimate("fft", "gpu", 1 << 10)
    assert cm.prior_estimate("fft", "gpu", 1 << 10) == prior
    # missing kind → prior as well
    assert (cm.prior_estimate("fft", "cpu", nb)
            == CostModel().prior_estimate("fft", "cpu", nb))
    # detach restores the prior everywhere
    cm.set_calibration(None)
    assert cm.prior_estimate("fft", "gpu", nb) == CostModel().prior_estimate(
        "fft", "gpu", nb)


# ---------------------------------------------------------------------------
# deterministic variant dispatch from a fixed table
# ---------------------------------------------------------------------------


def _variant_session(table):
    reg = OpRegistry()
    reg.register("double", "cpu", _double, calib=_make_f64)
    reg.register("double", "cpu", _double_alt, variant="alt")
    return Session.emulated(n_cpu=1, accelerators=(), registry=reg,
                            calibration=table)


def test_runtime_dispatches_winner_variant_from_fixed_table():
    n = 1024  # float64 → 8 KiB bucket
    table = CalibrationTable()
    table.record("double", "default", "cpu", 8 * n, 1e-3)
    table.record("double", "alt", "cpu", 8 * n, 5e-4, identical=True)
    table.set_winner("double", "cpu", 8 * n, "alt", speedup=2.0,
                     median_s=5e-4)
    session = _variant_session(table)
    try:
        x = np.arange(n, dtype=np.float64)
        out = session.submit("double", [x]).result(timeout=60)
        session.barrier()
        assert [v for (o, _k, v) in session.runtime.variant_log
                if o == "double"] == ["alt"]
        np.testing.assert_array_equal(np.asarray(out), x * 2.0)
    finally:
        session.close()


def test_runtime_default_dispatch_without_table_or_winner():
    # no calibration attached → default variant, nothing logged
    session = _variant_session(None)
    try:
        x = np.arange(1024, dtype=np.float64)
        session.submit("double", [x]).result(timeout=60)
        session.barrier()
        assert session.runtime.variant_log == []
    finally:
        session.close()
    # table attached but winner at a DIFFERENT bucket → default path
    table = CalibrationTable()
    table.set_winner("double", "cpu", 1 << 20, "alt", speedup=2.0,
                     median_s=1e-4)
    session = _variant_session(table)
    try:
        x = np.arange(1024, dtype=np.float64)
        out = session.submit("double", [x]).result(timeout=60)
        session.barrier()
        # the winner lives at a different bucket: default path, no log
        assert session.runtime.variant_log == []
        np.testing.assert_array_equal(np.asarray(out), x * 2.0)
    finally:
        session.close()


def test_registry_select_consults_table():
    reg = OpRegistry()
    reg.register("double", "cpu", _double)
    reg.register("double", "cpu", _double_alt, variant="alt")
    assert reg.select("double", "cpu", 8192).fn is _double
    table = CalibrationTable()
    table.set_winner("double", "cpu", 8192, "alt", speedup=2.0,
                     median_s=1e-4)
    assert reg.select("double", "cpu", 8192, table=table).fn is _double_alt
    # winner naming an unregistered variant falls back to the default
    table2 = CalibrationTable()
    table2.set_winner("double", "cpu", 8192, "gone", speedup=2.0,
                      median_s=1e-4)
    assert reg.select("double", "cpu", 8192, table=table2).fn is _double


# ---------------------------------------------------------------------------
# session calibration lifecycle
# ---------------------------------------------------------------------------


def test_session_calibrate_then_save_embeds_divergence(tmp_path):
    reg = OpRegistry()
    reg.register("double", "cpu", _double, calib=_make_f64)
    reg.register("double", "cpu", _double_alt, variant="alt")
    session = Session.emulated(n_cpu=1, accelerators=(), registry=reg)
    try:
        table = session.calibrate(ops=["double"], nbytes=[8192], k=2,
                                  warmup=1)
        assert session.calibration is table
        assert session.runtime.calibration is table
        # both variants measured, non-default verified bit-identical
        assert table.cell("double", "cpu", 8192)["count"] == 1
        alt = table.cell("double", "cpu", 8192, variant="alt")
        assert alt["identical"] is True
        assert table.winner("double", "cpu", 8192)["speedup"] >= 1.0
        # run something so the divergence monitor has cells to embed
        session.submit("double", [np.arange(64, dtype=np.float64)]
                       ).result(timeout=60)
        session.barrier()
        path = tmp_path / "calib.json"
        session.save_calibration(str(path))
    finally:
        session.close()
    back = CalibrationTable.load(str(path))
    assert back.divergence is not None
    # a new session picks the snapshot up into its live monitor
    s2 = Session.emulated(n_cpu=1, accelerators=(), registry=reg,
                          calibration=str(path))
    try:
        assert s2.runtime.divergence.table() != {}
    finally:
        s2.close()


def test_calibrate_skips_ops_without_input_factory():
    reg = OpRegistry()
    reg.register("double", "cpu", _double)  # no calib= factory
    session = Session.emulated(n_cpu=1, accelerators=(), registry=reg)
    try:
        table = calibrate(session, nbytes=[4096], k=1, warmup=1)
    finally:
        session.close()
    assert len(table) == 0
    assert "double" in table.meta["skipped_ops"]


# ---------------------------------------------------------------------------
# tuned Pallas variants: bit-identity of every candidate vs the default
# ---------------------------------------------------------------------------


def test_tuned_variant_candidates_bit_identical_to_default():
    from repro.core.autotune import tunables

    rng = np.random.default_rng(7)
    nb = 32 << 10
    for tun in tunables():
        if not tun.bit_identical:
            continue
        ins = [np.asarray(a) for a in tun.make_inputs(rng, nb)]
        ref = tun.fn(ins, **{tun.param: tun.default})
        for value in tun.candidates:
            outs = tun.fn(ins, **{tun.param: value})
            assert len(outs) == len(ref), tun.op
            for a, b in zip(outs, ref):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), (
                    f"{tun.op}: {tun.param}={value} is not bit-identical "
                    f"to the default {tun.default}"
                )


@pytest.mark.parametrize("platform, kinds", [
    ("cpu", {"cpu", "gpu", "acc"}),
    ("tpu", {"gpu", "acc"}),  # a cpu PE must not time the chip
])
def test_tunables_register_for_the_platforms_kinds(monkeypatch, platform,
                                                   kinds):
    import jax

    from repro.core.autotune import register_tunables

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    reg = OpRegistry()
    for op_name in register_tunables(reg):
        assert set(reg.kinds(op_name)) == kinds, op_name


def test_autotune_registers_variants_and_attaches_table():
    from repro.core.autotune import autotune, register_tunables

    reg = OpRegistry()
    ops = register_tunables(reg)
    assert set(ops) == {"fft_pallas", "zip_pallas", "flash_attention",
                        "mlstm", "rg_lru"}
    assert len(reg.variants("fft_pallas", "cpu")) == 3
    assert reg.variants("fft_pallas", "cpu")[0] == DEFAULT_VARIANT
    # double registration is idempotent only with replace
    with pytest.raises(ValueError, match="already registered"):
        reg.register("fft_pallas", "cpu", _double)
    register_tunables(reg)  # same fns → no-op, no raise

    session = Session.emulated(n_cpu=1, accelerators=(), registry=reg)
    try:
        table = autotune(session, nbytes=[16 << 10], k=1, warmup=1)
        assert session.runtime.calibration is table
        # every tuned op measured on the cpu kind
        measured = {key.split("/")[0] for key, _ in table.cells()}
        assert set(ops) <= measured
        # mlstm's chunk candidates change accumulation order: they must
        # be recorded as NOT identical, so the default always wins
        alts = [c for key, c in table.cells()
                if key.startswith("mlstm/chunk32/cpu/")]
        assert alts and all(c["identical"] is False for c in alts)
        win = [w for key, w in table.winners()
               if key.startswith("mlstm/cpu/")]
        assert win and all(w["variant"] == DEFAULT_VARIANT for w in win)
    finally:
        session.close()


# ---------------------------------------------------------------------------
# process backend: worker-side measurement + cross-process metric drain
# ---------------------------------------------------------------------------


def test_calibrate_process_backend_roundtrip_and_metric_drain(tmp_path):
    reg = OpRegistry()
    reg.register("scale", "gpu", elemwise.scale, calib=_make_f64)
    # same module-level fn, same params → bit-identical by construction
    reg.register("scale", "gpu", elemwise.scale, variant="alt",
                 params={"factor": 2.0})
    session = Session.emulated(n_cpu=0, accelerators=("gpu0",),
                               registry=reg, backend="process")
    try:
        table = session.calibrate(ops=["scale"], nbytes=[8192], k=2,
                                  warmup=1)
        assert table.meta["backend"] == "process"
        cell = table.cell("scale", "gpu", 8192)
        assert cell is not None and cell["median_s"] > 0
        alt = table.cell("scale", "gpu", 8192, variant="alt")
        assert alt["identical"] is True
        assert table.winner("scale", "gpu", 8192)["speedup"] >= 1.0
        path = tmp_path / "proc.json"
        session.save_calibration(str(path))
    finally:
        session.close()
        session.runtime.close()
    # the calibration runs executed in the PE's subprocess worker; its
    # locally accumulated metrics must drain into the session registry
    tasks = session.metrics.counter("worker/gpu0/tasks").value
    assert tasks > 0
    back = CalibrationTable.load(str(path))
    assert back.state()["cells"] == table.state()["cells"]
