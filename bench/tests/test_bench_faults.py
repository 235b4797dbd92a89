"""Whole radar runs on the CPU at a small size, past the harness's look
for a chip: a sound run is correct, and a run whose timed path is broken
underneath is not."""

from cells import measure


def test_sound_radar_run_is_correct():
    res = measure("sar-mixed")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res["checks"]) == ["max_rel_err"]
    assert res["metrics"]["frames_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_radar_answer_altered_where_produced(monkeypatch):
    from repro.apps import radar

    real = radar._jifft
    calls = {"n": 0}

    def altered(x):
        calls["n"] += 1
        out = real(x)
        return out.at[3].add(0.5) if calls["n"] == 40 else out

    monkeypatch.setattr(radar, "_jifft", altered)
    res = measure("sar-mixed")
    assert calls["n"] > 40
    assert not res["correct"] and res["failed"] >= 1


def test_radar_copy_to_the_host_left_out(monkeypatch):
    """The coherence layer hands a stale host copy to a reader now and
    then, instead of copying the device's bytes back."""
    from repro.core.hete import HeteContext
    from repro.core.locations import HOST

    real = HeteContext.stage
    calls = {"n": 0}

    def stale(self, hd, dst):
        if (dst == HOST and hd.last_location != HOST and HOST not in hd.valid_at
                and hd.copies.get(HOST) is not None):
            calls["n"] += 1
            if calls["n"] % 7 == 0:
                return hd.copies[HOST], 0.0
        return real(self, hd, dst)

    monkeypatch.setattr(HeteContext, "stage", stale)
    res = measure("sar-mixed")
    assert calls["n"] >= 7
    assert not res["correct"] and res["failed"] >= 1


def test_radar_half_the_tasks_left_out(monkeypatch):
    """Every second task the runtime dispatches passes its input through
    instead of running its kernel."""
    from repro.core.runtime import Runtime

    real = Runtime._run_kernel
    calls = {"n": 0}

    def half(self, task, pe, ins):
        calls["n"] += 1
        if calls["n"] % 2 == 0:
            return (ins[0],), 0.0
        return real(self, task, pe, ins)

    monkeypatch.setattr(Runtime, "_run_kernel", half)
    res = measure("sar-mixed")
    assert calls["n"] > 2
    assert not res["correct"] and res["failed"] >= 1
