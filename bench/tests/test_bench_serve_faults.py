"""Whole serving runs on the CPU at a small size, past the harness's look
for a chip: a sound run is correct, a run whose timed path is broken
underneath is not, and the float8 control fails the limit."""

import numpy as np
import pytest

from cells import SEED, tiny, measure


def test_sound_serving_run_is_correct():
    res = measure("yi9b-short")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert res["checks"]["mean_logit_gap"]["value"] <= res["checks"]["mean_logit_gap"]["limit"]


def _patch_substep(monkeypatch, fn):
    from repro.serve.session_engine import SessionServeEngine

    real = SessionServeEngine._decode_substep
    calls = {"n": 0}

    def patched(self, mask, client):
        calls["n"] += 1
        return fn(self, real, np.asarray(mask, bool), client, calls["n"])

    monkeypatch.setattr(SessionServeEngine, "_decode_substep", patched)
    return calls


def test_serving_token_altered_where_produced(monkeypatch):
    def altered(eng, real, mask, client, n):
        out = np.array(real(eng, mask, client))
        if n % 5 == 0:
            out[mask] = (out[mask] + 7) % eng.cfg.vocab
        return out

    calls = _patch_substep(monkeypatch, altered)
    res = measure("yi9b-short")
    assert calls["n"] >= 5
    assert not res["correct"]


def test_serving_half_the_batch_left_out(monkeypatch):
    """Each decode sub-step computes the first half of its slots; the
    rest are handed back their last token."""
    def half(eng, real, mask, client, n):
        idx = np.flatnonzero(mask)
        kept = mask.copy()
        kept[idx[(len(idx) + 1) // 2:]] = False
        out = np.array(real(eng, kept, client))
        out[mask & ~kept] = eng.slot_tok[mask & ~kept]
        return out

    calls = _patch_substep(monkeypatch, half)
    res = measure("yi9b-short")
    assert calls["n"] > 0
    assert not res["correct"]


def test_serving_step_returns_its_state_unchanged(monkeypatch):
    """The step program hands back the KV page groups it was given, so
    neither prefill nor decode leaves anything in the cache."""
    from repro.serve import session_engine

    real = session_engine._jit_grouped_step

    def patched(cfg, n_groups):
        step = real(cfg, n_groups)

        def unchanged(params, k_groups, v_groups, *rest):
            nxt, _, _ = step(params, k_groups, v_groups, *rest)
            return nxt, k_groups, v_groups

        return unchanged

    monkeypatch.setattr(session_engine, "_jit_grouped_step", patched)
    res = measure("yi9b-short")
    assert not res["correct"]


@pytest.mark.parametrize("seed", [SEED + 1, SEED + 2, SEED + 3])
def test_serving_control_fails_the_limit(seed):
    """The float8 control in the program's place comes out not correct by
    the benchmark's own comparison, on the tokens that a sound run
    served; the program comes out correct."""
    import run
    from reference.decoder import served_gaps
    from serve import Driver, judge

    spec = tiny("yi9b-short")
    drv = Driver(spec["config"], spec["traffic"], seed, trace=False)
    drv.setup()
    drv.run_window(3.0)
    drv.release()
    ref, ctl = drv.reference(), drv.reference("fp8")
    program, control = [], []
    for t in drv.sample():
        program += served_gaps(ref, t.req.prompt, t.handle.generated)
        control += served_gaps(ref, t.req.prompt, t.handle.generated, control=ctl)
    assert run.passes(judge(program)), judge(program)
    assert not run.passes(judge(control)), judge(control)


@pytest.mark.parametrize("gaps, correct", [
    ([], False),                        # nothing finished vouches for nothing
    ([0.0] * 300, True),
    ([0.0] * 295 + [0.01] * 5, True),   # a few near-ties flipped, as bfloat16 does
    ([0.0] * 270 + [0.02] * 30, False),  # one token in ten flipped, as float8 does
], ids=["empty", "exact", "near-ties", "control-like"])
def test_serving_judge_reads_the_mean_gap(gaps, correct):
    import run
    from serve import judge

    assert run.passes(judge(gaps)) is correct
