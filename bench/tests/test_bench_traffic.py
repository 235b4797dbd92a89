"""The traffic generator: deterministic from the seed, the same work for
every seed, and the mix's distributions as the traffic files state them."""

import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import gen  # noqa: E402

SHORT = json.loads((BENCH / "traffic" / "short-2tenant.json").read_text())
FRAMES = json.loads((BENCH / "traffic" / "sar-frames.json").read_text())
BIG_SEED = 2**31 + 977


def _key(reqs):
    return [(r.due_s, tuple(r.prompt), r.max_new, r.tenant, r.window) for r in reqs]


def test_requests_repeat_for_one_seed():
    a = gen.requests(SHORT, BIG_SEED, 40.0, 64000)
    b = gen.requests(SHORT, BIG_SEED, 40.0, 64000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(gen.requests(SHORT, BIG_SEED + 1, 40.0, 64000))


def test_every_seed_gets_the_same_schedule():
    def schedule(seed):
        return [(r.due_s, len(r.prompt), r.max_new, r.tenant, r.window)
                for r in gen.requests(SHORT, seed, 40.0, 64000)]

    assert schedule(1) == schedule(BIG_SEED) == schedule(2**40 + 3)
    other = dict(SHORT, schedule_seed=SHORT["schedule_seed"] + 1)
    moved = [(r.due_s, len(r.prompt)) for r in gen.requests(other, 1, 40.0, 64000)]
    assert moved != [s[:2] for s in schedule(1)]
    assert sorted(s[1] for s in moved) == sorted(s[1] for s in schedule(1))


def test_window_holds_rate_times_seconds():
    reqs = gen.requests(SHORT, 5, 40.0, 64000)
    inside = [r for r in reqs if r.window]
    assert len(inside) == round(SHORT["rate_per_s"] * 40.0)
    assert all(0.0 <= r.due_s < 40.0 for r in inside)
    assert all(r.due_s >= 40.0 for r in reqs if not r.window)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)


def test_lengths_follow_the_mix():
    reqs = gen.requests(SHORT, 9, 400.0, 64000)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    spec_p, spec_o = SHORT["prompt_tokens"], SHORT["output_tokens"]
    assert spec_p["min"] <= p.min() and p.max() <= spec_p["max"]
    assert spec_o["min"] <= o.min() and o.max() <= spec_o["max"]
    assert abs(np.median(p) - spec_p["median"]) <= 2
    assert abs(np.median(o) - spec_o["median"]) <= 2
    share = np.mean([r.tenant == "a" for r in reqs])
    assert abs(share - 0.75) < 0.01
    ids = np.concatenate([r.prompt for r in reqs])
    assert 0 <= ids.min() and ids.max() < 64000


def test_quantiles_are_stratified():
    q = gen.quantiles({"dist": "lognormal", "median": 100, "sigma": 0.5,
                       "min": 1, "max": 10**6}, 3)
    z = 0.967421566101701  # standard normal quantile at 5/6
    assert list(q) == [round(100 * np.exp(-0.5 * z)), 100, round(100 * np.exp(0.5 * z))]


def test_frames_repeat_for_one_seed():
    a = gen.frames(FRAMES, BIG_SEED, [(8,), (4,)])
    b = gen.frames(FRAMES, BIG_SEED, [(8,), (4,)])
    c = gen.frames(FRAMES, BIG_SEED + 1, [(8,), (4,)])
    assert len(a) == FRAMES["distinct_frames"]
    assert all(np.array_equal(x, y) for fa, fb in zip(a, b) for x, y in zip(fa, fb))
    assert not np.array_equal(a[0][0], c[0][0])
    assert a[0][0].dtype == np.complex64 and a[0][1].shape == (4,)
