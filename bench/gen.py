"""The one traffic generator: turns a mix file of ``bench/traffic`` and a
seed into the inputs a cell's driver feeds the system.

Every seed gets the same work.  Sizes and gaps are fixed quantiles of
the mix's distributions, put in an order drawn from the mix's own
``schedule_seed``; the run's seed draws the payload (token ids,
samples).  So two seeds differ in what they ask, not in how much or
when: a tail over a few dozen requests then moves with the system, not
with the draw.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

__all__ = ["Request", "frames", "requests", "quantiles"]


def _rng(seed: int, stream: int) -> np.random.Generator:
    # numpy takes seeds of any size; the stream keeps draws independent
    return np.random.default_rng([int(seed), stream])


def quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles (at (i + 0.5) / n) of a length
    distribution ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
    rounded to whole tokens and clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = np.round(spec["median"] * np.exp(spec["sigma"] * np.asarray(z)))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def frames(traffic: Dict, seed: int, shapes: List[tuple]) -> List[List[np.ndarray]]:
    """``distinct_frames`` input frames; each is one complex64 array per
    entry of ``shapes``, standard complex normal, from the seed."""
    rng = _rng(seed, 0)
    out = []
    for _ in range(traffic["distinct_frames"]):
        frame = []
        for shape in shapes:
            re = rng.standard_normal(shape, dtype=np.float32)
            im = rng.standard_normal(shape, dtype=np.float32)
            frame.append((re + 1j * im).astype(np.complex64))
        out.append(frame)
    return out


@dataclasses.dataclass
class Request:
    due_s: float
    prompt: List[int]
    max_new: int
    tenant: str
    window: bool  # due inside the measured window


def _arrivals(n: int, span: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` due times in ``[0, span)`` with exponential gaps: the gaps are
    the exponential's stratified quantiles in a seeded order, scaled so
    that the mean rate is exactly ``n / span``."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = rng.permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return t * (span * (n - 0.5) / n) / max(t[-1], 1e-12) if n > 1 else t


def requests(traffic: Dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """Open-loop requests for a window of ``seconds`` plus the drain that
    follows it.  The window holds ``round(rate * seconds)`` requests; the
    drain holds as many again at the same rate, so load goes on while
    the window's last requests finish (those are never measured)."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    rng = _rng(traffic["schedule_seed"], 1)
    ids_rng = _rng(seed, 1)
    out: List[Request] = []
    for part, t_off in ((True, 0.0), (False, seconds)):
        span = n / rate
        due = _arrivals(n, span, rng) + t_off
        prompts = rng.permutation(quantiles(traffic["prompt_tokens"], n))
        outputs = rng.permutation(quantiles(traffic["output_tokens"], n))
        names = list(traffic["tenants"])
        weights = np.array([traffic["tenants"][k] for k in names], float)
        counts = np.floor(n * weights / weights.sum()).astype(int)
        counts[0] += n - counts.sum()
        tenants = rng.permutation(np.repeat(np.arange(len(names)), counts))
        for i in range(n):
            ids = ids_rng.integers(0, vocab, int(prompts[i]))
            out.append(Request(float(due[i]), [int(x) for x in ids],
                               int(outputs[i]), names[tenants[i]], part))
    out.sort(key=lambda r: r.due_s)
    return out
