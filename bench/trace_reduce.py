"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark
reports: device busy time, time per device operation, and idle gaps
named by what the harness was doing on the host meanwhile.

Device operations are the events of each device plane's ``XLA Ops``
line.  Busy time is the union of their intervals inside the window,
which the harness marks with a ``bench.window`` annotation; harness
calls carry ``bench.*`` annotations on the host planes, on the same
clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

__all__ = ["find_trace", "load", "device_ops", "host_spans", "union",
           "reduce_trace"]

WINDOW = "bench.window"


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _is_device(plane) -> bool:
    """A device that runs XLA programs: its plane has an ``XLA Ops`` line."""
    return plane.name.startswith("/device:") and any(
        ln.name == "XLA Ops" for ln in plane.lines)


def _short(op: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return op.split(" = ", 1)[0]


def device_ops(pd) -> Dict[str, List[Tuple[int, int, str]]]:
    """plane name -> [(start ns, end ns, "module/op")] of every operation
    on each device, named by the program (XLA module, without its
    fingerprint) that ran it."""
    out = {}
    for plane in pd.planes:
        if not _is_device(plane):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                       e.name.split("(", 1)[0]) for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        evs = []
        for e in lines["XLA Ops"]:
            start = int(e.start_ns)
            i = bisect.bisect_right(starts, start) - 1
            mod = mods[i][2] if i >= 0 and start < mods[i][1] else "?"
            evs.append((start, start + int(e.duration_ns), f"{mod}/{_short(e.name)}"))
        out[plane.name] = evs
    return out


def host_spans(pd, prefix: str = "bench.") -> List[Tuple[int, int, str]]:
    """[(start ns, end ns, name)] of host events whose name has ``prefix``."""
    out = []
    for plane in pd.planes:
        if _is_device(plane):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    start = int(e.start_ns)
                    out.append((start, start + int(e.duration_ns), e.name))
    return out


def union(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged intervals, clipped to [lo, hi)."""
    merged: List[Tuple[int, int]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


class _Labeller:
    """The innermost harness span around a time.  Spans of one thread
    nest, so among the spans that hold ``t`` the innermost is the one
    that starts last; a running maximum of the ends stops the search
    where no earlier span reaches ``t``."""

    def __init__(self, spans):
        # a span that starts with another and ends sooner lies inside it
        self.spans = sorted(spans, key=lambda x: (x[0], -x[1]))
        self.starts = [s for s, _, _ in self.spans]
        self.reach = []
        top = None
        for _, e, _ in self.spans:
            top = e if top is None else max(top, e)
            self.reach.append(top)

    def __call__(self, t: int) -> str:
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.reach[j] > t:
            s, e, name = self.spans[j]
            if t < e:
                return name
            j -= 1
        return "outside harness spans"


def reduce_trace(pd, *, top: int = 10) -> Dict:
    """busy_s (mean over devices), window_s, device_ops and idle_gaps
    (each the ``top`` largest, in seconds) for the ``bench.window`` span."""
    spans = host_spans(pd)
    windows = [s for s in spans if s[2] == WINDOW]
    if not windows:
        raise ValueError("trace has no bench.window span")
    lo, hi = windows[0][0], windows[0][1]
    label = _Labeller([s for s in spans if s[2] != WINDOW])
    devices = device_ops(pd)
    if not devices:
        raise ValueError("trace has no device plane")
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    busy = []
    for evs in devices.values():
        for s, e, name in evs:
            cut = min(e, hi) - max(s, lo)
            if cut > 0:
                op_time[name] += cut * 1e-9
        merged = union(evs, lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gap_time[label((s + e) // 2)] += (e - s) * 1e-9
    rank = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy), "window_s": (hi - lo) * 1e-9,
            "devices": len(devices),
            "device_ops": [[k, v] for k, v in rank],
            "idle_gaps": [[k, v] for k, v in gaps]}
