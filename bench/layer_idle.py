"""Split the device's idle time by the runtime layer that held each PE's
thread, from the program's own ``rimms.*`` spans in a profiler trace.

The program wraps each phase of its work in a profiler annotation named
``rimms.<category>`` (submit, qos, stage, copy, compute, writeback, step,
admit) whose stats name the task, its op and its PE; they land on the
host line of the thread that ran them, on the device's clock.  A PE's
thread is the host line whose ``compute`` spans carry its ``pe`` stat
(prefetch staging for it runs on other threads).  For each PE, :func:`layer_facts` reports the device's idle time in the
``bench.window`` span split by the innermost span open on that thread,
the device's busy time split the same way, and the time the device is
idle inside an engine step (``rimms.step``, on any thread) split by the
PE's innermost span.  The per-layer readers ``idle_in_*``,
``prefill_busy_share.serve`` and ``idle_outside_tasks_ms.serve`` read
these facts under ``trace["rimms"]``.
"""

from __future__ import annotations

import bisect
from collections import Counter, defaultdict
from typing import Dict, List, Tuple
from urllib.parse import unquote

import trace_reduce

__all__ = ["NO_TASK", "rimms_lines", "gaps", "intersect", "split", "split_idle",
           "layer_facts"]

PREFIX = "rimms."
#: the label of time in which the thread has no span open
NO_TASK = "no task"
#: the phases a PE's thread runs for each task, labelled with the task's op
PHASES = ("stage", "compute", "writeback")


def _label(cat: str, stats: Dict) -> str:
    """``compute:llm_decode`` for a task's phase, the category for the rest."""
    return f"{cat}:{stats.get('op')}" if cat in PHASES else cat


def rimms_lines(pd, lo: int, hi: int) -> Dict[str, List[Tuple[int, int, str, Dict]]]:
    """Host line -> [(start ns, end ns, category, stats)] of the
    ``rimms.*`` events that overlap [lo, hi), in start order.  String
    stats come back decoded (the program percent-encodes ``%``, ``#``
    and ``,``)."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for k, ln in enumerate(plane.lines):
            evs = []
            for e in ln.events:
                if not e.name.startswith(PREFIX):
                    continue
                s = int(e.start_ns)
                t = s + int(e.duration_ns)
                if t <= lo or s >= hi:
                    continue
                stats = {key: unquote(v) if isinstance(v, str) else v
                         for key, v in e.stats}
                evs.append((s, t, e.name[len(PREFIX):], stats))
            if evs:
                out[f"{plane.name}#{k}"] = sorted(evs, key=lambda x: (x[0], -x[1]))
    return out


def gaps(busy, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The parts of [lo, hi) that the merged intervals ``busy`` leave free."""
    edges = [lo] + [x for iv in trace_reduce.union(busy, lo, hi) for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def intersect(a, b) -> List[Tuple[int, int]]:
    """The intersection of two lists of intervals (each merged first)."""
    lo = min([s for s, *_ in a] + [s for s, *_ in b], default=0)
    hi = max([e for _, e, *_ in a] + [e for _, e, *_ in b], default=0)
    a, b = trace_reduce.union(a, lo, hi), trace_reduce.union(b, lo, hi)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def split(spans, intervals) -> Dict[str, float]:
    """Seconds of ``intervals`` under each label of one thread's ``spans``
    ([(start, end, label)], nested), by the innermost span open; time
    with no span open is :data:`NO_TASK`."""
    label = trace_reduce._Labeller(spans)
    cuts = sorted({x for s, e, _ in spans for x in (s, e)})
    out: Dict[str, float] = defaultdict(float)
    for s, e in intervals:
        edges = [s] + cuts[bisect.bisect_right(cuts, s):bisect.bisect_left(cuts, e)] + [e]
        for a, b in zip(edges, edges[1:]):
            name = label((a + b) // 2)
            out[NO_TASK if name == "outside harness spans" else name] += (b - a) * 1e-9
    return dict(out)


def split_idle(spans, busy, lo: int, hi: int) -> Dict[str, float]:
    """Seconds of [lo, hi) in which the device is idle (``busy`` holds
    its operations), by the innermost of ``spans`` open meanwhile."""
    return split(spans, gaps(busy, lo, hi))


def layer_facts(pd) -> Dict:
    """What the layer readers read: for each PE, the device's idle and
    busy seconds in the window by the PE thread's innermost span (labels ``stage:<op>``, ``copy``, ``compute:<op>``,
    ``writeback:<op>``, ``no task``), its idle seconds inside engine
    steps split the same way, and the count of each label's spans."""
    windows = [s for s in trace_reduce.host_spans(pd) if s[2] == trace_reduce.WINDOW]
    if not windows:
        raise ValueError("trace has no bench.window span")
    lo, hi = windows[0][0], windows[0][1]
    busy = trace_reduce.union([iv for evs in trace_reduce.device_ops(pd).values()
                               for iv in evs], lo, hi)
    idle = gaps(busy, lo, hi)
    lines = rimms_lines(pd, lo, hi)
    steps = [(s, e) for evs in lines.values() for s, e, cat, _ in evs if cat == "step"]
    idle_in_steps = intersect(idle, steps)
    pes: Dict[str, Dict] = {}
    for evs in lines.values():
        named = Counter(st["pe"] for _, _, cat, st in evs if cat == "compute")
        if not named:
            continue
        pe = named.most_common(1)[0][0]
        spans = [(s, e, _label(cat, st)) for s, e, cat, st in evs]
        pes[pe] = {
            "idle": split(spans, idle),
            "busy": split(spans, busy),
            "idle_in_steps": split(spans, idle_in_steps),
            "count": dict(Counter(lab for s, e, lab in spans if lo <= s and e <= hi)),
        }
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "idle_s": sum(e - s for s, e in idle) * 1e-9,
            "pes": pes}


# -- for the readers -----------------------------------------------------------


def of(f) -> Dict:
    """The layer facts in a run's facts, or None where the trace's
    reduction does not hold them."""
    return (f.get("trace") or {}).get("rimms")


def serving_pe(lf) -> Dict:
    """The facts of the PE that ran the decode steps, or None."""
    return next((p for p in lf["pes"].values() if "compute:llm_decode" in p["count"]),
                None)


def total(split_s: Dict[str, float], *cats: str) -> float:
    """Seconds under the labels of ``cats`` (``stage`` takes every ``stage:<op>``)."""
    return sum(v for k, v in split_s.items() if k.split(":", 1)[0] in cats)
