"""Share of the window in which the accelerator PE has no task span
(stage, compute or write-back) open: time it waits for the scheduler
and the host."""

import trace_reduce


def read(f):
    spans = f.get("spans")
    if not spans:
        return None
    track = f"pe:{f['acc']}"
    ivs = [(e[4], e[4] + e[5]) for e in spans if e[3] in (track, track + ":stage")]
    if not ivs:
        return None
    ns = [(int(s * 1e9), int(e * 1e9)) for s, e in ivs]
    lo, hi = min(s for s, _ in ns), max(e for _, e in ns)
    busy = sum(e - s for s, e in trace_reduce.union(ns, lo, hi)) * 1e-9
    return 100.0 * (1.0 - busy / f["span_window_s"])
