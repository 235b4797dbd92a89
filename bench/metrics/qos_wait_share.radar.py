"""Share of the window in which the submitting thread waits in the
runtime's QoS admission (the program's ``qos`` spans, ``rimms.qos`` in a
profiler trace): backpressure while the client's in-flight window is
full."""

import trace_reduce


def read(f):
    spans = f.get("spans")
    if not spans or not f.get("span_window_s"):
        return None
    ns = [(int(e[4] * 1e9), int((e[4] + e[5]) * 1e9)) for e in spans if e[2] == "qos"]
    if not ns:
        return None
    lo, hi = min(s for s, _ in ns), max(e for _, e in ns)
    waiting = sum(e - s for s, e in trace_reduce.union(ns, lo, hi)) * 1e-9
    return 100.0 * waiting / f["span_window_s"]
