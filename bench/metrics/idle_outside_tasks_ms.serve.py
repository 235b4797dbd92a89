"""Milliseconds per decoding engine step in which the device is idle inside
``rimms.step`` while the serving PE's thread has no task span open:
admission, table building, the hand-off to the executor, the result."""

import layer_idle


def read(f):
    lf = layer_idle.of(f)
    pe = lf and layer_idle.serving_pe(lf)
    if not pe or not f.get("decode_steps"):
        return None
    return 1e3 * pe["idle_in_steps"].get(layer_idle.NO_TASK, 0.0) / f["decode_steps"]
