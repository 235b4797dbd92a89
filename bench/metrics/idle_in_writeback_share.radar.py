"""Share of the traced window in which the device is idle while the
accelerator PE's thread commits a task's outputs (``rimms.writeback``)."""

import layer_idle


def read(f):
    lf = layer_idle.of(f)
    pe = lf and lf["pes"].get(f.get("acc"))
    if not pe or lf["window_s"] <= 0:
        return None
    return 100.0 * layer_idle.total(pe["idle"], "writeback") / lf["window_s"]
