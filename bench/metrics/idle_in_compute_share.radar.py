"""Share of the traced window in which the device is idle while the
accelerator PE's thread is inside a kernel call (``rimms.compute``):
launching the 2-4 KiB FFT and product programs."""

import layer_idle


def read(f):
    lf = layer_idle.of(f)
    pe = lf and lf["pes"].get(f.get("acc"))
    if not pe or lf["window_s"] <= 0:
        return None
    return 100.0 * layer_idle.total(pe["idle"], "compute") / lf["window_s"]
