"""Milliseconds per ``llm_decode`` task in which the device is idle inside
the task's ``rimms.compute`` span: dispatching the step program."""

import layer_idle


def read(f):
    lf = layer_idle.of(f)
    pe = lf and layer_idle.serving_pe(lf)
    if not pe:
        return None
    return 1e3 * pe["idle"].get("compute:llm_decode", 0.0) / pe["count"]["compute:llm_decode"]
