"""Share of the device's busy time in the traced window that falls inside
the program's ``rimms.compute`` spans of ``llm_prefill`` tasks: prefill
and decode run the one step program, so the task span tells them apart."""

import layer_idle


def read(f):
    lf = layer_idle.of(f)
    pe = lf and layer_idle.serving_pe(lf)
    if not pe or lf["busy_s"] <= 0:
        return None
    return 100.0 * pe["busy"].get("compute:llm_prefill", 0.0) / lf["busy_s"]
