"""The least time of the window's model work (``moe_work.MoEShape``:
weights outside the experts read once per prompt and once per engine
step that made tokens, each distinct expert the router chose there read
once, KV read and written with sliding layers capped at their window)
at the chip's peaks, over the device's busy time."""

import work


def read(f):
    trace, peaks, steps = f.get("trace"), f.get("peaks"), f.get("steps")
    if not trace or not peaks or not steps or trace["busy_s"] <= 0:
        return None
    least = work.least_time([(fl, b) for _, fl, b in steps],
                            peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return 100.0 * least / trace["busy_s"]
