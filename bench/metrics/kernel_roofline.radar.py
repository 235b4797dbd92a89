"""The least time of the transforms and products the accelerator was
given (``work.radar_task_work``) at the chip's peaks, over the device's
busy time in the trace."""

import work


def read(f):
    trace, peaks = f.get("trace"), f.get("peaks")
    if not trace or not peaks or not f.get("work") or trace["busy_s"] <= 0:
        return None
    least = work.least_time(f["work"], peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    return 100.0 * least / trace["busy_s"]
