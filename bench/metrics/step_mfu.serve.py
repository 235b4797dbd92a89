"""Model FLOPs the window's tokens need, over the wall time of the engine
steps that did work times the chip's bf16 peak."""


def read(f):
    peaks, steps = f.get("peaks"), f.get("steps")
    if not peaks or not steps:
        return None
    wall = sum(w for w, _, _ in steps)
    flops = sum(fl for _, fl, _ in steps)
    if wall <= 0:
        return None
    return 100.0 * flops / (wall * peaks["bf16_flops_per_s"])
