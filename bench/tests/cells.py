"""The cells of BENCHMARK.json cut to a size that a CPU test run holds,
and one run of a cell past the harness's look for a chip."""

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (ROOT / "src", BENCH / "drivers", BENCH):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import run  # noqa: E402

SEED = 2**31 + 4242


def tiny(workload: str) -> dict:
    """The cell's spec at a test size.  Serving keeps the published
    vocabulary: how far a lower precision moves the argmax depends on how
    many logits crowd the top."""
    spec = run.cell_spec(workload)
    if workload == "sar-mixed":
        spec["config"]["scale"] = 64
        spec["traffic"]["distinct_frames"] = 2
    else:
        cfg = spec["config"]
        cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, num_hidden_layers=2)
        cfg["engine"] = dict(cfg["engine"], arena_bytes=16 << 20)
        t = spec["traffic"]
        t["rate_per_s"] = 4.0
        t["prompt_tokens"] = dict(t["prompt_tokens"], median=12, min=4, max=40)
        t["output_tokens"] = dict(t["output_tokens"], median=12, min=4, max=24)
        t["drain_limit_s"] = 30
        t["sample"] = {"min_tokens": 10**6, "max_requests": 10**6}
    return spec


def measure(workload: str, seconds: float = 1.5, seed: int = SEED) -> dict:
    """One whole ``--trace 0`` run of the cell at its test size on the CPU."""
    import jax

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return run.measure(args, tiny(workload), jax.devices(), skip_chip_check=True)
