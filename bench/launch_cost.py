"""What kernel launches cost on the chip, in a radar cell and alone.

    python3 bench/launch_cost.py --workload sar-mixed --seed 7 --seconds 51

Part one runs the cell's window with the program's spans kept in the
ring only (no profiler trace) and reads the accelerator PE's spans
(``launch_facts``): its launches, the tasks a launch carried, the median
time a launch held the PE's thread for each number of tasks, and how the
thread spent the window (inside compute, stage or write-back spans, or
none).  Part two times 16 inverse FFTs of 512 points three ways: each
dispatched and waited for alone, all dispatched then one wait, and one
program with 16 outputs; alone, and beside one busy Python thread.  One
JSON line.  The benchmark's own runs never run this.
"""

import argparse
import json
import statistics
import sys
import threading
import time
from typing import Dict, List, Tuple

import run


def _union_s(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if hi > end:
            total += hi - max(lo, end)
            end = hi
    return total


def launch_facts(spans, acc: str, window_s: float) -> Dict:
    """The accelerator PE's launches from the ring's spans of a window
    (``("X", name, cat, track, t0_s, dur_s, args)``): compute spans that
    share a ``launch`` stat are one launch, from its first start to its
    last end.  The thread's shares leave out the transfer pool's
    prefetch stages."""
    launches: Dict[int, List[float]] = {}
    by_cat: Dict[str, List[Tuple[float, float]]] = {}
    for _, _, cat, track, t0, dur, args in spans:
        if track not in (f"pe:{acc}", f"pe:{acc}:stage") or (args or {}).get("prefetch"):
            continue
        by_cat.setdefault(cat, []).append((t0, t0 + dur))
        if cat == "compute" and "launch" in (args or {}):
            lo, hi, n = launches.setdefault(args["launch"], [t0, t0 + dur, 0])
            launches[args["launch"]] = [min(lo, t0), max(hi, t0 + dur), n + 1]
    by_size: Dict[int, List[float]] = {}
    for lo, hi, n in launches.values():
        by_size.setdefault(n, []).append(hi - lo)
    tasks = sum(n for *_, n in launches.values())
    shares = {cat: 100.0 * _union_s(iv) / window_s for cat, iv in sorted(by_cat.items())}
    shares["none"] = 100.0 * (1.0 - _union_s([i for iv in by_cat.values() for i in iv])
                              / window_s)
    return {
        "launches": len(launches), "tasks": tasks,
        "tasks_per_launch": tasks / len(launches) if launches else None,
        "launch_ms_by_tasks": {n: {"launches": len(d), "median_ms": 1e3 * statistics.median(d)}
                               for n, d in sorted(by_size.items())},
        "thread_share_pct": shares,
    }


def _spin(stop: threading.Event) -> None:
    while not stop.is_set():
        sum(range(1000))


def dispatch_costs() -> Dict[str, float]:
    """Median milliseconds for 16 inverse FFTs of 512 points, three ways,
    alone and beside one busy Python thread."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    one = jax.jit(jnp.fft.ifft)
    rows = jax.jit(lambda *xs: tuple(jnp.fft.ifft(x) for x in xs))
    rng = np.random.default_rng(0)
    xs = [jnp.asarray((rng.standard_normal(512) + 1j * rng.standard_normal(512))
                      .astype(np.complex64)) for _ in range(16)]
    ways = {
        "16 x (dispatch + wait)": lambda: [jax.block_until_ready(one(x)) for x in xs],
        "16 dispatches + 1 wait": lambda: jax.block_until_ready([one(x) for x in xs]),
        "1 program, 16 outputs": lambda: jax.block_until_ready(rows(*xs)),
    }
    for way in ways.values():
        way()
    out = {}
    for busy, reps in ((0, 200), (1, 30)):
        stop = threading.Event()
        spinners = [threading.Thread(target=_spin, args=(stop,), daemon=True)
                    for _ in range(busy)]
        for t in spinners:
            t.start()
        for name, way in ways.items():
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                way()
                times.append(time.perf_counter() - t0)
            out[f"{name}, {busy} busy threads"] = 1e3 * statistics.median(times)
        stop.set()
        for t in spinners:
            t.join()
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    spec = run.cell_spec(args.workload)
    run.enable_cache()
    if run.find_chips(spec["cell"]["chips"]) is None:
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    cfg = spec["config"]
    drv_mod = run.load_module(run.BENCH / "drivers" / f"{cfg['kind']}.py",
                              f"bench_driver_{cfg['kind']}")
    drv = drv_mod.Driver(cfg, spec["traffic"], args.seed, trace=True)
    drv.setup()
    drv.run_window(args.seconds)
    e2e = drv.end_to_end()
    facts = drv.facts()
    drv.release()
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "end_to_end_traced": e2e,
        "frames": facts["frames"],
        "acc": launch_facts(facts["spans"], facts["acc"], facts["span_window_s"]),
        "dispatch_ms": dispatch_costs()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
