"""Rate sweep of a serving cell, to find the highest rate it sustains.

    python3 bench/sweep.py --workload yi9b-short --seconds 51 --rates 0.5 0.7 --repeats 3

One process, one set-up; then ``--repeats`` windows at each rate in
turn, with the cell's own lengths and tenants.  Each window prints one
JSON line with the tails, the queue wait of its first and last thirds
and the median time to first token of its two halves: a wait that grows
from the first part to the last is a backlog that grows.
The traffic file's rate is 0.8 times the highest rate whose backlog did
not grow, found once with this sweep and recorded in PERF.md.  The
benchmark's own runs never run this.
"""

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    parser.add_argument("--repeats", type=int, default=1)
    parser.add_argument("--seed", type=int, default=2**31 + 101)
    args = parser.parse_args(argv)
    sys.path.insert(0, str(run.BENCH))
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_cache()
    spec = run.cell_spec(args.workload)
    if run.find_chips(spec["cell"]["chips"]) is None:
        return 2
    drv_mod = run.load_module(run.BENCH / "drivers" / "serve.py", "bench_driver_serve")
    drv = drv_mod.Driver(spec["config"], dict(spec["traffic"]), args.seed, trace=False)
    drv.setup()
    for rate in [r for r in args.rates for _ in range(args.repeats)]:
        drv.traffic["rate_per_s"] = rate
        drv.run_window(args.seconds)
        drv.engine.run()  # finish the drain's requests before the next rate
        e2e = drv.end_to_end()
        waits = [1e3 * ((t.admit_t if t.admit_t is not None else drv.window_s) - t.req.due_s)
                 for t in drv.tracked]
        third = max(1, len(waits) // 3)
        ttft = drv._ttft_ms()
        half = len(ttft) // 2
        done = [t for t in drv.tracked if t.handle is not None and t.handle.done]
        last = max((t.token_t[-1] for t in done if t.token_t), default=drv.window_s)
        print(json.dumps({
            "rate_per_s": rate, "attempted": drv.attempted, "failed": drv.failed,
            "ttft_p50_ms": float(np.percentile(drv._ttft_ms(), 50)), **e2e,
            "queue_ms_first_third": float(np.median(waits[:third])),
            "queue_ms_last_third": float(np.median(waits[-third:])),
            "ttft_p50_ms_first_half": float(np.median(ttft[:half])),
            "ttft_p50_ms_second_half": float(np.median(ttft[half:])),
            "last_window_token_after_close_s": last - args.seconds,
            "window_and_drain_s": drv.window_s,
            "tokens_per_s": sum(len(t.handle.generated) for t in done) / drv.window_s,
        }), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
