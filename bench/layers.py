"""One traced run of a cell that also splits the device's idle time by
runtime layer.

    python3 bench/layers.py --workload sar-mixed --seed 7 --seconds 51

It runs as ``run.py --trace 1`` does, and differs in three ways.  The
trace's reduction also holds ``layer_idle.layer_facts`` under
``trace["rimms"]``, so the readers of the layer metrics in ``LAYERS``
find what they read.  Those metrics are not in BENCHMARK.json yet,
because ``run.py`` does not make that reduction.  The JSON line also
holds the window's end-to-end metrics as the traced run measured them.
Last, it holds the trace's size, the seconds ``stop_trace`` took, and
the split itself.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import layer_idle
import run
import trace_reduce

#: layer metric -> (unit, cell); each reads ``trace["rimms"]``
LAYERS = {
    "idle_in_stage_share.radar": ("%", "sar-mixed"),
    "idle_in_compute_share.radar": ("%", "sar-mixed"),
    "idle_in_writeback_share.radar": ("%", "sar-mixed"),
    "prefill_busy_share.serve": ("%", "yi9b-short"),
    "idle_in_compute_ms.serve": ("ms/task", "yi9b-short"),
    "idle_outside_tasks_ms.serve": ("ms/step", "yi9b-short"),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    import jax

    spec = run.cell_spec(args.workload)
    run.enable_cache()
    devices = run.find_chips(spec["cell"]["chips"])
    if devices is None:
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    cfg = spec["config"]
    drv_mod = run.load_module(run.BENCH / "drivers" / f"{cfg['kind']}.py",
                              f"bench_driver_{cfg['kind']}")
    counter = run.CompileCounter()
    drv = drv_mod.Driver(cfg, spec["traffic"], args.seed, trace=True)
    drv.setup()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = counter.count
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.run_window(args.seconds)
    in_window = counter.count - compiles0
    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    t1 = time.perf_counter()
    path = trace_reduce.find_trace(trace_dir)
    size = os.path.getsize(path)
    pd = trace_reduce.load(path)
    reduced = trace_reduce.reduce_trace(pd)
    reduced["rimms"] = layer_idle.layer_facts(pd)
    t2 = time.perf_counter()
    del pd
    shutil.rmtree(trace_dir, ignore_errors=True)
    drv.report(sys.stderr)
    e2e = drv.end_to_end()
    facts = drv.facts()
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    facts.update(trace=reduced, peaks=peaks.get(devices[0].device_kind),
                 compiles_in_window=in_window)
    drv.release()
    checks = drv.check()
    names = [m["name"] for m in spec["per_layer"]]
    names += [n for n, (_, cell) in LAYERS.items() if cell == args.workload]
    metrics = {}
    for name in names:
        reader = run.load_module(run.BENCH / "metrics" / f"{name}.py",
                                 "bench_metric_" + name.replace(".", "_"))
        metrics[name] = reader.read(facts)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": run.passes(checks) and drv.failed == 0,
        "attempted": drv.attempted, "failed": drv.failed,
        "compiles_in_window": in_window, "end_to_end_traced": e2e, "metrics": metrics,
        "trace_mib": size / 2**20, "stop_trace_s": t1 - t0, "reduce_s": t2 - t1,
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        "split": reduced["rimms"], "idle_gaps": reduced["idle_gaps"],
        "device_ops": reduced["device_ops"], "checks": checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
