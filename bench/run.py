"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload sar-mixed --seed 7 --seconds 40 --trace 0

The cell, its configuration, its traffic mix and its metrics are looked
up by name in ``BENCHMARK.json``; each comes from a file of its own
under ``bench/`` (``configs/``, ``traffic/``, ``drivers/<kind>.py``,
``metrics/<metric>.py``).  One process loads, warms up, measures for
``--seconds``, checks what the measured path produced against the plain
reference, and prints one JSON line last on standard output.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace
1`` it records a profiler trace of the window and reports the per-layer
metrics.  Where JAX finds no TPU, or fewer chips than the cell asks for,
it exits with code 2 and prints no result.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
else ``.jax_cache`` at the root of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str) -> dict:
    """The cell's entries of BENCHMARK.json, its configuration and traffic
    files, and the metrics it reports."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def mine(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "traffic": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
        "per_layer": [m for m in spec["per_layer"] if mine(m)],
    }


def enable_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # every program, however small or quick to compile, comes back from
    # the cache, so set-up does the same work on every warm run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(n: int):
    """The first ``n`` TPU devices, or None (with a reason on stderr)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return None
    if len(devices) < n:
        print(f"bench: the cell needs {n} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return None
    return devices[:n]


class CompileCounter:
    """Counts XLA compilations (cache loads included) from JAX's events."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1


def passes(checks: dict) -> bool:
    """Whether every number compared lies within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def measure(args, spec, devices, *, skip_chip_check=False) -> dict:
    """Set up, measure and check one cell; returns the result line."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    cfg, traffic = spec["config"], spec["traffic"]
    driver_mod = load_module(BENCH / "drivers" / f"{cfg['kind']}.py",
                             f"bench_driver_{cfg['kind']}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks and not skip_chip_check:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    counter = CompileCounter()
    drv = driver_mod.Driver(cfg, traffic, args.seed, trace=bool(args.trace))
    drv.setup()
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the harness's annotations, not JAX's internals
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = counter.count
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.run_window(args.seconds)
    in_window = counter.count - compiles0
    reduced = None
    if args.trace:
        from trace_reduce import find_trace, load, reduce_trace

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        path = find_trace(trace_dir)
        size = os.path.getsize(path)
        reduced = reduce_trace(load(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"bench: trace of {size / 2**20:.1f} MiB written in {t1 - t0:.1f} s, "
              f"reduced in {time.perf_counter() - t1:.1f} s", file=sys.stderr)
    print(f"bench: {in_window} compilations inside the window", file=sys.stderr)
    drv.report(sys.stderr)
    memory = peak_bytes(devices)
    facts = drv.facts()
    facts.update(trace=reduced, peaks=peaks.get(kind), compiles_in_window=in_window)
    drv.release()
    checks = drv.check()
    metrics = {}
    if args.trace:
        for m in spec["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = drv.end_to_end()
        values["setup_s"] = setup_s
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": passes(checks) and drv.failed == 0,
              "attempted": drv.attempted, "failed": drv.failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    spec = cell_spec(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(BENCH))
    enable_cache()
    devices = find_chips(spec["cell"]["chips"])
    if devices is None:
        return 2
    result = measure(args, spec, devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
