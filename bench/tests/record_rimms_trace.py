"""Record the small profiler trace that ``test_bench_layers.py`` reads.

    python3 bench/tests/record_rimms_trace.py OUT.xplane.pb

On a TPU: one SAR frame cut to 1/64 of its ways (48 tasks) through the
radar Session with a ``TraceCollector`` attached, so the program's own
``rimms.*`` spans land in the trace beside the device's operations,
inside a ``bench.window`` annotation with the harness's ``bench.*``
spans, under the profiler as ``run.py --trace 1`` sets it up.  It prints
each ``rimms.*`` name with its count and one event's stats, and copies
the ``.xplane.pb`` to OUT.  The committed copy was recorded on a TPU v5
lite.
"""

import glob
import os
import shutil
import sys
import tempfile
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.apps.radar import build_sar, make_session
    from repro.core.hete import hete_sync
    from repro.core.trace import TraceCollector

    if jax.devices()[0].platform != "tpu":
        print("record_rimms_trace: no TPU found", file=sys.stderr)
        return 2
    session = make_session(policy="rimms", scheduler="round_robin", n_cpu=1,
                           accelerators=("gpu0",), trace=TraceCollector())

    def frame(tag):
        with jax.profiler.TraceAnnotation("bench.build"):
            bufs, tasks = build_sar(session.context, scale=64)
        with jax.profiler.TraceAnnotation("bench.submit"):
            for j, t in enumerate(tasks):
                session.submit(t.op, t.inputs, out=t.outputs, name=f"{tag}.{j}")
        with jax.profiler.TraceAnnotation("bench.readback"):
            session.barrier()
            for p in bufs.values():
                hete_sync(p["out"][0])

    frame("w")  # compile outside the trace
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        frame("0")
    jax.profiler.stop_trace()
    session.close()
    session.runtime.close()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    counts, first = Counter(), {}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rimms."):
                    counts[e.name] += 1
                    first.setdefault(e.name, dict(e.stats))
    for name, n in sorted(counts.items()):
        print(name, n, first[name])
    shutil.copy(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    print("bytes", os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
