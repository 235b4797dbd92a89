"""Record the small profiler trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py OUT.xplane.pb

On a TPU: one SAR frame cut to 1/64 of its ways (48 tasks) through the
radar Session, inside a ``bench.window`` annotation with the harness's
own ``bench.*`` spans, under the profiler.  It prints every plane and
line of the trace with a few events each, and copies the
``.xplane.pb`` to OUT.  The committed copy was recorded on a TPU v5 lite.
"""

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.apps.radar import build_sar, make_session
    from repro.core.hete import hete_sync

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU found", file=sys.stderr)
        return 2
    session = make_session(policy="rimms", scheduler="round_robin", n_cpu=1,
                           accelerators=("gpu0",))

    def frame():
        with jax.profiler.TraceAnnotation("bench.build"):
            bufs, tasks = build_sar(session.context, scale=64)
        with jax.profiler.TraceAnnotation("bench.submit"):
            for t in tasks:
                session.submit(t.op, t.inputs, out=t.outputs, name=t.name)
        with jax.profiler.TraceAnnotation("bench.readback"):
            session.barrier()
            for p in bufs.values():
                hete_sync(p["out"][0])

    frame()  # compile outside the trace
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        frame()
    jax.profiler.stop_trace()
    session.close()
    session.runtime.close()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for e in evs[:4]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns, dict(e.stats))
    shutil.copy(path, out)
    shutil.rmtree(log_dir, ignore_errors=True)
    print("bytes", os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
