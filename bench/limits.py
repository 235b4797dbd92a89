"""Readings that a cell's correctness limit is set from: for each seed,
the number the sound program gives and the number its control gives.

    python3 bench/limits.py --workload yi9b-short --seconds 20 --seeds 1 2 3

One process runs every seed (set-up once compiled stays compiled): a
short window at the cell's own size and load, then the comparison the
benchmark makes, and the control's reading on the same inputs.  The
control is the reference one precision step below what the
configuration states (radar: bfloat16 stages; serving: float8 matrix
products).  The control is judged by the benchmark's own comparison
(``run.passes``) and has to come out not correct.  Each seed prints one
JSON line; the limits and the readings
they rest on are in PERF.md.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time

import numpy as np

import run


def readings(workload: str, seed: int, seconds: float) -> dict:
    spec = run.cell_spec(workload)
    cfg = spec["config"]
    drv_mod = run.load_module(run.BENCH / "drivers" / f"{cfg['kind']}.py",
                              f"bench_driver_{cfg['kind']}")
    drv = drv_mod.Driver(cfg, spec["traffic"], seed, trace=False)
    t0 = time.perf_counter()
    drv.setup()
    drv.run_window(seconds)
    drv.release()
    out = {"workload": workload, "seed": seed, "attempted": drv.attempted}
    if cfg["kind"] == "radar":
        from reference import radar as ref

        (name, check), = drv.check().items()
        control = 0.0
        for idx, _ in drv.outputs[:len(drv.frames)]:
            frame = drv.frames[idx]
            for i, (ways, n) in enumerate(drv.phases):
                a = frame[2 * i].reshape(ways, n)
                b = frame[2 * i + 1].reshape(ways, n)
                control = max(control, ref.rel_error(ref.control(a, b), ref.chain(a, b)))
        control_correct = run.passes({name: dict(check, value=control)})
    else:
        from reference.decoder import served_gaps

        ref, ctl = drv.reference(), drv.reference("fp8")
        program, controls = [], []
        for t in drv.sample():
            program += served_gaps(ref, t.req.prompt, t.handle.generated)
            controls += served_gaps(ref, t.req.prompt, t.handle.generated, control=ctl)
        (name, check), = drv_mod.judge(program).items()
        judged = drv_mod.judge(controls)
        control, control_correct = judged[name]["value"], run.passes(judged)
        # the other numbers that could be compared, for the choice of one
        for side, g in (("program", program), ("control", controls)):
            g = np.asarray(g)
            out[side + "_max"] = float(g.max()) if g.size else None
            out[side + "_flipped_share"] = float(np.mean(g > 0)) if g.size else None
        out["sampled_tokens"] = len(program)
    out.update(number=name, program=check["value"], control=control,
               limit=check["limit"], program_correct=run.passes({name: check}),
               control_correct=control_correct, failed=drv.failed,
               seconds=time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    args = parser.parse_args(argv)
    sys.path.insert(0, str(run.BENCH))
    sys.path.insert(0, str(run.ROOT / "src"))
    run.enable_cache()
    spec = run.cell_spec(args.workload)
    if run.find_chips(spec["cell"]["chips"]) is None:
        return 2
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
