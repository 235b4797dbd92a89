"""Run the Session's main paths once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: radar, serving, kernels
    python chip_smoke.py --chips 4   # four chips: the SAR stream spread
                                     # one accelerator per chip, only

Everything runs in this one process: the chip belongs to the process
that first touches JAX, and a child reaching for it would fail or hang.
Each phase prints one line (name, wall seconds, what it checked); any
failed check raises and fails the script.  The last line is one JSON
object naming the device.  Where JAX finds no TPU the script exits
non-zero and prints no such line: it never falls back to the CPU.

The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set, else
to ``.jax_cache`` beside this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0

# Radar outputs against float64 numpy: three chained complex64 transforms
# of at most 512 points, each a few f32 roundings per stage, stay well
# inside 1e-4 of the output's scale.
RADAR_TOL = 1e-4


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def report(phase: str, t0: float, detail: str) -> None:
    print(f"[{phase}] {time.perf_counter() - t0:.2f}s {detail}", flush=True)


# ---------------------------------------------------------------------------
# radar: RC, PD and SAR through a Session, rimms against reference
# ---------------------------------------------------------------------------


def _buffers(obj):
    """Every HeteData root reachable from an app builder's buffer dict."""
    from repro.core.hete import HeteData

    if isinstance(obj, HeteData):
        yield obj
    elif isinstance(obj, dict):
        for v in obj.values():
            yield from _buffers(v)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            yield from _buffers(v)


def _chains(bufs):
    """(a, b, out, n) per 2FZF data flow in an app's buffers: out is
    ifft(fft(a) * fft(b)) row by row over rows of n samples."""
    if "a" in bufs and not isinstance(bufs["a"], tuple):  # RC: plain buffers
        return [(bufs["a"], bufs["b"], bufs["out"], bufs["a"].shape[0])]
    phases = [bufs] if "a" in bufs else list(bufs.values())
    return [(p["a"][0], p["b"][0], p["out"][0], p["a"][1][0].shape[0])
            for p in phases]


def _device_payloads(bufs):
    """(location, payload) for every accelerator-resident copy."""
    for root in _buffers(bufs):
        for hd in [root, *(root.fragments or ())]:
            for loc, value in hd.copies.items():
                if loc.kind == "device":
                    yield loc, value


def _check_payloads(bufs, device_of) -> int:
    import jax

    n = 0
    for loc, value in _device_payloads(bufs):
        check(isinstance(value, jax.Array),
              f"{loc} holds a {type(value).__name__}, not a jax.Array")
        check(value.devices() == {device_of[loc.name]},
              f"{loc} payload on {value.devices()}, not {device_of[loc.name]}")
        n += 1
    return n


def run_radar_app(builder, *, policy, scheduler, n_cpu, accelerators):
    """One app's task list streamed through a fresh Session.  Returns
    (host outputs, host inputs, buffers, session) after the stream
    drained; copies are read before the outputs are synced to host."""
    from repro.apps.radar import make_session
    from repro.core.hete import hete_sync

    session = make_session(policy=policy, scheduler=scheduler, n_cpu=n_cpu,
                           accelerators=accelerators)
    bufs, tasks = builder(session.context)
    for t in tasks:
        session.submit(t.op, t.inputs, out=t.outputs, pin=t.pin, name=t.name)
    session.barrier()
    stats = {"copies": session.ledger.total_copies,
             "by_pair": dict(session.ledger.copies),
             "pes": sorted({pe for _, pe in session.runtime.task_log})}
    chains = _chains(bufs)
    outs = [hete_sync(out).reshape(-1, n).copy() for _, _, out, n in chains]
    ins = [(hete_sync(a).reshape(-1, n), hete_sync(b).reshape(-1, n))
           for a, b, _, n in chains]
    session.close()
    session.runtime.close()
    return outs, ins, bufs, stats


def radar_phase(device, *, sar_scale: int = 1) -> None:
    """RC, PD (128 x 128, fragmented) and SAR (512 x 256, then 256 x 512)
    on one CPU PE and one accelerator bound to ``device``, under both
    memory policies.  Static placement puts every task on the same PE
    under both policies, so their outputs must agree bit for bit:
    round-robin for PD and SAR, and RC's one chain pinned to the
    accelerator (round-robin would split its four tasks so that no
    intermediate stays on the accelerator, leaving RIMMS nothing to
    save)."""
    import numpy as np

    from repro.apps.radar import build_2fzf, build_pd, build_sar

    apps = [("RC", functools.partial(build_2fzf, n=256, pins=("gpu0",) * 4)),
            ("PD", functools.partial(build_pd, ways=128, n=128,
                                     use_fragment=True)),
            ("SAR", functools.partial(build_sar, scale=sar_scale))]
    for name, builder in apps:
        t0 = time.perf_counter()
        runs = {}
        for policy in ("rimms", "reference"):
            runs[policy] = run_radar_app(
                builder, policy=policy, scheduler="round_robin", n_cpu=1,
                accelerators=("gpu0",))
        (rim, ins, bufs, rim_stats), (ref, _, _, ref_stats) = (
            runs["rimms"], runs["reference"])
        check(all(np.array_equal(x, y) for x, y in zip(rim, ref, strict=True)),
              f"{name}: rimms and reference outputs differ")
        err = 0.0
        for out, (a, b) in zip(rim, ins, strict=True):
            want = np.fft.ifft(np.fft.fft(a.astype(np.complex128))
                               * np.fft.fft(b.astype(np.complex128)))
            err = max(err, float(np.max(np.abs(out - want))
                                 / np.max(np.abs(want))))
        check(err <= RADAR_TOL, f"{name}: error {err:.3g} > {RADAR_TOL}")
        check(rim_stats["copies"] < ref_stats["copies"],
              f"{name}: rimms copies {rim_stats['copies']} not below "
              f"reference {ref_stats['copies']}")
        n_payloads = _check_payloads(bufs, {"gpu0": device})
        check(n_payloads > 0, f"{name}: no accelerator payload to check")
        report(f"radar {name}", t0,
               f"rimms==reference bitwise, max rel err vs float64 {err:.3g} "
               f"(tol {RADAR_TOL}), copies rimms {rim_stats['copies']} < "
               f"reference {ref_stats['copies']}, {n_payloads} accelerator "
               f"payloads are jax.Arrays on {device}")


# ---------------------------------------------------------------------------
# serving: SessionServeEngine against the legacy engine
# ---------------------------------------------------------------------------

SERVE_GEOMETRY = dict(max_batch=4, page_size=16, num_pages=256,
                      max_pages_per_seq=32, allocator="nextfit")
PAGES_PER_GROUP = 4
PROMPT_LENS = (16, 256, 48, 192, 96, 128, 32, 64)
NEW_TOKENS = 32
# Requests arrive every few decode steps, so they finish at different
# steps and next-fit keeps handing out fresh pages: over the run the KV
# touches more page groups than any one step references.  The spill run
# gives the arena room for one step's groups but not for all of them,
# so the dirty KV of finished requests is evicted to host.
ARRIVAL_GAP = 6
SPILL_ARENA_GROUPS = 16


def serve_requests(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [([int(t) for t in rng.integers(1, vocab, n)], NEW_TOKENS,
             ("a", "b")[i % 2]) for i, n in enumerate(PROMPT_LENS)]


def drive(engine, work, *, tenants: bool, max_steps: int = 10_000):
    """Submit ``work[i]`` before decode step ``i * ARRIVAL_GAP`` and step
    until every request is done; returns the requests in arrival order."""
    reqs = []
    for step in range(max_steps):
        while len(reqs) < len(work) and step >= len(reqs) * ARRIVAL_GAP:
            prompt, n_new, tenant = work[len(reqs)]
            reqs.append(engine.submit(prompt, n_new, tenant=tenant) if tenants
                        else engine.submit(prompt, n_new))
        if (engine.step() == 0 and not engine.waiting
                and len(reqs) == len(work)):
            return reqs
    raise CheckFailed(f"requests still running after {max_steps} steps")


def serve_phase(cfg, *, seed: int = SEED, cut: str = "") -> None:
    """``cfg``'s engines answer PROMPT_LENS requests from two tenants with
    seeded random weights: the legacy engine, then the Session engine
    with an arena that holds the whole KV pool, then with one that holds
    what a step references but not the pool."""
    import jax

    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    from repro.serve.session_engine import SessionServeEngine

    t0 = time.perf_counter()
    params = jax.jit(build_model(cfg).init)(jax.random.key(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    work = serve_requests(cfg.vocab, seed)
    reqs = drive(ServeEngine(cfg, params, **SERVE_GEOMETRY), work,
                 tenants=False)
    want = [r.generated for r in reqs]
    report("serve legacy", t0,
           f"{cfg.name}{cut}: {n_params} params, {len(work)} requests, "
           f"prompts {min(PROMPT_LENS)}-{max(PROMPT_LENS)} tokens, "
           f"{NEW_TOKENS} new each, one every {ARRIVAL_GAP} steps")

    itemsize = jax.numpy.dtype(cfg.dtype).itemsize
    group_bytes = (2 * cfg.n_layers * PAGES_PER_GROUP * SERVE_GEOMETRY["page_size"]
                   * cfg.n_kv_heads * cfg.head_dim_ * itemsize)
    pool_bytes = SERVE_GEOMETRY["num_pages"] // PAGES_PER_GROUP * group_bytes
    for label, arena in (("roomy", 2 * pool_bytes),
                         ("spill", SPILL_ARENA_GROUPS * group_bytes)):
        t0 = time.perf_counter()
        with SessionServeEngine(cfg, params, pages_per_group=PAGES_PER_GROUP,
                                arena_bytes=arena, **SERVE_GEOMETRY) as eng:
            reqs = drive(eng, work, tenants=True)
            spill = eng.kv.spill_bytes()
        eng.session.runtime.close()
        check(all(r.done for r in reqs), f"serve {label}: a request is unfinished")
        check([r.generated for r in reqs] == want,
              f"serve {label}: tokens differ from the legacy engine's")
        if label == "roomy":
            check(spill == 0, f"serve roomy: {spill} bytes spilled")
        else:
            check(arena < pool_bytes and spill > 0,
                  f"serve spill: arena {arena} B, pool {pool_bytes} B, "
                  f"spilled {spill} B")
        report(f"serve {label}", t0,
               f"arena {arena} B (KV pool {pool_bytes} B): all "
               f"{len(reqs)} requests done, tokens == legacy, "
               f"kv spill {spill} B")


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel compiled for the chip, against its oracle
# ---------------------------------------------------------------------------


def kernel_phase(device, *, seed: int = SEED) -> None:
    import jax
    import numpy as np

    from repro.kernels import resolve_interpret
    from repro.kernels.cases import cases, max_rel_error

    check(resolve_interpret() is False, "kernels would run interpreted")
    rng = np.random.default_rng(seed)
    for case in cases():
        t0 = time.perf_counter()
        inputs = case.make_inputs(rng)
        args = [jax.device_put(x, device) for x in inputs]
        check("tpu_custom_call" in case.kernel.lower(*args).as_text(),
              f"{case.name}: lowered without a Mosaic kernel")
        got = jax.block_until_ready(case.kernel(*args))
        # the oracles' matmuls at full f32, not the TPU's 1-pass bf16
        with jax.default_matmul_precision("highest"):
            want = case.reference(*args)
        err = max_rel_error(got, want)
        check(err <= case.tol, f"{case.name}: error {err:.3g} > {case.tol}")
        report(f"kernel {case.name}", t0,
               f"Mosaic kernel, max rel err vs ref {err:.3g} (tol {case.tol})")


# ---------------------------------------------------------------------------
# four chips: one accelerator per chip
# ---------------------------------------------------------------------------


def multi_chip_phase(devices) -> None:
    """The SAR stream on one accelerator per device under HEFT, against
    the same stream on one accelerator.  Every task runs on a chip of the
    same kind, so placement cannot change the bits."""
    import numpy as np

    from repro.apps.radar import build_sar

    names = tuple(f"gpu{i}" for i in range(len(devices)))
    t0 = time.perf_counter()
    one, _, _, one_stats = run_radar_app(
        build_sar, policy="rimms", scheduler="heft", n_cpu=0,
        accelerators=names[:1])
    many, _, bufs, stats = run_radar_app(
        build_sar, policy="rimms", scheduler="heft", n_cpu=0,
        accelerators=names)
    check(all(np.array_equal(x, y) for x, y in zip(one, many, strict=True)),
          "SAR outputs on several chips differ from one chip's")
    n_payloads = _check_payloads(bufs, dict(zip(names, devices)))
    check(len(stats["pes"]) > 1, f"every task ran on {stats['pes']}")
    d2d = sum(c for (src, dst), c in stats["by_pair"].items()
              if src.startswith("device:") and dst.startswith("device:"))
    report(f"sar x{len(devices)} chips", t0,
           f"outputs == one accelerator bitwise, tasks on {stats['pes']}, "
           f"{n_payloads} payloads each on its own chip, copies "
           f"{stats['copies']} (one accelerator: {one_stats['copies']}), "
           f"device-to-device {d2d} (staged through the host)")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the one-accelerator-per-chip phase")
    args = parser.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(ROOT / ".jax_cache")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2

    cache_events = {"hits": 0, "misses": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(count)
    t0 = time.perf_counter()
    if args.chips == 4:
        multi_chip_phase(devices[:4])
    else:
        from repro.configs import get_config

        radar_phase(devices[0])
        yi = get_config("yi_9b")
        depth = 4
        serve_phase(dataclasses.replace(yi, n_layers=depth),
                    cut=f" cut to {depth} of {yi.n_layers} layers, published "
                        f"widths, {yi.dtype} compute, {yi.param_dtype} params")
        kernel_phase(devices[0])
    report("total", t0, f"compile cache {cache_dir}: {cache_events['hits']} "
                        f"hits, {cache_events['misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
