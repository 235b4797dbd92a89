"""Radar frame stream: the app's SAR task list through one long-lived
``Session``, one frame in flight (closed loop).

A frame is the task list ``repro.apps.radar.build_sar`` returns, with
the benchmark's own seeded samples written into its input buffers.  It
is submitted as built, read back to the host, and its buffers are freed;
then the next frame is built.  Every frame of the window is compared
with the float64 reference.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np
from jax.profiler import TraceAnnotation

import gen
from reference import radar as ref
from work import radar_task_work

ACC_KEYS = ("a", "b", "fa", "fb", "z", "out")
WARMUP_FRAMES = 2
#: complex64 FFT chains of at most 512 points against float64: a few f32
#: roundings per stage; the limit and the readings it rests on are in PERF.md
MAX_REL_ERR = 1e-4


class Driver:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, *, trace: bool):
        if cfg.get("app") != "sar":
            raise ValueError(f"radar driver runs the SAR app, not {cfg.get('app')!r}")
        self.cfg, self.traffic, self.seed, self.trace = cfg, traffic, seed, trace
        self.scale = int(cfg.get("scale", 1))
        self.phases = [(p["ways"] // self.scale, p["samples"]) for p in cfg["phases"]]
        self.acc = cfg["accelerators"][0]
        self.attempted = self.failed = 0
        self.outputs: List[tuple] = []  # (input frame index, [phase outputs])
        self.frame_s: List[float] = []
        self.work: List[tuple] = []
        self.tracer = None

    # -- set-up ----------------------------------------------------------------
    def setup(self) -> None:
        from repro.apps.radar import make_session

        cfg = self.cfg
        self.session = make_session(policy=cfg["policy"], scheduler=cfg["scheduler"],
                                    n_cpu=cfg["n_cpu"],
                                    accelerators=tuple(cfg["accelerators"]))
        if self.trace:
            from repro.core.trace import TraceCollector

            self.tracer = TraceCollector(capacity_per_thread=1 << 22)
            self.session.context.set_tracer(self.tracer)
        shapes = []
        for ways, n in self.phases:
            shapes += [(ways * n,), (ways * n,)]
        self.frames = gen.frames(self.traffic, self.seed, shapes)
        for i in range(WARMUP_FRAMES):
            self._frame(i, tag=f"w{i}")

    # -- one frame ---------------------------------------------------------------
    def _frame(self, k: int, *, tag: str):
        from repro.apps.radar import build_sar
        from repro.core.hete import hete_sync

        session = self.session
        idx = k % len(self.frames)
        with TraceAnnotation("bench.build"):
            bufs, tasks = build_sar(session.context, scale=self.scale,
                                    use_fragment=self.cfg["use_fragment"])
            phases = [bufs["phase1"], bufs["phase2"]]
            for p, (ways, n), i in zip(phases, self.phases, (0, 2)):
                if p["a"][0].shape != (ways * n,) or len(p["a"][1]) != ways:
                    raise RuntimeError(f"build_sar made {p['a'][0].shape} in "
                                       f"{len(p['a'][1])} fragments, the config "
                                       f"says {ways} x {n}")
                p["a"][0].data[...] = self.frames[idx][i]
                p["b"][0].data[...] = self.frames[idx][i + 1]
        with TraceAnnotation("bench.submit"):
            for j, t in enumerate(tasks):
                session.submit(t.op, t.inputs, out=t.outputs, pin=t.pin,
                               name=f"{tag}.{j}")
        with TraceAnnotation("bench.readback"):
            session.barrier()
            outs = [hete_sync(p["out"][0]).reshape(ways, n).copy()
                    for p, (ways, n) in zip(phases, self.phases)]
        desc = [(t.op, int(t.inputs[0].shape[0]), [id(x) for x in t.inputs],
                 [id(y) for y in t.outputs]) for t in tasks]
        read_back = [id(f) for p in phases for f in p["out"][1]]
        with TraceAnnotation("bench.free"):
            for p in phases:
                for key in ACC_KEYS:
                    session.free(p[key][0])
        return idx, outs, desc, read_back

    # -- the window --------------------------------------------------------------
    def run_window(self, seconds: float) -> None:
        ledger = self.session.ledger
        self.copies0 = ledger.total_copies
        if self.tracer is not None:
            self.tracer.instant("bench.window_start", "bench", "bench")
        t0 = time.perf_counter()
        t = t0
        frames = []
        while t - t0 < seconds:
            f0 = time.perf_counter()
            idx, outs, desc, read_back = self._frame(len(frames), tag=str(len(frames)))
            t = time.perf_counter()
            self.frame_s.append(t - f0)
            self.outputs.append((idx, outs))
            frames.append((desc, read_back))
        if self.tracer is not None:
            self.tracer.instant("bench.window_end", "bench", "bench")
        self.window_s = t - t0
        self.copies = ledger.total_copies - self.copies0
        self.attempted = len(frames)
        self._frame_desc = frames

    def end_to_end(self) -> Dict[str, float]:
        return {"frames_per_s": self.attempted / self.window_s}

    def report(self, err) -> None:
        n = len(self.frame_s)
        h = n // 2
        if h:
            print(f"bench: {n} frames; mean frame {np.mean(self.frame_s[:h]):.4f} s "
                  f"in the first half, {np.mean(self.frame_s[h:]):.4f} s in the "
                  f"second", file=err)

    # -- per-layer material -------------------------------------------------------
    def facts(self) -> Dict:
        out = {"window_s": self.window_s, "frames": self.attempted,
               "copies": self.copies, "acc": self.acc, "spans": None, "work": None}
        if self.tracer is None:
            return out
        events = self.tracer.wall_events()
        marks = {e[1]: e[4] for e in events if e[2] == "bench"}
        lo, hi = marks["bench.window_start"], marks["bench.window_end"]
        spans = [e for e in events if e[0] == "X" and lo <= e[4] and e[4] + e[5] <= hi]
        out["spans"] = spans
        out["span_window_s"] = hi - lo
        # which PE ran each task of each frame, from the compute spans
        placed: Dict[str, str] = {}
        for e in spans:
            if e[2] == "compute":
                placed[e[1]] = e[3].split(":", 1)[1]
        work = []
        for f, (desc, read_back) in enumerate(self._frame_desc):
            placement = [placed.get(f"{f}.{j}", "") for j in range(len(desc))]
            work += radar_task_work(desc, placement, self.acc, read_back)
        out["work"] = work
        return out

    # -- after the window ----------------------------------------------------------
    def release(self) -> None:
        self.session.close()
        self.session.runtime.close()
        self.session = None

    def check(self) -> Dict[str, Dict[str, float]]:
        want = {}
        worst = 0.0
        for idx, outs in self.outputs:
            if idx not in want:
                frame = self.frames[idx]
                want[idx] = [ref.chain(frame[2 * i].reshape(ways, n),
                                       frame[2 * i + 1].reshape(ways, n))
                             for i, (ways, n) in enumerate(self.phases)]
            err = max(ref.rel_error(o, w) for o, w in zip(outs, want[idx]))
            if err > MAX_REL_ERR:
                self.failed += 1
            worst = max(worst, err)
        return {"max_rel_err": {"value": worst, "limit": MAX_REL_ERR}}
