"""Serving engine: open-loop requests through ``SessionServeEngine``.

The harness owns the clock: it submits each request when it is due,
calls ``engine.step()`` while there is work, and stamps every token when
the step that produced it returns.  Time to first token and time per
output token run from the time a request was due, so a stall delays
every request behind it.  After the window, a sample of the finished
requests is run through the plain float32 reference, and each served
token's logit is compared with the reference's best.

Set-up ends with a full garbage collection and ``gc.freeze()``, so that
the objects set-up leaves (imports, compiled programs, weights) are not
scanned again by a collection inside the window; the window's own
garbage is collected as usual, and each collection's pause is reported.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

import gen
from reference.decoder import Reference, make_weights, served_gaps
from work import decoder_shape

#: mean gap, in logits, by which the sampled served tokens may lie below
#: the reference's best; the readings it rests on are in PERF.md
MEAN_LOGIT_GAP = 5e-4


def judge(gaps: List[float]) -> Dict[str, Dict[str, float]]:
    """The number compared, with its limit, for the gaps of every sampled
    served token; with nothing sampled there is nothing to vouch for the
    run."""
    value = float(np.mean(gaps)) if gaps else 1e30
    return {"mean_logit_gap": {"value": value, "limit": MEAN_LOGIT_GAP}}


def arch_config(cfg: Dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"], dtype=cfg["compute_dtype"],
        param_dtype=cfg["torch_dtype"])


def program_params(w: Dict) -> Dict:
    """The benchmark's weights in the layout the program's dense stack reads."""
    return {
        "embed": {"table": w["embed"], "head": w["head"]},
        "final_norm": {"scale": w["final_norm"]},
        "stacks": [{"b0": {
            "norm1": {"scale": w["attn_norm"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": w["mlp_norm"]},
            "mlp": {"w_in": w["w_up"], "w_gate": w["w_gate"], "w_out": w["w_down"]},
        }}],
    }


@dataclasses.dataclass
class Tracked:
    req: gen.Request
    handle: object = None
    submit_t: float = 0.0
    admit_t: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)


class Driver:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, *, trace: bool):
        self.cfg, self.traffic, self.seed, self.trace = cfg, traffic, seed, trace
        self.eng_cfg = cfg["engine"]
        self.shape = decoder_shape(cfg)
        self.attempted = self.failed = 0
        self.tracer = None

    # -- set-up ----------------------------------------------------------------
    def setup(self) -> None:
        from repro.serve.session_engine import SessionServeEngine

        e = self.eng_cfg
        self.weights = make_weights(self.cfg, self.seed)
        jax.block_until_ready(self.weights)
        self.engine = SessionServeEngine(
            arch_config(self.cfg), program_params(self.weights),
            max_batch=e["max_batch"], page_size=e["page_size"],
            num_pages=e["num_pages"], pages_per_group=e["pages_per_group"],
            max_pages_per_seq=e["max_pages_per_seq"], allocator=e["allocator"],
            arena_bytes=e["arena_bytes"])
        if self.trace:
            from repro.core.trace import TraceCollector

            self.tracer = TraceCollector(capacity_per_thread=1 << 20)
            self.engine.session.context.set_tracer(self.tracer)
        for name in self.traffic["tenants"]:
            self.engine.tenant(name)
        # warm-up: a request of every tenant, alone and side by side,
        # through prefill and decode; the one decode program is compiled
        # or loaded from the cache here
        vocab = self.cfg["vocab_size"]
        for group in ([n] for n in self.traffic["tenants"]):
            for name in group:
                self.engine.submit([1, vocab - 1, 2], 2, tenant=name)
            self.engine.run()
        for name in self.traffic["tenants"]:
            self.engine.submit([3, 4], 2, tenant=name)
        self.engine.run()
        gc.collect()
        gc.freeze()

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"], time.perf_counter() - self._gc_t0))

    # -- the window --------------------------------------------------------------
    def run_window(self, seconds: float) -> None:
        eng = self.engine
        reqs = gen.requests(self.traffic, self.seed, seconds, self.cfg["vocab_size"])
        tracked = [Tracked(r) for r in reqs]
        drain = float(self.traffic["drain_limit_s"])
        if self.tracer is not None:
            self.tracer.instant("bench.window_start", "bench", "bench")
        self.tlog0 = len(eng.session.runtime.task_log)
        pending: List[Tracked] = []
        steps = []  # (t0, t1, prompts admitted, decode positions)
        self.gc_pauses = []  # (generation, seconds)
        gc.callbacks.append(self._on_gc)
        t0 = time.perf_counter()
        nxt = 0
        while True:
            now = time.perf_counter() - t0
            while nxt < len(tracked) and tracked[nxt].req.due_s <= now:
                tr = tracked[nxt]
                with TraceAnnotation("bench.submit"):
                    tr.handle = eng.submit(tr.req.prompt, tr.req.max_new,
                                           tenant=tr.req.tenant)
                tr.submit_t = time.perf_counter() - t0
                pending.append(tr)
                nxt += 1
            window_left = [t for t in tracked if t.req.window and
                           (t.handle is None or not t.handle.done)]
            if not window_left or now > seconds + drain:
                break
            if not pending:
                wait = tracked[nxt].req.due_s - now if nxt < len(tracked) else 0.01
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(wait, 0.05)))
                continue
            s0 = time.perf_counter() - t0
            before = {id(t): len(t.handle.generated) for t in pending}
            with TraceAnnotation("bench.step"):
                eng.step()
            s1 = time.perf_counter() - t0
            admitted, decoded = [], []
            slots = {id(r) for r in eng.slot_req if r is not None}
            for t in pending:
                h = t.handle
                if t.admit_t is None and (id(h) in slots or h.done or h.generated):
                    t.admit_t = s0
                    admitted.append(len(h.prompt))
                for g in range(before[id(t)], len(h.generated)):
                    t.token_t.append(s1)
                    decoded.append(len(h.prompt) - 1 + g)
            pending = [t for t in pending if not t.handle.done]
            steps.append((s0, s1, admitted, decoded))
        self.window_s = time.perf_counter() - t0
        gc.callbacks.remove(self._on_gc)
        if self.tracer is not None:
            self.tracer.instant("bench.window_end", "bench", "bench")
        self.tracked = [t for t in tracked if t.req.window]
        self.steps = steps
        self.attempted = len(self.tracked)
        self.failed = sum(1 for t in self.tracked
                          if t.handle is None or not t.handle.done)
        self.late_s = [t.submit_t - t.req.due_s for t in tracked if t.handle is not None]

    def _ttft_ms(self) -> List[float]:
        return [1e3 * ((t.token_t[0] if t.token_t else self.window_s) - t.req.due_s)
                for t in self.tracked]

    def _tpot_ms(self) -> List[float]:
        out = []
        for t in self.tracked:
            if len(t.token_t) == t.req.max_new and len(t.token_t) > 1:
                out.append(1e3 * (t.token_t[-1] - t.token_t[0]) / (len(t.token_t) - 1))
            else:
                out.append(1e3 * self.window_s)  # unfinished: misses any limit
        return out

    def end_to_end(self) -> Dict[str, float]:
        return {"ttft_p95_ms": float(np.percentile(self._ttft_ms(), 95)),
                "tpot_p95_ms": float(np.percentile(self._tpot_ms(), 95))}

    def report(self, err) -> None:
        ttft = self._ttft_ms()
        h = len(ttft) // 2
        print(f"bench: {self.attempted} requests due in the window, {self.failed} "
              f"unfinished; {len(self.steps)} engine steps; generator late by at "
              f"most {1e3 * max(self.late_s or [0.0]):.1f} ms (steps block it)",
              file=err)
        if h:
            print(f"bench: median time to first token {np.median(ttft[:h]):.1f} ms "
                  f"in the first half, {np.median(ttft[h:]):.1f} ms in the second; "
                  "longest three " + ", ".join(f"{v:.1f}" for v in sorted(ttft)[-3:]), file=err)
        gen2 = [s for g, s in self.gc_pauses if g == 2]
        longest = max((s1 - s0 for s0, s1, _, _ in self.steps), default=0.0)
        print(f"bench: {len(self.gc_pauses)} garbage collections in the window "
              f"({len(gen2)} full), longest pause "
              f"{1e3 * max([s for _, s in self.gc_pauses] or [0.0]):.2f} ms; "
              f"longest engine step {1e3 * longest:.1f} ms", file=err)

    # -- per-layer material -------------------------------------------------------
    def facts(self) -> Dict:
        shape = self.shape
        work = [(s1 - s0,) + shape.step_work(adm, dec)
                for s0, s1, adm, dec in self.steps if adm or dec]
        log = self.engine.session.runtime.task_log[self.tlog0:]
        out = {
            "window_s": self.window_s,
            "steps": work,
            "decode_tasks": sum(1 for name, _ in log if name.startswith("llm_decode")),
            "decode_steps": sum(1 for s in self.steps if s[3]),
            "spans": None,
        }
        if self.tracer is not None:
            events = self.tracer.wall_events()
            marks = {e[1]: e[4] for e in events if e[2] == "bench"}
            lo, hi = marks["bench.window_start"], marks["bench.window_end"]
            out["spans"] = [e for e in events
                            if e[0] == "X" and lo <= e[4] and e[4] + e[5] <= hi]
            out["prompt_tokens"] = {f"prefill#{t.handle.rid}": len(t.req.prompt) - 1
                                    for t in self.tracked if t.handle is not None}
        return out

    # -- after the window ----------------------------------------------------------
    def release(self) -> None:
        self.engine.close()
        self.engine.session.runtime.close()
        self.engine = None
        gc.unfreeze()
        gc.collect()

    def sample(self) -> List[Tracked]:
        """The longest finished request, then others in a seeded order,
        until the sample holds enough served tokens."""
        done = [t for t in self.tracked if t.handle is not None and t.handle.done]
        if not done:
            return []
        spec = self.traffic["sample"]
        longest = max(done, key=lambda t: (len(t.handle.generated), len(t.req.prompt)))
        rest = [t for t in done if t is not longest]
        order = np.random.default_rng([int(self.seed), 2]).permutation(len(rest))
        pick, tokens = [longest], len(longest.handle.generated)
        for i in order:
            if tokens >= spec["min_tokens"] or len(pick) >= spec["max_requests"]:
                break
            pick.append(rest[i])
            tokens += len(rest[i].handle.generated)
        return pick

    def reference(self, mode: str = "f32") -> Reference:
        e = self.eng_cfg
        return Reference(self.cfg, self.weights, mode=mode,
                         seq_len=e["page_size"] * e["max_pages_per_seq"],
                         n_rows=self.traffic["output_tokens"]["max"])

    def check(self) -> Dict[str, Dict[str, float]]:
        ref = self.reference()
        return judge([g for t in self.sample()
                      for g in served_gaps(ref, t.req.prompt, t.handle.generated)])
