"""Serving engine for a model with sliding-window and full attention
layers and sparse-expert MLPs: the open-loop harness of ``serve.py``
(same clock, window, drain, tails and sample), with the model's own
weights, reference, limit and work count.

Besides what ``serve.py`` reports, the per-layer material holds the
routing of every engine step (the distinct experts it read) and the
Session's KV counters: pages each pool held for live sequences, added
once per decoding step.
"""

from __future__ import annotations

import gc
import importlib.util
import sys
from pathlib import Path
from typing import Dict, List

import jax
import numpy as np

from moe_work import moe_shape
from reference.decoder import served_gaps
from reference.moe_decoder import Reference, make_weights


def _serve_module():
    """``drivers/serve.py``, loaded by path as the harness loads drivers
    (its directory is not on the import path)."""
    name = "bench_driver_serve"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, Path(__file__).with_name("serve.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


ServeDriver = _serve_module().Driver

#: mean gap, in logits, by which the sampled served tokens may lie below
#: the reference's best; the readings it rests on are in PERF.md
MEAN_LOGIT_GAP = 5e-4

#: the Session counters the driver reads over the window
COUNTERS = ("kv/full/pages_held", "kv/window/pages_held", "kv/window/ring_wraps",
            "serve_tokens_generated")


def judge(gaps: List[float]) -> Dict[str, Dict[str, float]]:
    """The number compared, with its limit; nothing sampled vouches for
    nothing."""
    value = float(np.mean(gaps)) if gaps else 1e30
    return {"mean_logit_gap": {"value": value, "limit": MEAN_LOGIT_GAP}}


def arch_config(cfg: Dict):
    """The program's configuration object for a configuration file."""
    from repro.configs.base import ArchConfig, YarnRope

    yarn = cfg["rope_parameters"]["full_attention"]
    slide = cfg["rope_parameters"]["sliding_attention"]
    if yarn["rope_theta"] != slide["rope_theta"] or slide["rope_type"] != "default":
        raise ValueError("the program takes one rope_theta and plain RoPE on sliding layers")
    return ArchConfig(
        name=cfg["name"], family="swa_moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        rope_theta=float(slide["rope_theta"]), n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], window=cfg["sliding_window"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        full_rope_yarn=YarnRope(
            factor=float(yarn["factor"]),
            original_max_position=int(yarn["original_max_position_embeddings"]),
            beta_fast=float(yarn["beta_fast"]), beta_slow=float(yarn["beta_slow"]),
            attention_factor=float(yarn["attention_factor"])),
        dtype=cfg["compute_dtype"], param_dtype=cfg["torch_dtype"])


def program_params(w: Dict) -> Dict:
    """The benchmark's weights in the program's per-layer layout (the
    same arrays: nothing is copied)."""
    return {
        "embed": {"table": w["embed"], "head": w["head"]},
        "final_norm": {"scale": w["final_norm"]},
        "layers": [{
            "norm1": {"scale": lw["attn_norm"]},
            "attn": {k: lw[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": lw["mlp_norm"]},
            "moe": {"router": lw["router"], "w_gate": lw["w_gate"],
                    "w_in": lw["w_up"], "w_out": lw["w_down"]},
        } for lw in w["layers"]],
    }


class Driver(ServeDriver):
    def __init__(self, cfg: Dict, traffic: Dict, seed: int, *, trace: bool):
        super().__init__(cfg, traffic, seed, trace=trace)
        # the program's configuration first: a program that cannot take
        # it fails here, before any weight is made
        self.arch = arch_config(cfg)
        self.moe = moe_shape(cfg)

    def setup(self) -> None:
        from repro.serve.session_engine import SessionServeEngine

        e = self.eng_cfg
        self.weights = make_weights(self.cfg, self.seed)
        jax.block_until_ready(self.weights)
        self.engine = SessionServeEngine(
            self.arch, program_params(self.weights),
            max_batch=e["max_batch"], page_size=e["page_size"],
            num_pages=e["num_pages"], pages_per_group=e["pages_per_group"],
            max_pages_per_seq=e["max_pages_per_seq"], allocator=e["allocator"],
            arena_bytes=e["arena_bytes"])
        if self.engine.kv_window.pool.num_pages != e["window_pages"]:
            raise ValueError(f"the engine's window pool has "
                             f"{self.engine.kv_window.pool.num_pages} pages, the "
                             f"configuration states {e['window_pages']}")
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
        for name in self.traffic["tenants"]:
            self.engine.submit([1, vocab - 1, 2], 2, tenant=name)
            self.engine.run()
        for name in self.traffic["tenants"]:
            self.engine.submit([3, 4], 2, tenant=name)
        self.engine.run()
        self._record_steps()
        gc.collect()
        gc.freeze()

    def _record_steps(self) -> None:
        """Keep the routing of every engine step, in step order."""
        eng, real = self.engine, self.engine.step
        self.routing = []

        def step():
            n = real()
            self.routing.append(eng.last_routing)
            return n

        eng.step = step

    def _counters(self) -> Dict[str, int]:
        m = self.engine.session.metrics
        return {name: m.counter(name).value for name in COUNTERS}

    def run_window(self, seconds: float) -> None:
        self.routing = []
        before = self._counters()
        super().run_window(seconds)
        after = self._counters()
        self.counters = {k: after[k] - before[k] for k in COUNTERS}

    def report(self, err) -> None:
        super().report(err)
        hit = [int(np.count_nonzero(r["decode"])) for r in self.routing
               if r["decode"] is not None]
        layers = self.cfg["num_hidden_layers"]
        print(f"bench: experts read per decoding step and layer "
              f"{np.mean(hit) / layers if hit else 0.0:.2f} of "
              f"{self.cfg['num_experts']} (mean); window ring wraps "
              f"{self.counters['kv/window/ring_wraps']}", file=err)

    def facts(self) -> Dict:
        out = super().facts()
        # the model's own work count in place of the dense one
        out["steps"] = [
            (s1 - s0,) + self.moe.step_work(r["prefill"], dec, r["decode"])
            for (s0, s1, adm, dec), r in zip(self.steps, self.routing) if adm or dec]
        c = self.counters
        kv = self.moe.kv_layer_bytes * self.cfg["engine"]["page_size"]
        kinds = self.moe.kinds
        out["kv"] = {
            "page_steps": {"full": c["kv/full/pages_held"],
                           "window": c["kv/window/pages_held"]},
            "page_bytes": {"full": kv * kinds.count("full_attention"),
                           "window": kv * kinds.count("sliding_attention")},
            "seq_steps": c["serve_tokens_generated"],
        }
        return out

    def reference(self, mode: str = "f32") -> Reference:
        e = self.eng_cfg
        return Reference(self.cfg, self.weights, mode=mode,
                         seq_len=e["page_size"] * e["max_pages_per_seq"],
                         n_rows=self.traffic["output_tokens"]["max"])

    def check(self) -> Dict[str, Dict[str, float]]:
        ref = self.reference()
        return judge([g for t in self.sample()
                      for g in served_gaps(ref, t.req.prompt, t.handle.generated)])
