"""Plain decoder with sliding-window and full attention layers and
sparse-expert MLPs (Mellum2's layout), in float32.

It follows the published configuration: pre-norm RMSNorm residual
blocks; rotary embeddings on half-split heads, YaRN on full attention
layers (as transformers' ``_compute_yarn_parameters`` computes it, with
cos and sin scaled by ``attention_factor``) and plain RoPE on sliding
ones; grouped-query causal attention, sliding layers masked to the
``sliding_window`` keys ending at the query; a softmax router over the
experts, top ``num_experts_per_tok`` renormalised to sum to 1
(``norm_topk_prob``), SwiGLU experts; an untied output head.  Every
expert is applied to every token and weighted by the router (0 off a
token's top-k).  It uses no kernel, cache or batching of the system
under test, and imports nothing of it.  Matrix products run at
``jax.default_matmul_precision("highest")``.

``mode="fp8"`` is the control: every matrix product (router and
experts included) takes its operands rounded to float8 e4m3 with one
scale per tensor, the precision one step below the bfloat16 the
configuration serves in.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference.decoder import _fp8, _rms

__all__ = ["make_weights", "Reference", "layer_kinds", "yarn_freqs"]


def layer_kinds(cfg: Dict) -> List[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def make_weights(cfg: Dict, seed: int) -> Dict:
    """Seeded random weights in the stored dtype, made on the device one
    layer per jitted call: ``layers`` (a dict per layer: norms, q/k/v/o,
    router, and the experts' gate, up and down stacked over experts),
    then the embedding, the final norm and the head.  Projections are
    uniform in +-1/sqrt(fan_in); embedding rows have unit RMS; norm
    scales are 1."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd, e, f = cfg["head_dim"], cfg["num_experts"], cfg["moe_intermediate_size"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    dt = jnp.dtype(cfg["torch_dtype"])
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
              "router": (d, e), "w_gate": (e, d, f), "w_up": (e, d, f),
              "w_down": (e, f, d)}

    def uniform(key, shape):
        lim = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, dt, -lim, lim)

    @jax.jit
    def layer(key):
        keys = jax.random.split(key, len(shapes))
        out = {name: uniform(k, shape) for k, (name, shape) in zip(keys, shapes.items())}
        out["attn_norm"] = jnp.ones((d,), dt)
        out["mlp_norm"] = jnp.ones((d,), dt)
        return out

    @jax.jit
    def outer(key):
        k1, k2 = jax.random.split(key)
        return {"embed": jax.random.uniform(k1, (v, d), dt, -math.sqrt(3.0), math.sqrt(3.0)),
                "head": uniform(k2, (d, v)), "final_norm": jnp.ones((d,), dt)}

    key = jax.random.fold_in(jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    keys = jax.random.split(key, cfg["num_hidden_layers"] + 1)
    out = outer(keys[-1])
    out["layers"] = [layer(k) for k in keys[:-1]]
    return out


def yarn_freqs(head_dim: int, theta: float, rope: Dict) -> np.ndarray:
    """YaRN frequencies of a ``rope_type: "yarn"`` entry (transformers'
    ``_compute_yarn_parameters``, ``truncate`` true)."""
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    orig = rope["original_max_position_embeddings"]

    def dim(rot):
        return head_dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # share of the unscaled frequency
    return (1.0 / (rope["factor"] * base)) * (1.0 - keep) + (1.0 / base) * keep


def _rope(x, pos, freqs, scale):
    """x: (S, H, hd); rotate the two halves of each head."""
    ang = pos[:, None, None].astype(jnp.float32) * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """Logits of whole sequences, one layer at a time so that only one
    layer's float32 weights are live."""

    def __init__(self, cfg: Dict, weights: Dict, *, seq_len: int, n_rows: int,
                 mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference mode {mode!r}")
        self.cfg, self.w, self.mode = cfg, weights, mode
        # every sequence is padded to one length and one row count, so
        # one program per layer type serves them all; padding sits after
        # the tokens and causal attention keeps it out of the rows read
        self.seq_len, self.n_rows = seq_len, n_rows
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.hd = cfg["head_dim"]
        ropes = cfg["rope_parameters"]
        self.rope = {}
        for kind, r in ropes.items():
            if r["rope_type"] == "yarn":
                self.rope[kind] = (yarn_freqs(self.hd, r["rope_theta"], r),
                                   r["attention_factor"])
            else:
                base = r["rope_theta"] ** (np.arange(0, self.hd, 2) / self.hd)
                self.rope[kind] = (1.0 / base, 1.0)
        self._layer = {kind: jax.jit(lambda x, lw, kind=kind: self._layer_fn(x, lw, kind))
                       for kind in ropes}
        self._head = jax.jit(self._head_fn)

    def _q(self, x):
        return _fp8(x) if self.mode == "fp8" else x

    def _mm(self, a, w):
        return self._q(a) @ self._q(w.astype(jnp.float32))

    def _attention(self, h, lw, kind):
        s = h.shape[0]
        pos = jnp.arange(s)
        q = self._mm(h, lw["wq"]).reshape(s, self.heads, self.hd)
        k = self._mm(h, lw["wk"]).reshape(s, self.kv_heads, self.hd)
        v = self._mm(h, lw["wv"]).reshape(s, self.kv_heads, self.hd)
        freqs, scale = self.rope[kind]
        q, k = _rope(q, pos, freqs, scale), _rope(k, pos, freqs, scale)
        g = self.heads // self.kv_heads
        qg = q.reshape(s, self.kv_heads, g, self.hd)
        scores = jnp.einsum("skgd,tkd->kgst", qg, k) / math.sqrt(self.hd)
        back = pos[:, None] - pos[None, :]  # query position minus key position
        mask = back >= 0
        if kind == "sliding_attention":
            mask &= back < self.cfg["sliding_window"]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("kgst,tkd->skgd", p, v).reshape(s, self.heads * self.hd)
        return self._mm(attn, lw["wo"])

    def _moe(self, h, lw):
        cfg = self.cfg
        probs = jax.nn.softmax(self._mm(h, lw["router"]), axis=-1)
        vals, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
        if cfg["norm_topk_prob"]:
            vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
        rows = jnp.arange(h.shape[0])[:, None]
        comb = jnp.zeros_like(probs).at[rows, idx].set(vals)
        hq = self._q(h)
        gate = jnp.einsum("sd,edf->sef", hq, self._q(lw["w_gate"].astype(jnp.float32)))
        up = jnp.einsum("sd,edf->sef", hq, self._q(lw["w_up"].astype(jnp.float32)))
        act = self._q(jax.nn.silu(gate) * up)
        out = jnp.einsum("sef,efd->sed", act, self._q(lw["w_down"].astype(jnp.float32)))
        return jnp.einsum("se,sed->sd", comb, out)

    def _layer_fn(self, x, lw, kind):
        eps = self.cfg["rms_norm_eps"]
        x = x + self._attention(_rms(x, lw["attn_norm"].astype(jnp.float32), eps), lw, kind)
        return x + self._moe(_rms(x, lw["mlp_norm"].astype(jnp.float32), eps), lw)

    def _head_fn(self, x, norm, head):
        return self._mm(_rms(x, norm.astype(jnp.float32), self.cfg["rms_norm_eps"]), head)

    def logits(self, tokens: Sequence[int], rows: Sequence[int]) -> np.ndarray:
        """float32 logits at positions ``rows`` of the causal forward over
        ``tokens``."""
        if len(tokens) > self.seq_len or len(rows) > self.n_rows:
            raise ValueError(f"{len(tokens)} tokens / {len(rows)} rows exceed "
                             f"{self.seq_len} / {self.n_rows}")
        toks = np.zeros(self.seq_len, np.int32)
        toks[:len(tokens)] = tokens
        idx = np.zeros(self.n_rows, np.int32)
        idx[:len(rows)] = rows
        with jax.default_matmul_precision("highest"):
            x = self.w["embed"][jnp.asarray(toks)].astype(jnp.float32)
            for kind, lw in zip(layer_kinds(self.cfg), self.w["layers"]):
                x = self._layer[kind](x, lw)
            out = self._head(x[jnp.asarray(idx)], self.w["final_norm"], self.w["head"])
        return np.asarray(out, np.float32)[:len(rows)]

    def routes(self, tokens: Sequence[int]) -> np.ndarray:
        """(layers, len(tokens), k) experts the router picks for each
        token of the causal forward, in descending weight."""
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(self.w["embed"])[jnp.asarray(tokens)].astype(jnp.float32)
            out = []
            eps = self.cfg["rms_norm_eps"]
            for kind, lw in zip(layer_kinds(self.cfg), self.w["layers"]):
                x = x + self._attention(_rms(x, lw["attn_norm"].astype(jnp.float32), eps),
                                        lw, kind)
                h = _rms(x, lw["mlp_norm"].astype(jnp.float32), eps)
                probs = jax.nn.softmax(self._mm(h, lw["router"]), axis=-1)
                out.append(jax.lax.top_k(probs, self.cfg["num_experts_per_tok"])[1])
                x = x + self._moe(h, lw)
        return np.asarray(jnp.stack(out))
