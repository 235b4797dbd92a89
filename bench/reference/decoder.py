"""Plain dense GQA decoder (the llama layout yi-9b uses), in float32.

It follows the published description: RMSNorm, rotary embeddings on
half-split heads, grouped-query causal attention, a SwiGLU MLP, an
untied output head.  It uses no kernel, cache or batching of the system
under test, and imports nothing of it.  Matrix products run at
``jax.default_matmul_precision("highest")``: a TPU would otherwise round
float32 operands to bfloat16.

``mode="fp8"`` is the control: every matrix product takes its operands
rounded to float8 e4m3 with one scale per tensor, the precision one step
below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["make_weights", "Reference", "served_gaps"]

WEIGHT_NAMES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                "w_gate", "w_up", "w_down")


def _sizes(cfg: Dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d, f, q, kv


def make_weights(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded random weights in the stored dtype, made on the device in
    one jitted call: per layer (stacked on a leading axis) the norms and
    projections, then the embedding, the final norm and the head.
    Projections are uniform in +-1/sqrt(fan_in); embedding rows have
    unit RMS; norm scales are 1."""
    d, f, q, kv = _sizes(cfg)
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    dt = jnp.dtype(cfg["torch_dtype"])
    shapes = {"wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
              "wo": (n, q, d), "w_gate": (n, d, f), "w_up": (n, d, f),
              "w_down": (n, f, d), "head": (d, v)}

    def gen(key):
        keys = jax.random.split(key, len(shapes) + 1)
        out = {}
        for k, (name, shape) in zip(keys, shapes.items()):
            lim = 1.0 / math.sqrt(shape[-2])
            out[name] = jax.random.uniform(k, shape, dt, -lim, lim)
        out["embed"] = jax.random.uniform(keys[-1], (v, d), dt,
                                          -math.sqrt(3.0), math.sqrt(3.0))
        for name in ("attn_norm", "mlp_norm"):
            out[name] = jnp.ones((n, d), dt)
        out["final_norm"] = jnp.ones((d,), dt)
        return out

    key = jax.random.fold_in(jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    return jax.jit(gen)(key)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(x):
    """x rounded to float8 e4m3 under one per-tensor scale, back in f32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class Reference:
    """Logits of whole sequences, one layer at a time so that only one
    layer's float32 weights are live."""

    def __init__(self, cfg: Dict, weights: Dict[str, jax.Array], *,
                 seq_len: int, n_rows: int, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference mode {mode!r}")
        self.cfg, self.w, self.mode = cfg, weights, mode
        # every sequence is padded to one length and one row count, so
        # one program serves them all; padding sits after the tokens and
        # causal attention keeps it out of the rows read
        self.seq_len, self.n_rows = seq_len, n_rows
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.hd = cfg["head_dim"]
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _mm(self, a, w):
        w = w.astype(jnp.float32)
        if self.mode == "fp8":
            a, w = _fp8(a), _fp8(w)
        return a @ w

    def _layer_fn(self, x, lw):
        cfg = self.cfg
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        s = x.shape[0]
        pos = jnp.arange(s)
        h = _rms(x, lw["attn_norm"].astype(jnp.float32), eps)
        q = self._mm(h, lw["wq"]).reshape(s, self.heads, self.hd)
        k = self._mm(h, lw["wk"]).reshape(s, self.kv_heads, self.hd)
        v = self._mm(h, lw["wv"]).reshape(s, self.kv_heads, self.hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = self.heads // self.kv_heads
        qg = q.reshape(s, self.kv_heads, g, self.hd)
        scores = jnp.einsum("skgd,tkd->kgst", qg, k) / math.sqrt(self.hd)
        scores = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :],
                           scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("kgst,tkd->skgd", p, v).reshape(s, self.heads * self.hd)
        x = x + self._mm(attn, lw["wo"])
        h = _rms(x, lw["mlp_norm"].astype(jnp.float32), eps)
        up = jax.nn.silu(self._mm(h, lw["w_gate"])) * self._mm(h, lw["w_up"])
        return x + self._mm(up, lw["w_down"])

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
            for i in range(self.cfg["num_hidden_layers"]):
                x = self._layer(x, {k: self.w[k][i] for k in WEIGHT_NAMES})
            out = self._head(x[jnp.asarray(idx)], self.w["final_norm"],
                             self.w["head"])
        return np.asarray(out, np.float32)[:len(rows)]


def served_gaps(ref: Reference, prompt: Sequence[int], served: Sequence[int],
                control: Reference = None) -> List[float]:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where they agree).  With
    ``control``, the gap of the token the control puts first instead."""
    seq = list(prompt) + list(served[:-1])
    rows = list(range(len(prompt) - 1, len(seq)))
    want = ref.logits(seq, rows)
    picks = (np.asarray(served) if control is None
             else control.logits(seq, rows).argmax(-1))
    best = want.max(-1)
    return [float(b - w[t]) for b, w, t in zip(best, want, picks)]
