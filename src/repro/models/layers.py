"""Model primitives: norms, RoPE, GQA attention (chunked), MLPs, embeddings.

Conventions
-----------
* Params are plain dicts of ``jnp`` arrays, stored in ``param_dtype``
  (fp32) and cast to the compute dtype (bf16) at use.
* Softmax / norm statistics are computed in fp32.
* Full-sequence attention is *row-chunked* over queries (``q_chunk``):
  per chunk the full key range (or the local window slice) is scored and
  softmaxed — memory O(chunk × S) instead of O(S²).  The chunk loop is a
  ``lax.scan`` with an ``unroll_all`` escape hatch used by the roofline
  probes (DESIGN.md: scan bodies are counted once by XLA cost analysis,
  so probes compile fully unrolled).
* GQA: KV heads are repeated by the smallest factor making them
  shardable over the tensor-model axis (DESIGN.md §5); when no factor
  works (e.g. 40-head MHA on a 16-wide axis) K/V switch to a
  sequence-sharded layout over the model axis (pjit boundary shardings
  must divide evenly, so padding is not an option for cache args).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_rules, shard

# ---------------------------------------------------------------------------
# small utils
# ---------------------------------------------------------------------------


def cdtype(cfg):
    return jnp.dtype(cfg.dtype)


def pdtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    scale = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def rms_norm(x, w, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def layer_norm(x, w, b, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w.astype(x.dtype) + b.astype(x.dtype)


def norm_apply(cfg, params, x):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, params["scale"])
    return layer_norm(x, params["scale"], params["bias"])


def norm_init(cfg, key):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), pdtype(cfg))}
    return {
        "scale": jnp.ones((cfg.d_model,), pdtype(cfg)),
        "bias": jnp.zeros((cfg.d_model,), pdtype(cfg)),
    }


def norm_spec(cfg):
    if cfg.norm == "rmsnorm":
        return {"scale": P()}
    return {"scale": P(), "bias": P()}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn):
    """YaRN's frequencies and its cos/sin scale (``attention_factor``),
    as transformers' ``_compute_yarn_parameters`` computes them: each
    frequency blends the plain one with the plain one over ``factor``,
    by a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position``."""
    def correction_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    plain = rope_freqs(head_dim, theta)
    freqs = plain / yarn.factor * (1 - extrapolation) + plain * extrapolation
    return freqs, yarn.attention_factor


def layer_rope(cfg, layer_type: str):
    """(frequencies, cos/sin scale) of one layer type's rotary embedding."""
    if layer_type == "full_attention" and cfg.full_rope_yarn is not None:
        return yarn_freqs(cfg.head_dim_, cfg.rope_theta, cfg.full_rope_yarn)
    return rope_freqs(cfg.head_dim_, cfg.rope_theta), 1.0


def apply_rope(x, pos, theta: float, *, freqs=None, scale: float = 1.0):
    """x: (..., S, H, D); pos: broadcastable to (..., S).  ``freqs``
    replaces the plain frequencies of ``theta``; ``scale`` multiplies
    cos and sin (YaRN's attention factor)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)
    freqs = jnp.asarray(freqs, jnp.float32)
    angles = pos[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def kv_repeat_factor(cfg) -> int:
    """Smallest r with (kv·r) % tp == 0 and heads % (kv·r) == 0, else 1."""
    rules = current_rules()
    axes = rules.axes_for("heads")
    tp = rules.mesh_size(axes) if axes else 1
    kv, h = cfg.n_kv_heads, cfg.n_heads
    if tp <= 1 or kv % tp == 0:
        return 1
    r = 1
    while kv * r < max(tp, h) + 1:
        if (kv * r) % tp == 0 and h % (kv * r) == 0:
            return r
        r += 1
    return 1  # fall back to uneven sharding / replication


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_q: int       # query heads
    n_kv: int      # stored KV heads (after repeat)
    group: int     # queries per stored KV head
    head_dim: int


def attn_dims(cfg) -> AttnDims:
    rep = kv_repeat_factor(cfg)
    n_kv = cfg.n_kv_heads * rep
    return AttnDims(cfg.n_heads, n_kv, cfg.n_heads // n_kv, cfg.head_dim_)


def kv_heads_shardable(cfg) -> bool:
    """True if the (repeated) KV head count divides the TP axis."""
    rules = current_rules()
    axes = rules.axes_for("kv_heads")
    tp = rules.mesh_size(axes) if axes else 1
    return tp <= 1 or attn_dims(cfg).n_kv % tp == 0


def divisor_chunk(s: int, target: int) -> int:
    """Largest chunk ≤ target that divides s (handles e.g. 3840 labels)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return c


def attention_init(cfg, key):
    d, hd = cfg.d_model, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd)),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd)),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd)),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), pdtype(cfg))
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), pdtype(cfg))
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), pdtype(cfg))
    return p


def attention_spec(cfg):
    s = {
        "wq": P("fsdp", "model"),
        "wk": P("fsdp", "model"),
        "wv": P("fsdp", "model"),
        "wo": P("model", "fsdp"),
    }
    if cfg.qkv_bias:
        s.update({"bq": P("model"), "bk": P("model"), "bv": P("model")})
    return s


def _project_qkv(cfg, params, x, pos, rope: bool = True):
    """x: (B,S,D) → q (B,S,Hq,hd), k/v (B,S,Hkv_eff,hd) with repeat."""
    dims = attn_dims(cfg)
    dt = x.dtype
    q = x @ params["wq"].astype(dt)
    k = x @ params["wk"].astype(dt)
    v = x @ params["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    B, S = x.shape[:2]
    q = q.reshape(B, S, dims.n_q, dims.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, dims.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, dims.head_dim)
    if rope and cfg.pos_embed == "rope":
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    rep = dims.n_kv // cfg.n_kv_heads
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    q = shard(q, "batch", "seq", "heads", None)
    if kv_heads_shardable(cfg):
        k = shard(k, "batch", "seq", "kv_heads", None)
        v = shard(v, "batch", "seq", "kv_heads", None)
    else:  # MHA-ish archs on a wider TP axis: sequence-sharded KV
        k = shard(k, "batch", "model", None, None)
        v = shard(v, "batch", "model", None, None)
    return q, k, v


def _chunk_attend(q_c, k, v, q_pos, k_pos, window: int):
    """One query chunk against a key range. Shapes:
    q_c (B,C,Hkv,G,hd); k,v (B,T,Hkv,hd); q_pos (C,), k_pos (T,).
    Causal + optional window mask. fp32 softmax."""
    scale = 1.0 / math.sqrt(q_c.shape[-1])
    scores = jnp.einsum(
        "bckgd,btkd->bkgct", q_c, k, preferred_element_type=jnp.float32
    ) * scale
    mask = k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgct,btkd->bckgd", probs.astype(q_c.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q_c.dtype)


def full_attention(cfg, q, k, v, *, pos0: int = 0, probe: bool = False):
    """Causal (optionally windowed) attention over a full sequence, row-
    chunked over queries. q: (B,S,Hq,hd) → (B,S,Hq*hd)."""
    dims = attn_dims(cfg)
    B, S = q.shape[:2]
    C = divisor_chunk(S, cfg.q_chunk)
    n_chunks = S // C
    qg = q.reshape(B, S, dims.n_kv, dims.group, dims.head_dim)

    win = cfg.window
    if win > 0 and win % C == 0 and S > win:
        # local attention: slice only the needed key range per chunk
        def chunk(i):
            q_c = jax.lax.dynamic_slice_in_dim(qg, i * C, C, axis=1)
            k0 = jnp.maximum(i * C - win, 0)
            span = win + C
            k_c = jax.lax.dynamic_slice_in_dim(k, k0, span, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v, k0, span, axis=1)
            q_pos = pos0 + i * C + jnp.arange(C)
            k_pos = pos0 + k0 + jnp.arange(span)
            return _chunk_attend(q_c, k_c, v_c, q_pos, k_pos, win)
    else:
        def chunk(i):
            q_c = jax.lax.dynamic_slice_in_dim(qg, i * C, C, axis=1)
            q_pos = pos0 + i * C + jnp.arange(C)
            k_pos = pos0 + jnp.arange(S)
            return _chunk_attend(q_c, k, v, q_pos, k_pos, win)

    if probe or n_chunks == 1:
        out = jnp.concatenate([chunk(i) for i in range(n_chunks)], axis=1)
    else:
        # Nested remat: recompute each chunk's probs in the backward pass
        # so only one chunk's (C×S) scores are ever live (flash-attention
        # memory behaviour on the XLA path).
        outs = jax.lax.map(jax.checkpoint(chunk), jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(
            B, S, dims.n_kv, dims.group, dims.head_dim
        )
    return out.reshape(B, S, dims.n_q * dims.head_dim)


def decode_attention(cfg, q, k_cache, v_cache, kv_len, *, apply_window=True):
    """Single-token attention. q: (B,1,Hq,hd); caches (B,Smax,Hkv,hd);
    kv_len: (B,) valid lengths (new token already written).
    ``apply_window=False`` for ring-buffer caches whose slots are already
    window-resident."""
    dims = attn_dims(cfg)
    B = q.shape[0]
    Smax = k_cache.shape[1]
    qg = q.reshape(B, 1, dims.n_kv, dims.group, dims.head_dim)
    scale = 1.0 / math.sqrt(dims.head_dim)
    scores = jnp.einsum(
        "bckgd,btkd->bkgct", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale  # (B,Hkv,G,1,Smax)
    t = jnp.arange(Smax)
    mask = t[None, :] < kv_len[:, None]  # (B,Smax)
    if cfg.window > 0 and apply_window:
        mask &= t[None, :] >= kv_len[:, None] - cfg.window
    scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgct,btkd->bckgd", probs.astype(q.dtype), v_cache,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return out.reshape(B, 1, dims.n_q * dims.head_dim)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(cfg, key, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act in ("swiglu", "geglu"):
        return {
            "w_in": dense_init(ks[0], (d, f)),
            "w_gate": dense_init(ks[1], (d, f)),
            "w_out": dense_init(ks[2], (f, d)),
        }
    return {
        "w_in": dense_init(ks[0], (d, f)),
        "b_in": jnp.zeros((f,), pdtype(cfg)),
        "w_out": dense_init(ks[2], (f, d)),
        "b_out": jnp.zeros((d,), pdtype(cfg)),
    }


def mlp_spec(cfg):
    if cfg.act in ("swiglu", "geglu"):
        return {"w_in": P("fsdp", "model"), "w_gate": P("fsdp", "model"),
                "w_out": P("model", "fsdp")}
    return {"w_in": P("fsdp", "model"), "b_in": P("model"),
            "w_out": P("model", "fsdp"), "b_out": P()}


def mlp_apply(cfg, params, x):
    dt = x.dtype
    if cfg.act in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.act == "swiglu" else jax.nn.gelu
        h = act(x @ params["w_gate"].astype(dt)) * (x @ params["w_in"].astype(dt))
        h = shard(h, "batch", "seq", "ff")
        return h @ params["w_out"].astype(dt)
    h = jax.nn.gelu(x @ params["w_in"].astype(dt) + params["b_in"].astype(dt))
    h = shard(h, "batch", "seq", "ff")
    return h @ params["w_out"].astype(dt) + params["b_out"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------


def embed_init(cfg, key):
    ks = jax.random.split(key, 3)
    p = {"table": dense_init(ks[0], (cfg.vocab, cfg.d_model)) * 0.02 * math.sqrt(cfg.d_model)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab))
    if cfg.pos_embed == "learned":
        # sized generously so assigned decode shapes (32k) fit
        p["pos"] = dense_init(ks[2], (65536, cfg.d_model)) * 0.02
    return p


def embed_spec(cfg):
    s = {"table": P("model", "fsdp")}
    if not cfg.tie_embeddings:
        s["head"] = P("fsdp", "model")
    if cfg.pos_embed == "learned":
        s["pos"] = P(None, "fsdp")
    return s


def embed_tokens(cfg, params, tokens, pos=None):
    x = jnp.take(params["table"], tokens, axis=0).astype(cdtype(cfg))
    if cfg.pos_embed == "learned" and pos is not None:
        x = x + jnp.take(params["pos"], pos, axis=0).astype(cdtype(cfg))
    return shard(x, "batch", "res_seq", "dmodel")


def lm_logits(cfg, params, x):
    if cfg.tie_embeddings:
        w = params["table"].astype(x.dtype).T
    else:
        w = params["head"].astype(x.dtype)
    logits = x @ w
    return shard(logits, "batch", "seq", "vocab")


def xent_loss(cfg, params, hidden, labels, *, probe: bool = False,
              chunk: int = 512):
    """Sequence-chunked softmax cross-entropy (keeps (B,C,V) logits
    bounded). hidden: (B,S,D); labels: (B,S) with -100 = ignore."""
    B, S, _ = hidden.shape
    C = divisor_chunk(S, chunk)
    n = S // C

    def piece(h_c, y_c):
        logits = lm_logits(cfg, params, h_c).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y_c, 0)[..., None], axis=-1
        )[..., 0]
        valid = (y_c >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * valid), jnp.sum(valid)

    if probe or n == 1:
        parts = [piece(hidden[:, i * C:(i + 1) * C], labels[:, i * C:(i + 1) * C])
                 for i in range(n)]
        tot = sum(p[0] for p in parts)
        cnt = sum(p[1] for p in parts)
    else:
        hs = hidden.reshape(B, n, C, -1).swapaxes(0, 1)
        ys = labels.reshape(B, n, C).swapaxes(0, 1)
        piece_ckpt = jax.checkpoint(piece)  # don't keep logits for bwd

        def body(acc, xs):
            h_c, y_c = xs
            l, c = piece_ckpt(h_c, y_c)
            return (acc[0] + l, acc[1] + c), None

        (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hs, ys))
    return tot / jnp.maximum(cnt, 1.0)
