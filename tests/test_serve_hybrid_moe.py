"""Sliding-window / full attention layers with sparse experts (family
``swa_moe``, Mellum2's layout) served through the Session engine, at a
small size on the CPU: YaRN frequencies, routed experts with nothing
dropped, the window pool's rings, routing counts and counters, and the
engine's logits against the plain float32 reference."""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.moe import ops as moe_ops
from repro.kernels.moe import ref as moe_ref
from repro.models import layers as L
from repro.serve import session_engine
from repro.serve.engine import ServeEngine
from repro.serve.session_engine import SessionServeEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
for _p in (BENCH, BENCH / "drivers"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from reference.moe_decoder import Reference, make_weights  # noqa: E402
from serve_moe import arch_config, program_params  # noqa: E402

MELLUM = json.loads((BENCH / "configs" / "mellum2-12b-8l.json").read_text())
SEED = 2**31 + 1515


def small(dtype="float32", **over):
    """Hidden 64, the published 3:1 pattern over 8 layers, 16 experts
    top-4, a window of 16 keys."""
    cfg = dict(MELLUM)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
               sliding_window=16, vocab_size=256, torch_dtype=dtype, compute_dtype=dtype)
    cfg.update(over)
    return cfg


def engine(cfg, weights, *, arch=None, max_batch=2, page_size=4, max_pages=16, **kw):
    return SessionServeEngine(
        arch or arch_config(cfg), program_params(weights), max_batch=max_batch,
        page_size=page_size, num_pages=max_batch * max_pages + 1,
        pages_per_group=max_batch * max_pages + 1, max_pages_per_seq=max_pages,
        arena_bytes=16 << 20, **kw)


def test_yarn_frequencies_match_the_formula():
    """transformers' ``_compute_yarn_parameters``, transcribed: the
    correction range of beta_fast and beta_slow rotations over the
    original context, floored and ceiled, and a linear ramp between
    interpolated (plain / factor) and extrapolated (plain) frequencies."""
    cfg = get_config("mellum2_12b_a2_5b")
    y = cfg.full_rope_yarn
    dim, base = 128, 500_000.0
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)

    def corr(n_rot):
        return (dim * math.log(y.original_max_position / (n_rot * 2 * math.pi))) / (
            2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra_factor = 1 - ramp
    want = (1 / (16 * pos_freqs)) * (1 - extra_factor) + (1 / pos_freqs) * extra_factor

    freqs, scale = L.layer_rope(cfg, "full_attention")
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert scale == pytest.approx(0.1 * math.log(16) + 1)  # YaRN's default factor
    plain, one = L.layer_rope(cfg, "sliding_attention")
    np.testing.assert_allclose(plain, 1 / pos_freqs, rtol=1e-12)
    assert one == 1.0
    # the fastest dims keep the plain frequency, the slowest are scaled by 1/16
    assert freqs[0] == pytest.approx(1.0) and freqs[-1] == pytest.approx(plain[-1] / 16)


def test_mellum_config_is_published_widths():
    cfg = get_config("mellum2_12b_a2_5b")
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_) == (2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.window) == (64, 8, 896, 1024)
    assert cfg.layer_types == tuple(MELLUM["layer_types"]) and cfg.n_layers == 28


def _rows(rng, b, d):
    return jnp.asarray(rng.standard_normal((b, d)), jnp.float32)


@pytest.mark.parametrize("skew", [False, True], ids=["spread", "one-expert-takes-all"])
def test_every_token_gets_all_its_experts(skew):
    """No capacity, nothing dropped: each row's output is the sum of its
    k experts weighted by the renormalised router, even when one expert
    is every row's first choice."""
    rng = np.random.default_rng(3)
    B, D, F, E, K = 6, 32, 16, 8, 3
    h = _rows(rng, B, D)
    router = jnp.asarray(rng.standard_normal((D, E)), jnp.float32)
    if skew:
        router = router.at[:, 2].set(h.sum(0) * 10.0)  # expert 2 wins every row
    p = {"router": router,
         "w_gate": jnp.asarray(rng.standard_normal((E, D, F)) / 6, jnp.float32),
         "w_in": jnp.asarray(rng.standard_normal((E, D, F)) / 6, jnp.float32),
         "w_out": jnp.asarray(rng.standard_normal((E, F, D)) / 4, jnp.float32)}
    active = jnp.asarray([True] * 5 + [False])
    y, counts = moe_ops.moe_mlp(h, p, K, active)
    y = np.asarray(y)
    probs = np.asarray(jax.nn.softmax(h @ router, axis=-1))
    for b in range(B):
        top = np.argsort(-probs[b])[:K]
        w = probs[b, top] / probs[b, top].sum()
        want = np.zeros(D)
        for e, we in zip(top, w):
            g, u = np.asarray(h[b] @ p["w_gate"][e]), np.asarray(h[b] @ p["w_in"][e])
            want += we * (g / (1 + np.exp(-g)) * u) @ np.asarray(p["w_out"][e])
        if bool(active[b]):
            # f32 products of order 1, summed over 32 and 16 terms
            np.testing.assert_allclose(y[b], want, rtol=1e-4, atol=1e-5)
        else:
            assert not y[b].any()  # an inactive row is routed nowhere
    assert int(counts.sum()) == 5 * K
    if skew:
        assert int(counts[2]) == 5


def test_kernel_reads_only_chosen_experts_and_matches_the_oracle():
    rng = np.random.default_rng(4)
    B, D, F, E = 4, 32, 16, 8
    x = _rows(rng, B, D).astype(jnp.bfloat16)
    comb = np.zeros((B, E), np.float32)
    comb[0, 5], comb[1, 1], comb[2, 5], comb[3, 6] = 1.0, 0.5, 0.25, 0.75
    counts = jnp.asarray((comb > 0).sum(0), jnp.int32)
    ids, n = moe_ops.expert_order(counts)
    assert ids.tolist() == [1, 5, 6, 6, 6, 6, 6, 6] and n.tolist() == [3]
    w = [jnp.asarray(rng.standard_normal(s) / 4, jnp.bfloat16)
         for s in ((E, D, F), (E, D, F), (E, F, D))]
    # unchosen experts hold NaN: a kernel that read them would show it
    w = [a.at[jnp.asarray([0, 2, 3, 4, 7])].set(jnp.nan) for a in w]
    got = moe_ops.moe_experts(x, jnp.asarray(comb), ids, n, *w)
    clean = [jnp.nan_to_num(a) for a in w]
    want = moe_ref.moe_experts(x, jnp.asarray(comb), ids, n, *clean)
    # bf16 activations between the f32-accumulated products
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_legacy_engine_keeps_rejecting_the_family():
    cfg = small()
    w = make_weights(cfg, SEED)
    with pytest.raises(ValueError, match="full-attention dense decoder"):
        ServeEngine(arch_config(cfg), program_params(w))


def test_window_pool_rings_at_the_real_geometry():
    """Window 1,024 in pages of 16: a ring of 65 pages per sequence,
    whatever its length, charged to its tenant in the window pool and
    freed at completion; the full pool pages the whole sequence."""
    cfg = small(sliding_window=1024)
    w = make_weights(cfg, SEED)
    eng = engine(cfg, w, page_size=16, max_pages=8)
    with eng:
        assert eng.kv_window.pool.num_pages == 2 * 65 + 1
        eng.tenant("t", quota_pages=100)
        a = eng.submit([5, 6, 7], 4, tenant="t")
        eng.step()
        assert eng.kv_window.used_pages == 1 + 65  # scratch + one ring
        assert eng.kv.used_pages == 1 + 1  # 7 tokens in one 16-token page
        assert eng.kv_window.pool.tenant_pages("t") == 65
        assert eng.kv.pool.tenant_pages("t") == 1
        # a second ring would pass the tenant's quota of 100 window pages
        b = eng.submit([8, 9], 2, tenant="t")
        eng.step()
        assert eng.slot_req.count(None) == 1 and b in eng.waiting
        eng.run()
        assert a.done and b.done
        assert eng.kv_window.used_pages == 1 and eng.kv.used_pages == 1
        assert eng.kv_window.pool.tenant_pages("t") == 0


def _program_logits(monkeypatch):
    """Host copies of the step's logits, one (batch, vocab) array per
    step program call, in call order."""
    seen = []
    real = L.lm_logits

    def spy(cfg, params, x):
        out = real(cfg, params, x)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), out[:, 0], ordered=True)
        return out

    monkeypatch.setattr(L, "lm_logits", spy)
    session_engine._jit_hybrid_step.cache_clear()
    return seen


@pytest.fixture
def fresh_programs():
    session_engine._jit_hybrid_step.cache_clear()
    yield
    session_engine._jit_hybrid_step.cache_clear()


def _per_position(seen, eng, n_prompt):
    """One row of logits per position served, from the step calls' logits
    of one request in slot 0: the prefill's calls carry a position in
    each of their first ``prefill_rows`` rows, then every decode call one
    in slot 0."""
    n_pre, rows = n_prompt - 1, eng.prefill_rows
    calls = -(-n_pre // rows)
    pre = [a[r] for a in seen[:calls] for r in range(rows)][:n_pre]
    return np.stack(pre + [a[0] for a in seen[calls:]]), calls


def _serve_one(cfg, w, prompt, n_new, **kw):
    with engine(cfg, w, **kw) as eng:
        req = eng.submit(prompt, n_new)
        eng.run()
        return req, eng


def test_engine_logits_match_the_reference(monkeypatch, fresh_programs):
    """Prefill, then decode through both pools, past the point where the
    window rings wrap (ring of 5 pages of 4 = 20 slots; prompt 23, then
    17 more), against the reference's full forward pass in float32."""
    cfg = small()
    w = make_weights(cfg, SEED)
    seen = _program_logits(monkeypatch)
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, 256, 23)]
    req, eng = _serve_one(cfg, w, prompt, 18)
    seq = prompt + req.generated[:-1]
    got, calls = _per_position(seen, eng, len(prompt))
    assert eng.prefill_rows == 2 and calls == 11  # 22 prompt positions, 2 a call
    assert len(seen) == calls + 18 and len(got) == len(seq)
    ref = Reference(cfg, w, seq_len=64, n_rows=64)
    want = ref.logits(seq, list(range(len(seq))))
    # float32 throughout; the program sums attention over ring slots and
    # experts in another order than the reference: a few ulps of logits
    # of order 1 after 8 layers
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    assert req.generated == [int(t) for t in want[len(prompt) - 1:].argmax(-1)]


def test_bfloat16_engine_stays_near_the_reference(monkeypatch, fresh_programs):
    """The configuration's own precision: bfloat16 weights, activations
    and KV, against the float32 reference on the same bfloat16 weights."""
    cfg = small("bfloat16")
    w = make_weights(cfg, SEED + 1)
    seen = _program_logits(monkeypatch)
    prompt = [int(t) for t in np.random.default_rng(2).integers(0, 256, 21)]
    req, eng = _serve_one(cfg, w, prompt, 20)
    seq = prompt + req.generated[:-1]
    got, calls = _per_position(seen, eng, len(prompt))
    assert len(seen) == calls + 20 and len(got) == len(seq)
    want = Reference(cfg, w, seq_len=64, n_rows=64).logits(seq, list(range(len(seq))))
    # logits of order 1 through 8 bf16 layers (2**-8 relative each) and
    # an occasional router near-tie that flips one expert
    err = np.abs(got - want)
    assert float(np.median(err)) < 0.02 and float(err.max()) < 0.25


def test_routing_counts_and_counters_follow_the_traffic(fresh_programs):
    """In float32, the engine's routing counts equal the reference
    router's choices at every position; the counters add them up, hold
    the window ring for each live sequence and count its wraps."""
    cfg = small()
    w = make_weights(cfg, SEED + 2)
    eng = engine(cfg, w)
    ring = 5 * 4
    with eng:
        prompt = [int(t) for t in np.random.default_rng(5).integers(0, 256, 24)]
        req = eng.submit(prompt, 20)
        per_step = []
        while not req.done:
            eng.step()
            per_step.append(eng.last_routing)
        seq = prompt + req.generated[:-1]
        routes = Reference(cfg, w, seq_len=64, n_rows=64).routes(seq)  # (layers, S, k)

        def hist(positions):
            out = np.zeros((8, 16), np.int64)
            for li in range(8):
                np.add.at(out[li], routes[li, positions].ravel(), 1)
            return out

        (n_prompt, pre), = per_step[0]["prefill"]
        assert n_prompt == 24
        np.testing.assert_array_equal(pre, hist(list(range(23))))
        for i, r in enumerate(per_step):
            np.testing.assert_array_equal(r["decode"], hist([23 + i]))
        m = {k: v["value"] for k, v in eng.session.metrics.snapshot().items()
             if v["type"] == "counter"}
        n_tok = len(seq)
        assert all(m[f"moe/{li}/tokens_routed"] == 4 * n_tok for li in range(8))
        hits = [int((pre[li] > 0).sum()) + sum(int((r["decode"][li] > 0).sum())
                                               for r in per_step) for li in range(8)]
        assert [m[f"moe/{li}/experts_hit"] for li in range(8)] == hits
        assert all(4 * len(per_step) <= h for h in hits)  # each step reads its 4
        assert m["kv/window/pages_held"] == 5 * len(per_step)
        assert m["kv/full/pages_held"] == 11 * len(per_step)  # 44 tokens in pages of 4
        written = range(1, n_tok)  # position 0 never wraps
        assert m["kv/window/ring_wraps"] == sum(1 for p in written if p % ring == 0) == 2


def _decode_one_tenant_at_a_time(eng):
    """Make ``eng`` decode each tenant's rows in a masked call of its own,
    and its routing counts the calls' sum; returns the number of tenants
    of each decoding step.

    A masked row still writes its keys into its own pages, computed
    without attention or experts; so, as in a step of per-tenant calls,
    a tenant's rows go into the later calls already advanced to the next
    position, which their next step writes again before reading it."""
    real, seen = eng._decode_substep, []

    def per_tenant(mask, client):
        tok, pos = eng.slot_tok.copy(), eng.slot_pos.copy()
        out, total = np.zeros_like(tok), 0
        tenants = dict.fromkeys(eng.slot_req[s].tenant for s in np.flatnonzero(mask))
        for name in tenants:
            own = mask & np.array([r is not None and r.tenant == name for r in eng.slot_req])
            out[own] = real(own, client)[own]
            total = total + eng.last_routing["decode"]
            eng.slot_tok[own], eng.slot_pos[own] = out[own], pos[own] + 1
        eng.slot_tok[:], eng.slot_pos[:] = tok, pos  # the engine advances them
        eng.last_routing["decode"] = total
        seen.append(len(tenants))
        return out

    eng._decode_substep = per_tenant
    return seen


def test_two_tenants_decode_in_one_task_as_if_one_at_a_time(fresh_programs):
    """With two tenants in flight together, each step is one decode task;
    its tokens and routing counts equal those of the same requests
    decoded one tenant at a time."""
    cfg = small()
    w = make_weights(cfg, SEED + 3)
    rng = np.random.default_rng(6)
    work = [([int(t) for t in rng.integers(0, 256, n)], k, name)
            for n, k, name in ((3, 6, "a"), (5, 4, "b"), (2, 7, "a"), (4, 5, "b"))]
    runs = []
    for split in (False, True):
        with engine(cfg, w, max_batch=4) as eng:
            seen = _decode_one_tenant_at_a_time(eng) if split else None
            reqs = [eng.submit(p, k, tenant=name) for p, k, name in work]
            routing = []
            while not all(r.done for r in reqs):
                if eng.step():
                    routing.append(eng.last_routing["decode"])
            decodes = sum(1 for n, _ in eng.session.runtime.task_log
                          if n.startswith("llm_decode"))
            runs.append(([r.generated for r in reqs], routing, decodes))
    (tokens, routing, decodes), (want, want_routing, split_decodes) = runs
    assert max(seen) == 2 and split_decodes == sum(seen) > len(seen)
    assert decodes == len(routing) == len(seen)
    assert tokens == want
    for got, summed in zip(routing, want_routing):
        np.testing.assert_array_equal(got, summed)


def test_dense_family_keeps_its_step_program():
    """A dense model's step still comes from ``_jit_grouped_step`` with
    one block table and no routing output."""
    cfg = dataclasses.replace(get_config("llama3_8b").smoke(), dtype="float32")
    from repro.models import build_model

    params = build_model(cfg).init(jax.random.key(0))
    with SessionServeEngine(cfg, params, max_batch=2, page_size=8, num_pages=32,
                            max_pages_per_seq=4, pages_per_group=8) as eng:
        assert not eng.hybrid and eng.kvs == [eng.kv] and len(eng.tables) == 1
        r = eng.submit([3, 4, 5], 3)
        eng.run()
        assert r.done and len(r.generated) == 3
        assert not any(k.startswith(("moe/", "kv/")) for k in eng.session.metrics.snapshot())
