"""Chunked prefill: a prompt taken in through the batch-wide decode step,
up to a batch width of prompt tokens a call, writes the KV that one
token a call writes, touches no other sequence's pages, keeps window
rings intact when the prompt wraps them, and stays one Session task."""

import dataclasses
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.paged_kv import ring_pages
from repro.models import build_model
from repro.models import layers as L
from repro.serve import session_engine
from repro.serve.engine import _paged_decode_step, _paged_hybrid_step, chunked_prefill
from repro.serve.session_engine import SessionServeEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
for _p in (BENCH, BENCH / "drivers"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from reference.moe_decoder import make_weights  # noqa: E402
from serve_moe import arch_config, program_params  # noqa: E402

MELLUM = json.loads((BENCH / "configs" / "mellum2-12b-8l.json").read_text())
SEED = 2**31 + 1616
BATCH, PAGE, MAX_PAGES, N_PAGES, SCRATCH = 4, 4, 8, 24, 0
#: the prefilling sequence's pages (slot 1), another live sequence's (slot 0)
PROMPT_PAGES, OTHER_PAGES = [5, 9, 2, 17, 11], [3, 7, 20]


def small_moe(**over):
    """Mellum2's 3:1 layer pattern over 8 layers at hidden 64, 16 experts
    top-4, a window of 16 keys, in float32."""
    cfg = dict(MELLUM)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
               sliding_window=16, vocab_size=256, torch_dtype="float32",
               compute_dtype="float32")
    cfg.update(over)
    return cfg


@functools.lru_cache(maxsize=None)
def _model(family):
    """(config, params, jitted step(pools, tables, tokens, pos, lengths) ->
    (routing counts or None, pools), pool shapes, block table widths)."""
    if family == "dense":
        cfg = dataclasses.replace(get_config("llama3_8b").smoke(), dtype="float32")
        params = build_model(cfg).init(jax.random.key(3))
        plane = (N_PAGES, PAGE, cfg.n_kv_heads, cfg.head_dim_)
        shapes = [(cfg.n_layers,) + plane] * 2

        @jax.jit
        def run(params, pools, tables, tokens, pos, lengths):
            _, k, v = _paged_decode_step(cfg, params, *pools, tables[0], tokens, pos,
                                         lengths)
            return None, (k, v)

        return cfg, params, functools.partial(run, params), shapes, (MAX_PAGES,)
    mcfg = small_moe()
    cfg = arch_config(mcfg)
    params = program_params(make_weights(mcfg, SEED))
    kinds = cfg.layer_types[:cfg.n_layers]
    plane = (N_PAGES, PAGE, cfg.n_kv_heads, cfg.head_dim_)
    n_full, n_win = kinds.count("full_attention"), kinds.count("sliding_attention")
    shapes = [(n_full,) + plane] * 2 + [(n_win,) + plane] * 2

    @jax.jit
    def run(params, pools, tables, tokens, pos, lengths):
        _, counts, *pools = _paged_hybrid_step(cfg, params, *pools, *tables, tokens, pos,
                                               lengths)
        return counts, tuple(pools)

    return (cfg, params, functools.partial(run, params), shapes,
            (MAX_PAGES, ring_pages(cfg.window, PAGE)))


def _tables(widths):
    """Block tables per pool: slot 0 another live sequence, slot 1 the
    prompt's, slots 2 and 3 idle on the scratch page.  The window pool's
    rings take the first pages of each sequence's list."""
    out = []
    for w in widths:
        t = np.full((BATCH, w), SCRATCH, np.int32)
        t[0, :min(w, len(OTHER_PAGES))] = OTHER_PAGES[:w]
        t[1, :min(w, len(PROMPT_PAGES))] = PROMPT_PAGES[:w]
        out.append(t)
    return out


def _rows_for(cfg):
    """The engine's rule: a batch width, and no more than a window ring's
    slots less the window."""
    if cfg.family != "swa_moe":
        return BATCH
    return min(BATCH, ring_pages(cfg.window, PAGE) * PAGE - cfg.window)


@pytest.mark.parametrize("family", ["dense", "swa_moe"])
@pytest.mark.parametrize("length", ["2", "R", "R+1", "3R+5"])
def test_chunked_prefill_writes_what_per_token_prefill_writes(family, length):
    cfg, _, step, shapes, widths = _model(family)
    rows = _rows_for(cfg)
    assert rows == 4
    n = {"2": 2, "R": rows, "R+1": rows + 1, "3R+5": 3 * rows + 5}[length]
    rng = np.random.default_rng(n)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab, n)][:-1]
    init = tuple(jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes)
    tables = _tables(widths)

    # the reference: one prompt token a call, in row 0 through the
    # prompt's tables, every other row idle on the scratch page
    pools, want_counts = init, None
    for i, tok in enumerate(prompt):
        rows0 = [np.where(np.arange(BATCH)[:, None] == 0, t[1], SCRATCH).astype(np.int32)
                 for t in tables]
        pos = np.array([i] + [0] * (BATCH - 1), np.int32)
        lengths = np.array([i + 1] + [0] * (BATCH - 1), np.int32)
        counts, pools = step(pools, rows0, np.array([tok] + [0] * (BATCH - 1), np.int32),
                             pos, lengths)
        want_counts = counts if want_counts is None else want_counts + counts
    want = [np.asarray(p) for p in pools]

    state = {"pools": init}

    def call(tbs, tokens, pos, lengths):
        counts, state["pools"] = step(state["pools"], tbs, tokens, pos, lengths)
        return counts

    calls, counts = chunked_prefill(call, tables, 1, [SCRATCH] * len(tables), prompt, rows)
    got = [np.asarray(p) for p in state["pools"]]
    assert calls == -(-len(prompt) // rows)
    if want_counts is None:
        assert counts is None
    else:
        np.testing.assert_array_equal(counts, want_counts)

    before = [np.asarray(p) for p in init]
    for pool, (g, w, b) in enumerate(zip(got, want, before)):
        full = family == "dense" or pool < 2
        table = tables[0 if full else 1][1]
        slot_of = {}  # (page, offset) of each prompt position in this pool
        for p in range(len(prompt)):
            q = p if full else p % (len(table) * PAGE)
            slot_of[(int(table[q // PAGE]), q % PAGE)] = p
        # each row's K/V is the step's own arithmetic on that row alone,
        # so the prompt's positions are bitwise equal to the per-token loop
        for page, off in slot_of:
            np.testing.assert_array_equal(g[:, page, off], w[:, page, off])
        # every page but the prompt's and the scratch page, and the
        # prompt's pages past the prompt, are untouched
        for page in range(N_PAGES):
            if page == SCRATCH:
                continue
            for off in range(PAGE):
                if (page, off) not in slot_of:
                    np.testing.assert_array_equal(g[:, page, off], b[:, page, off])
    # the other live sequence's pages in particular
    assert all(np.array_equal(g[:, OTHER_PAGES], b[:, OTHER_PAGES])
               for g, b in zip(got, before))


@pytest.fixture
def logits_spy(monkeypatch):
    """Host copies of every step call's (batch, vocab) logits, in call
    order, from freshly traced step programs."""
    seen = []
    real = L.lm_logits

    def spy(cfg, params, x):
        out = real(cfg, params, x)
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), out[:, 0], ordered=True)
        return out

    monkeypatch.setattr(L, "lm_logits", spy)
    session_engine._jit_hybrid_step.cache_clear()
    yield seen
    session_engine._jit_hybrid_step.cache_clear()


def test_ring_wrapping_prompt_matches_the_per_token_loop(logits_spy):
    """A prompt of 45 tokens wraps the window rings (5 pages of 4 = 20
    slots, window 16) twice.  At batch 8 the engine takes 20 − 16 = 4
    prompt tokens a call, not 8, and then serves the same logits as one
    prompt token a call."""
    mcfg = small_moe()
    w = make_weights(mcfg, SEED + 1)
    prompt = [int(t) for t in np.random.default_rng(4).integers(0, 256, 45)]
    served = {}
    for rows in (None, 1):
        logits_spy.clear()
        with SessionServeEngine(arch_config(mcfg), program_params(w), max_batch=8,
                                page_size=4, num_pages=8 * 16 + 1,
                                pages_per_group=8 * 16 + 1, max_pages_per_seq=16,
                                arena_bytes=16 << 20) as eng:
            if rows is None:
                assert eng.prefill_rows == 4
            else:
                eng.prefill_rows = rows  # the per-token loop
            req = eng.submit(prompt, 6)
            eng.run()
        calls = -(-44 // eng.prefill_rows)
        assert len(logits_spy) == calls + 6
        served[rows] = (req.generated, np.stack([a[0] for a in logits_spy[calls:]]))
    (tok4, got), (tok1, want) = served[None], served[1]
    # float32, the same program, each row on its own: bitwise equal
    np.testing.assert_array_equal(got, want)
    assert tok4 == tok1


@pytest.mark.parametrize("family", ["dense", "swa_moe"])
def test_prefill_is_one_task_and_counted(family):
    """Each prompt is one ``llm_prefill`` task, ``prefill#<rid>``, and the
    Session's counters take in its calls and tokens."""
    cfg, params, _, _, _ = _model(family)
    lengths = [2, 9, 14]  # 1, 8 and 13 tokens to take in
    with SessionServeEngine(cfg, params, max_batch=BATCH, page_size=PAGE,
                            num_pages=4 * 16 + 1, pages_per_group=4 * 16 + 1,
                            max_pages_per_seq=16, arena_bytes=16 << 20) as eng:
        rows = eng.prefill_rows
        assert rows == 4
        rng = np.random.default_rng(9)
        reqs = [eng.submit([int(t) for t in rng.integers(1, cfg.vocab, n)], 2)
                for n in lengths]
        eng.run()
        assert all(r.done for r in reqs)
        log = eng.session.runtime.task_log
        assert sorted(n for n, _ in log if not n.startswith("llm_decode")) == sorted(
            f"prefill#{r.rid}" for r in reqs)
        m = eng.session.metrics
        assert m.counter("serve/prefill_calls").value == sum(-(-(n - 1) // rows)
                                                             for n in lengths)
        assert m.counter("serve/prefill_tokens").value == sum(n - 1 for n in lengths)


@pytest.mark.parametrize("family", ["dense", "swa_moe"])
def test_prompts_of_many_calls_compile_nothing_after_a_one_call_warm_up(family):
    """The serving benchmark warms up on prompts of 3 and 2 tokens, which
    take one prefill call each; longer prompts, of several calls, beside
    decoding requests, must then compile nothing (so nothing compiles in
    its measured window)."""
    cfg, params, _, _, _ = _model(family)
    jax.clear_caches()
    session_engine._jit_grouped_step.cache_clear()
    session_engine._jit_hybrid_step.cache_clear()
    compiles = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    with SessionServeEngine(cfg, params, max_batch=BATCH, page_size=PAGE,
                            num_pages=4 * 16 + 1, pages_per_group=4 * 16 + 1,
                            max_pages_per_seq=16, arena_bytes=16 << 20) as eng:
        for name in ("a", "b"):
            eng.tenant(name)
        for name in ("a", "b"):  # the benchmark's warm-up
            eng.submit([1, cfg.vocab - 1, 2], 2, tenant=name)
            eng.run()
        for name in ("a", "b"):
            eng.submit([3, 4], 2, tenant=name)
        eng.run()
        jax.monitoring.register_event_duration_secs_listener(on)
        try:
            rng = np.random.default_rng(11)
            for n in (14, 9, 30):  # 4, 2 and 8 calls of 4 rows
                eng.submit([int(t) for t in rng.integers(1, cfg.vocab, n)], 3, tenant="a")
                eng.step()
                eng.submit([5, 6, 7, 8, 9, 10], 2, tenant="b")
            eng.run()
        finally:
            jax.monitoring.unregister_event_duration_listener(on)
        # one call for each warm-up prompt, then 4, 2 and 8, and 2 for each of b's
        assert eng.session.metrics.counter("serve/prefill_calls").value == 4 + 14 + 3 * 2
    assert compiles == []
