"""Session-backed serving engine: token streams bit-identical to the
legacy engine, KV spill through the runtime eviction path, tenant
quotas/backpressure, and serving telemetry."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve.engine import ServeEngine
from repro.serve.session_engine import SessionServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("llama3_8b").smoke(), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(1))
    return cfg, model, params


def make_work(vocab, n=6, seed=0):
    rng = np.random.default_rng(seed)
    return [([int(t) for t in rng.integers(1, vocab, int(rng.integers(2, 7)))],
             int(rng.integers(2, 6)))
            for _ in range(n)]


def legacy_tokens(cfg, params, work, max_batch=3, **kw):
    eng = ServeEngine(cfg, params, max_batch=max_batch, page_size=8,
                      num_pages=64, max_pages_per_seq=8, **kw)
    reqs = [eng.submit(p, m) for p, m in work]
    eng.run()
    return [r.generated for r in reqs]


def test_bit_identical_to_legacy_multi_tenant(setup):
    cfg, model, params = setup
    work = make_work(cfg.vocab)
    want = legacy_tokens(cfg, params, work)
    with SessionServeEngine(cfg, params, max_batch=3, page_size=8,
                            num_pages=64, max_pages_per_seq=8,
                            pages_per_group=8) as eng:
        reqs = [eng.submit(p, m, tenant=["a", "b"][i % 2])
                for i, (p, m) in enumerate(work)]
        eng.run()
        assert all(r.done for r in reqs)
        assert [r.generated for r in reqs] == want
        # runtime managed the KV: pages all recycled, tasks all traced
        assert eng.kv.used_pages == 1  # scratch page only
        assert eng.kv.spill_bytes() == 0  # a roomy arena never spills
        rep = eng.qos_report()
        assert {"a", "b", "prefill"} <= set(rep["latency_percentiles"])


def test_spill_under_pressure_is_bit_identical(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(7)
    # enough churn that the nextfit cursor cycles every page group: the
    # resident KV working set then exceeds the shrunken arena
    work = [([int(t) for t in rng.integers(1, cfg.vocab,
                                           int(rng.integers(1, 9)))],
             int(rng.integers(1, 7)))
            for _ in range(28)]
    want = legacy_tokens(cfg, params, work, allocator="nextfit",
                         max_batch=4)
    with SessionServeEngine(cfg, params, max_batch=4, page_size=8,
                            num_pages=64, max_pages_per_seq=8,
                            pages_per_group=4, allocator="nextfit",
                            arena_bytes=150_000) as eng:
        reqs = [eng.submit(p, m, tenant=["a", "b"][i % 2])
                for i, (p, m) in enumerate(work)]
        eng.run()
        # cold page groups were evicted to host (dirty write-back through
        # the runtime coherence path) and re-staged — same tokens out.
        assert eng.kv.spill_bytes() > 0
        assert [r.generated for r in reqs] == want


def test_tenant_quota_defers_without_blocking_others(setup):
    cfg, model, params = setup
    work = make_work(cfg.vocab, n=4, seed=2)
    with SessionServeEngine(cfg, params, max_batch=4, page_size=8,
                            num_pages=64, max_pages_per_seq=8,
                            pages_per_group=8) as eng:
        eng.tenant("capped", quota_pages=2)
        reqs = [eng.submit(p, m, tenant="capped") for p, m in work[:3]]
        other = eng.submit(*work[3], tenant="open")
        eng.run()
        # quota forced serialization, not starvation: everything finishes
        assert all(r.done for r in reqs) and other.done
        assert int(eng.session.metrics.counter(
            "serve_quota_deferrals").value) > 0
        assert eng.kv.pool.tenant_pages("capped") == 0


def test_pool_exhaustion_backpressure_is_clean(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(4)
    # every request needs 2 pages (10 prompt + 4 new tokens, page=8)
    work = [([int(t) for t in rng.integers(1, cfg.vocab, 10)], 4)
            for _ in range(6)]
    want = legacy_tokens(cfg, params, work)
    # 7 usable pages → only 3 of the 4 slots can hold a sequence:
    # admission must defer cleanly, not corrupt — and the tokens still
    # match the unconstrained legacy run.
    with SessionServeEngine(cfg, params, max_batch=4, page_size=8,
                            num_pages=8, max_pages_per_seq=8,
                            pages_per_group=4) as eng:
        reqs = [eng.submit(p, m) for p, m in work]
        eng.run()
        assert all(r.done for r in reqs)
        assert [r.generated for r in reqs] == want
        assert int(eng.session.metrics.counter(
            "serve_pool_backpressure").value) > 0


def test_eos_mid_page_frees_and_matches_legacy(setup):
    cfg, model, params = setup
    work = make_work(cfg.vocab, n=3, seed=0)
    # pick an eos that actually fires mid-stream: the first generated
    # token of the first request, reused as eos for a longer rerun
    probe = legacy_tokens(cfg, params, work)
    eos = probe[0][0]
    long_work = [(p, 6) for p, _ in work]
    want = legacy_tokens(cfg, params, long_work, eos_id=eos)
    assert any(len(t) < 6 for t in want), "eos never fired; bad probe"
    with SessionServeEngine(cfg, params, max_batch=3, page_size=8,
                            num_pages=64, max_pages_per_seq=8,
                            pages_per_group=8, eos_id=eos) as eng:
        reqs = [eng.submit(p, m) for p, m in long_work]
        eng.run()
        assert [r.generated for r in reqs] == want
        assert eng.kv.used_pages == 1  # early-stopped pages recycled too


def test_prompt_longer_than_max_pages_rejected(setup):
    cfg, model, params = setup
    long_prompt = list(range(1, 40))  # 39 + 4 tokens > 2 pages * 8
    for ctor in (
        lambda: ServeEngine(cfg, params, page_size=8, num_pages=64,
                            max_pages_per_seq=2),
        lambda: SessionServeEngine(cfg, params, page_size=8, num_pages=64,
                                   max_pages_per_seq=2),
    ):
        eng = ctor()
        with pytest.raises(ValueError, match="max_pages_per_seq"):
            eng.submit(long_prompt, max_new_tokens=4)
        if isinstance(eng, SessionServeEngine):
            eng.close()


def test_serving_metrics_and_slo_exported(setup):
    cfg, model, params = setup
    work = make_work(cfg.vocab, n=3, seed=1)
    with SessionServeEngine(cfg, params, max_batch=3, page_size=8,
                            num_pages=64, max_pages_per_seq=8,
                            pages_per_group=8) as eng:
        eng.tenant("t0", slo_latency_s=60.0, slo_target=0.99)
        reqs = [eng.submit(p, m, tenant="t0") for p, m in work]
        eng.run()
        total = sum(len(r.generated) for r in reqs)
        m = eng.session.metrics
        assert int(m.counter("serve_tokens_generated").value) == total
        assert int(m.counter("serve_requests_completed").value) == len(work)
        text = eng.session.metrics_text()
        for name in ("serve_tokens_generated", "serve_requests_completed",
                     "serve_kv_pages_resident", "serve_kv_spill_bytes"):
            assert name in text
        slo = eng.qos_report()["slo"]["t0"]
        assert slo["violations"] == 0 and not slo["breached"]


def test_session_engine_rejects_recurrent_families(setup):
    cfg, model, params = setup
    bad = dataclasses.replace(cfg, family="ssm")
    with pytest.raises(ValueError, match="dense"):
        SessionServeEngine(bad, params)
