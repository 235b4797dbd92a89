"""The mellum2-reason cell at a CPU test size: whole runs past the
harness's look for a chip come out correct when sound and not correct
when the model's own mechanisms are broken underneath, the float8
control fails the limit, and the work count and KV reader match hand
counts."""

import argparse
import json

import numpy as np
import pytest

from cells import BENCH, SEED  # noqa: F401  (puts bench/ and src/ on the path)

import run  # noqa: E402

MELLUM = json.loads((BENCH / "configs" / "mellum2-12b-8l.json").read_text())


def tiny() -> dict:
    """The cell at a test size: hidden 64, the published 3:1 pattern over
    8 layers, 16 experts top-4 of width 32, window 16 in pages of 4 (a
    ring of 5 pages), the published vocabulary; prompts of 4-24 tokens
    and answers of 18-36, so the rings wrap in prefill and in decode.

    The program computes in float32 on the bfloat16 weights: at hidden 64
    and 8 layers, bfloat16 activations put the sound program's
    ``mean_logit_gap`` at 1.0e-4 to 8.4e-4 over 8 seeds, astride the
    limit that the published widths set on the chip (bfloat16 there
    reads at most 2.1e-4 over 12 seeds).  The float8 control, which the
    limit has to catch, reads 1.7e-3 to 4.5e-3 here."""
    spec = run.cell_spec("mellum2-reason")
    cfg = spec["config"]
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
               sliding_window=16, compute_dtype="float32")
    cfg["engine"] = dict(cfg["engine"], max_batch=4, page_size=4, max_pages_per_seq=16,
                         num_pages=65, pages_per_group=65, window_pages=4 * 5 + 1,
                         arena_bytes=16 << 20)
    t = spec["traffic"]
    t["rate_per_s"] = 4.0
    t["prompt_tokens"] = dict(t["prompt_tokens"], median=12, min=4, max=24)
    t["output_tokens"] = dict(t["output_tokens"], median=24, min=18, max=36)
    t["sample"] = {"min_tokens": 10**6, "max_requests": 10**6}
    return spec


def measure(spec=None, seed=SEED, seconds=1.5) -> dict:
    import jax

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return run.measure(args, spec or tiny(), jax.devices(), skip_chip_check=True)


@pytest.fixture
def fresh_programs():
    """Step programs traced anew, so a patch below is compiled in."""
    from repro.serve import session_engine

    session_engine._jit_hybrid_step.cache_clear()
    yield
    session_engine._jit_hybrid_step.cache_clear()


def test_sound_run_is_correct(fresh_programs):
    res = measure()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"tpot_p95_ms", "setup_s"}


def _patch_arch(monkeypatch, **change):
    """The engine serves a changed configuration; the reference does not."""
    import dataclasses

    from repro.serve.session_engine import SessionServeEngine

    real = SessionServeEngine.__init__

    def init(self, cfg, params, **kw):
        real(self, dataclasses.replace(cfg, **change), params, **kw)

    monkeypatch.setattr(SessionServeEngine, "__init__", init)


def test_window_layers_attending_to_every_key(monkeypatch, fresh_programs):
    """The program's sliding layers keep and attend to every key of a
    sequence (a window as long as the longest sequence); the reference
    keeps its 16."""
    spec = tiny()
    e = spec["config"]["engine"]
    longest = e["page_size"] * e["max_pages_per_seq"]
    e["window_pages"] = e["max_batch"] * (longest // e["page_size"] + 1) + 1
    _patch_arch(monkeypatch, window=longest)
    assert not measure(spec)["correct"]


def test_full_layers_on_plain_rope(monkeypatch, fresh_programs):
    _patch_arch(monkeypatch, full_rope_yarn=None)
    assert not measure()["correct"]


def test_eighth_expert_dropped(monkeypatch, fresh_programs):
    """Each token's last-ranked expert contributes nothing (as a capacity
    drop would), the others keep their weights."""
    import jax.numpy as jnp

    from repro.kernels.moe import ops

    real = ops.route

    def route(h, router, top_k, active):
        comb, counts = real(h, router, top_k, active)
        last = jnp.min(jnp.where(comb > 0, comb, jnp.inf), axis=-1, keepdims=True)
        return jnp.where(comb == last, 0.0, comb), counts

    monkeypatch.setattr(ops, "route", route)
    assert not measure()["correct"]


@pytest.mark.parametrize("seed", [SEED + 1, SEED + 2, SEED + 3])
def test_control_fails_the_limit(seed, fresh_programs):
    """The float8 control in the program's place comes out not correct by
    the benchmark's own comparison, on the tokens a sound run served;
    the program comes out correct."""
    from reference.decoder import served_gaps

    spec = tiny()
    drv_mod = run.load_module(BENCH / "drivers" / "serve_moe.py", "bench_driver_serve_moe")
    drv = drv_mod.Driver(spec["config"], spec["traffic"], seed, trace=False)
    drv.setup()
    drv.run_window(1.0)
    drv.release()
    ref, ctl = drv.reference(), drv.reference("fp8")
    program, control = [], []
    for t in drv.sample():
        program += served_gaps(ref, t.req.prompt, t.handle.generated)
        control += served_gaps(ref, t.req.prompt, t.handle.generated, control=ctl)
    assert run.passes(drv_mod.judge(program)), drv_mod.judge(program)
    assert not run.passes(drv_mod.judge(control)), drv_mod.judge(control)


def test_work_count_by_hand():
    import moe_work

    shape = moe_work.moe_shape(MELLUM)
    # per layer: q and o 2304x4096, k and v 2304x512, router 2304x64; the head
    assert shape.dense_params == 8 * (2 * 9_437_184 + 2 * 1_179_648 + 147_456) \
        + 2304 * 98304 == 397_541_376
    # one expert: gate and up 2304x896, down 896x2304
    assert shape.expert_params == 6_193_152 and shape.expert_bytes == 12_386_304
    assert shape.dense_read_bytes == (397_541_376 + 17 * 2304) * 2 == 795_161_088
    assert shape.kv_layer_bytes == 2 * 512 * 2 == 2048
    # a token at position 2000: full layers attend to 2001 keys, sliding to 1024
    per_token = 2 * (397_541_376 + 8 * 8 * 6_193_152)
    attn = 4 * 32 * 128 * (2 * 2001 + 6 * 1024)
    assert shape.token_flops(2000) == per_token + attn
    assert shape.token_bytes(2000) == 2048 * (2 * 2002 + 6 * 1025) + 2304 * 2
    counts = np.zeros((8, 64), np.int32)
    counts[:, :10] = 3  # 10 distinct experts in every layer
    counts[0, 63] = 1   # and one more in layer 0
    flops, nbytes = shape.step_work([], [2000], counts)
    assert flops == shape.token_flops(2000)
    assert nbytes == shape.token_bytes(2000) + 795_161_088 + 81 * 12_386_304
    # a prompt of 3 tokens: positions 0 and 1, one weight read, its experts
    flops, nbytes = shape.step_work([(3, counts)], [])
    assert flops == shape.token_flops(0) + shape.token_flops(1)
    assert nbytes == (shape.token_bytes(0) + shape.token_bytes(1)
                      + 795_161_088 + 81 * 12_386_304)


def test_kv_reader_by_hand():
    reader = run.load_module(BENCH / "metrics" / "kv_mib_per_seq.mellum.py", "m_kv")
    # two sequences for 10 steps: 84 full pages of 64 KiB and a 65-page
    # ring of 192 KiB each
    f = {"kv": {"page_steps": {"full": 2 * 84 * 10, "window": 2 * 65 * 10},
                "page_bytes": {"full": 65536, "window": 196608},
                "seq_steps": 20}}
    assert reader.read(f) == pytest.approx(84 * 0.0625 + 65 * 0.1875)
    assert reader.read({}) is None


def test_config_is_the_published_stage():
    assert MELLUM["reduced"] == ["num_hidden_layers"]
    assert (MELLUM["num_hidden_layers"], MELLUM["published_num_hidden_layers"]) == (8, 28)
    for key, want in (("hidden_size", 2304), ("num_attention_heads", 32),
                      ("num_key_value_heads", 4), ("head_dim", 128), ("num_experts", 64),
                      ("num_experts_per_tok", 8), ("moe_intermediate_size", 896),
                      ("sliding_window", 1024), ("vocab_size", 98304)):
        assert MELLUM[key] == want, key
    assert MELLUM["layer_types"][:8] == ["sliding_attention"] * 3 + ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    e = MELLUM["engine"]
    assert e["num_pages"] == e["max_batch"] * e["max_pages_per_seq"] + 1 == 2241
    assert e["window_pages"] == e["max_batch"] * 65 + 1 == 1041
