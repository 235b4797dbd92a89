"""The plain references against the system's own model and FFT paths at
small sizes on the CPU, and their controls against the limits."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from reference import radar as radar_ref  # noqa: E402

YI = json.loads((BENCH / "configs" / "yi-9b-8l.json").read_text())


def smoke_config(dtype="float32"):
    cfg = dict(YI)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256,
               num_hidden_layers=2, torch_dtype=dtype, compute_dtype=dtype)
    return cfg


def test_decoder_matches_the_model_forward():
    import jax
    import jax.numpy as jnp

    from reference.decoder import Reference, make_weights
    from repro.models import build_model

    sys.path.insert(0, str(BENCH / "drivers"))
    from serve import arch_config, program_params

    cfg = smoke_config()
    w = make_weights(cfg, 2**31 + 3)
    tokens = np.random.default_rng(0).integers(0, 256, 24)
    model = build_model(arch_config(cfg))
    with jax.default_matmul_precision("highest"):
        got, _ = model.prefill(program_params(w), {"tokens": jnp.asarray(tokens[None])}, 32)
    ref = Reference(cfg, w, seq_len=32, n_rows=4)
    want = ref.logits(tokens, [23])
    np.testing.assert_allclose(np.asarray(got)[0], want[0], rtol=2e-4, atol=2e-4)


def test_weights_repeat_for_one_seed():
    from reference.decoder import make_weights

    cfg = smoke_config("bfloat16")
    a, b = make_weights(cfg, 2**33 + 1), make_weights(cfg, 2**33 + 1)
    c = make_weights(cfg, 2**33 + 2)
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    assert not np.array_equal(np.asarray(a["wq"]), np.asarray(c["wq"]))
    assert a["wq"].dtype == np.dtype("bfloat16") and a["wq"].shape == (2, 64, 64)


def test_radar_chain_matches_the_app_kernels():
    from repro.apps import radar

    rng = np.random.default_rng(1)
    a = (rng.standard_normal((4, 256)) + 1j * rng.standard_normal((4, 256))).astype(np.complex64)
    b = (rng.standard_normal((4, 256)) + 1j * rng.standard_normal((4, 256))).astype(np.complex64)
    fa, fb = radar._fft_cpu([a]), radar._fft_cpu([b])
    got = radar._ifft_cpu([radar._zip_cpu([fa, fb])])
    assert radar_ref.rel_error(got, radar_ref.chain(a, b)) < 1e-6


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_radar_control_fails_the_limit(seed):
    sys.path.insert(0, str(BENCH / "drivers"))
    from radar import MAX_REL_ERR

    rng = np.random.default_rng(seed)
    a = (rng.standard_normal((8, 512)) + 1j * rng.standard_normal((8, 512))).astype(np.complex64)
    b = (rng.standard_normal((8, 512)) + 1j * rng.standard_normal((8, 512))).astype(np.complex64)
    assert radar_ref.rel_error(radar_ref.control(a, b), radar_ref.chain(a, b)) > 3 * MAX_REL_ERR
