"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts (unaligned blocks,
unlowerable ops, programs that do not fit), so every Pallas kernel at
its deployed widths and the serving decode steps at yi-9b's and
Mellum2-12B-A2.5B's widths are compiled here.  The topology is described inside a fixture: only the
worker that runs this file loads the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import cases


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # Entries compiled for a described chip cannot be read back here.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("case", cases(), ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, one_chip):
    args = [_spec(shape, dtype, one_chip) for shape, dtype in case.args]
    lowered = jax.jit(lambda *a: case.kernel(*a, interpret=False)).lower(*args)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serving_decode_step_compiles_at_yi_9b_width(one_chip):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.session_engine import _jit_grouped_step

    cfg = dataclasses.replace(get_config("yi_9b"), n_layers=4)
    params = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), params)
    n_groups, group_pages, page, batch, pages_per_seq = 4, 8, 16, 4, 32
    group = _spec((cfg.n_layers, group_pages, page, cfg.n_kv_heads,
                   cfg.head_dim_), jnp.bfloat16, one_chip)
    vec = _spec((batch,), jnp.int32, one_chip)
    compiled = _jit_grouped_step(cfg, n_groups).lower(
        params, (group,) * n_groups, (group,) * n_groups,
        _spec((batch, pages_per_seq), jnp.int32, one_chip), vec, vec, vec,
    ).compile()
    mem = compiled.memory_analysis()
    # the 4-layer cut at published widths must fit one v5e's 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_serving_hybrid_step_compiles_at_mellum_width(one_chip, monkeypatch):
    """The 8-layer stage of Mellum2-12B-A2.5B at published widths: the
    routed experts run as the Pallas kernel, read straight from the
    weights (no instruction but the kernel takes an expert-stacked array),
    and the stage fits one v5e."""
    import json
    from pathlib import Path

    from repro.kernels.moe import moe
    from repro.serve.session_engine import _jit_hybrid_step

    bench = Path(__file__).resolve().parents[1] / "bench"
    cfg = json.loads((bench / "configs" / "mellum2-12b-8l.json").read_text())
    import sys

    sys.path.insert(0, str(bench))
    sys.path.insert(0, str(bench / "drivers"))
    from reference.moe_decoder import make_weights
    from serve_moe import arch_config, program_params

    # compile the kernel for the described chip, not the interpreter
    monkeypatch.setattr(moe, "resolve_interpret", lambda interpret=None: False)
    _jit_hybrid_step.cache_clear()
    arch = arch_config(cfg)
    weights = jax.eval_shape(lambda: make_weights(cfg, 1))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          program_params(weights))
    e = cfg["engine"]
    batch, page = e["max_batch"], e["page_size"]
    full = _spec((2, e["num_pages"], page, 4, 128), jnp.bfloat16, one_chip)
    win = _spec((6, e["window_pages"], page, 4, 128), jnp.bfloat16, one_chip)
    vec = _spec((batch,), jnp.int32, one_chip)
    try:
        compiled = _jit_hybrid_step(arch, 1, 1).lower(
            params, (full,), (full,), (win,), (win,),
            _spec((batch, e["max_pages_per_seq"]), jnp.int32, one_chip),
            _spec((batch, 65), jnp.int32, one_chip), vec, vec, vec).compile()
    finally:
        _jit_hybrid_step.cache_clear()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8  # one per layer
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("%") and ("[64,2304,896]" in line or "[64,896,2304]" in line):
            assert " parameter(" in line or "tpu_custom_call" in line, line
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 9e9
