"""Least-work counts against values worked out by hand."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import work  # noqa: E402

YI = json.loads((BENCH / "configs" / "yi-9b-8l.json").read_text())


def test_fft_and_zip_counts():
    assert work.fft_flops(256) == 5 * 256 * 8
    assert work.fft_flops(512) == 5 * 512 * 9
    assert work.zip_flops(256) == 6 * 256


def _chain(n):
    # fftA, fftB, zip, ifft over value ids a, b, fa, fb, z, out
    return [("fft", n, ["a"], ["fa"]), ("fft", n, ["b"], ["fb"]),
            ("zip", n, ["fa", "fb"], ["z"]), ("ifft", n, ["z"], ["out"])]


def test_split_chain_pays_every_crossing():
    # round-robin over (cpu, gpu0): fftB and ifft run on the accelerator;
    # b enters and fb leaves for the CPU's zip; z enters, out is read back
    got = work.radar_task_work(_chain(256), ["cpu0", "gpu0", "cpu0", "gpu0"],
                               "gpu0", ["out"])
    assert got == [(10240.0, 4096.0), (10240.0, 4096.0)]


def test_chain_on_the_accelerator_keeps_intermediates_free():
    got = work.radar_task_work(_chain(256), ["gpu0"] * 4, "gpu0", ["out"])
    assert got == [(10240.0, 2048.0), (10240.0, 2048.0), (1536.0, 0.0), (10240.0, 2048.0)]


def test_one_sar_frame_on_the_accelerator():
    from repro.apps.radar import build_sar
    from repro.core.hete import HeteContext

    bufs, tasks = build_sar(HeteContext())
    desc = [(t.op, t.inputs[0].shape[0], [id(x) for x in t.inputs],
             [id(y) for y in t.outputs]) for t in tasks]
    outs = [id(f) for p in bufs.values() for f in p["out"][1]]
    got = work.radar_task_work(desc, ["gpu0"] * len(desc), "gpu0", outs)
    # phase 1: 512 chains of 256 samples, 3 transforms of 10,240 flops and
    # a product of 1,536; a, b enter and out leaves, 2,048 bytes each
    # phase 2: 256 chains of 512 samples, 3 x 23,040 + 3,072 flops, 3 x 4,096 bytes
    assert len(got) == 3072
    assert sum(f for f, _ in got) == 512 * 32256 + 256 * 72192 == 34_996_224
    assert sum(b for _, b in got) == 512 * 6144 + 256 * 12288 == 6_291_456


def test_least_time_takes_the_slower_bound():
    assert work.least_time([(2e12, 1e9), (1e9, 8e9)], 1e12, 1e9) == pytest.approx(2.0 + 8.0)


def test_yi_decode_step():
    shape = work.decoder_shape(YI)
    # per layer: q 4096x4096, k and v 4096x512 each, o 4096x4096, MLP 3 x 4096x11008
    per_layer = 16_777_216 + 4_194_304 + 16_777_216 + 135_266_304
    assert shape.matmul_params == 8 * per_layer + 4096 * 64000 == 1_646_264_320
    # bf16 weights plus 17 norm vectors (2 per layer and the final one)
    assert shape.weight_read_bytes == (1_646_264_320 + 17 * 4096) * 2 == 3_292_667_904
    assert shape.kv_token_bytes == 2 * 8 * 512 * 2 == 16_384
    flops, nbytes = shape.step_work([], [99, 199])
    # 2 flops per weight per token, and 4 * layers * heads * head_dim per key
    assert flops == 2 * 2 * 1_646_264_320 + 4 * 8 * 32 * 128 * (100 + 200) == 6_624_378_880
    # one weight read; KV written once and read (pos + 1) times; the embedding row
    assert nbytes == 3_292_667_904 + 16_384 * (101 + 201) + 2 * 4096 * 2 == 3_297_632_256


def test_prompt_reads_the_weights_once():
    shape = work.decoder_shape(YI)
    flops, nbytes = shape.step_work([3], [])
    assert flops == shape.token_flops(0) + shape.token_flops(1)
    assert nbytes == shape.weight_read_bytes + shape.token_bytes(0) + shape.token_bytes(1)
