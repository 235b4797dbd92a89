"""Least work: the operations and bytes that the traffic asks for, from
its shapes alone.  Roofline and MFU shares divide these by measured
time, so a program that fuses, batches or re-implements a kernel is
read against the same count and cannot pass 100%.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence, Tuple

__all__ = [
    "fft_flops", "zip_flops", "radar_task_work", "least_time",
    "DecoderShape", "decoder_shape",
]

C64_BYTES = 8


def fft_flops(n: int) -> float:
    """A complex ``n``-point FFT (or inverse): 5 n log2 n real operations."""
    return 5.0 * n * math.log2(n)


def zip_flops(n: int) -> float:
    """An elementwise complex product of ``n`` samples: 6 real operations each."""
    return 6.0 * n


def radar_task_work(tasks: Sequence[Tuple[str, int, Sequence, Sequence]],
                    placement: Sequence[str], acc: str,
                    read_back: Iterable) -> List[Tuple[float, float]]:
    """(flops, bytes) of each task that ran on ``acc``.

    ``tasks[k]`` is ``(op, samples, input ids, output ids)``; ``placement[k]``
    the PE that ran it; ``read_back`` the ids the host reads at the end.
    A value costs its bytes once when it enters ``acc`` (charged to its
    first consumer there) and once when it leaves (charged to its
    producer), if it is consumed off ``acc`` or read back; values that
    stay on ``acc`` between tasks cost nothing.
    """
    producer: Dict = {}
    for k, (_, _, _, outs) in enumerate(tasks):
        for v in outs:
            producer[v] = k
    leaving = set(read_back)
    for k, (_, _, ins, _) in enumerate(tasks):
        if placement[k] != acc:
            leaving.update(v for v in ins if v in producer)
    entered: set = set()
    work = []
    for k, (op, n, ins, outs) in enumerate(tasks):
        if placement[k] != acc:
            continue
        flops = fft_flops(n) if op in ("fft", "ifft") else zip_flops(n)
        nbytes = 0
        for v in ins:
            src = producer.get(v)
            if (src is None or placement[src] != acc) and v not in entered:
                entered.add(v)
                nbytes += n * C64_BYTES
        nbytes += sum(n * C64_BYTES for v in outs if v in leaving)
        work.append((flops, float(nbytes)))
    return work


def least_time(work: Iterable[Tuple[float, float]], peak_flops: float,
               peak_bytes: float) -> float:
    """Seconds the chip needs at least: each piece bound by the slower of
    its operations and its bytes at the chip's peaks."""
    return sum(max(f / peak_flops, b / peak_bytes) for f, b in work)


class DecoderShape:
    """Per-token operations and bytes of a dense GQA decoder."""

    def __init__(self, *, layers: int, d: int, heads: int, kv_heads: int,
                 head_dim: int, ff: int, vocab: int, weight_bytes: int,
                 kv_bytes: int):
        self.layers = layers
        self.d = d
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.ff, self.vocab = ff, vocab
        self.wb, self.kvb = weight_bytes, kv_bytes
        q, kv = heads * head_dim, kv_heads * head_dim
        #: weights one token multiplies with (the embedding is a gather)
        self.matmul_params = layers * (d * q + 2 * d * kv + q * d + 3 * d * ff) + d * vocab
        #: bytes of one full read of the weights (norm scales included)
        self.weight_read_bytes = (self.matmul_params + (2 * layers + 1) * d) * weight_bytes
        #: K and V of one token over all layers
        self.kv_token_bytes = 2 * layers * kv * kv_bytes

    def token_flops(self, pos: int) -> float:
        """A token at position ``pos`` (0-based), attending to pos + 1 keys."""
        attn = 4 * self.layers * self.heads * self.head_dim * (pos + 1)
        return 2.0 * self.matmul_params + attn

    def token_bytes(self, pos: int) -> float:
        """KV written and read, and the embedding row, for one token."""
        return (self.kv_token_bytes * (pos + 2)
                + self.d * self.wb)

    def step_work(self, prompts: Sequence[int],
                  decode_positions: Sequence[int]) -> Tuple[float, float]:
        """(flops, bytes) of an engine step that admitted ``prompts`` (their
        lengths; a prompt of P tokens is taken in through positions
        0..P-2, the last token being the first decode's input) and
        produced one token at each of ``decode_positions``.  The weights
        are read once per prompt and once for the step's tokens."""
        flops = 0.0
        nbytes = 0.0
        for p in prompts:
            for pos in range(p - 1):
                flops += self.token_flops(pos)
                nbytes += self.token_bytes(pos)
            nbytes += self.weight_read_bytes
        for pos in decode_positions:
            flops += self.token_flops(pos)
            nbytes += self.token_bytes(pos)
        if decode_positions:
            nbytes += self.weight_read_bytes
        return flops, nbytes


def decoder_shape(cfg: Dict) -> DecoderShape:
    """A :class:`DecoderShape` from a configuration file of ``bench/configs``."""
    sizes = {"bfloat16": 2, "float16": 2, "float32": 4}
    return DecoderShape(
        layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], weight_bytes=sizes[cfg["torch_dtype"]],
        kv_bytes=sizes[cfg["compute_dtype"]])
