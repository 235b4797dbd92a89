"""Least work of a decoder with sliding-window and full attention layers
and sparse-expert MLPs, from the traffic's shapes and the router's
choices.  As for ``work.DecoderShape``: the weights outside the experts
are read once per prompt and once per engine step that made tokens;
the experts are read once for each distinct expert the router chose
there (from the step's routing counts); KV is written once and read
once per key attended, a sliding layer attending to at most its window;
operations are those of the active parameters.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

__all__ = ["MoEShape", "moe_shape"]

SIZES = {"bfloat16": 2, "float16": 2, "float32": 4}


class MoEShape:
    def __init__(self, *, kinds: Sequence[str], d: int, heads: int, kv_heads: int,
                 head_dim: int, experts: int, top_k: int, expert_ff: int, vocab: int,
                 window: int, weight_bytes: int, kv_bytes: int):
        self.kinds = list(kinds)
        self.layers = len(self.kinds)
        self.d, self.heads, self.head_dim = d, heads, head_dim
        self.top_k, self.window, self.wb = top_k, window, weight_bytes
        q, kv = heads * head_dim, kv_heads * head_dim
        #: weights outside the experts that one token multiplies with
        #: (attention projections, router, head; the embedding is a gather)
        self.dense_params = self.layers * (2 * d * q + 2 * d * kv + d * experts) + d * vocab
        #: weights of one expert (gate, up, down)
        self.expert_params = 3 * d * expert_ff
        #: bytes of one read of the weights outside the experts, norms included
        self.dense_read_bytes = (self.dense_params + (2 * self.layers + 1) * d) * weight_bytes
        self.expert_bytes = self.expert_params * weight_bytes
        #: K and V of one token in one layer
        self.kv_layer_bytes = 2 * kv * kv_bytes

    def keys(self, kind: str, pos: int) -> int:
        """Keys a token at ``pos`` (0-based) attends to in a layer."""
        return min(pos + 1, self.window) if kind == "sliding_attention" else pos + 1

    def token_flops(self, pos: int) -> float:
        matmul = self.dense_params + self.layers * self.top_k * self.expert_params
        attn = sum(4 * self.heads * self.head_dim * self.keys(k, pos) for k in self.kinds)
        return 2.0 * matmul + attn

    def token_bytes(self, pos: int) -> float:
        """KV written and read, and the embedding row, for one token."""
        kv = sum(self.kv_layer_bytes * (self.keys(k, pos) + 1) for k in self.kinds)
        return kv + self.d * self.wb

    def expert_read_bytes(self, counts) -> float:
        """Bytes of the distinct experts with a routed token, per layer."""
        return float(np.count_nonzero(np.asarray(counts))) * self.expert_bytes

    def step_work(self, prompts: Sequence[Tuple[int, object]],
                  decode_positions: Sequence[int], decode_counts=None) -> Tuple[float, float]:
        """(flops, bytes) of an engine step that took in ``prompts`` (each
        its length P and the (layers, experts) routing counts of its
        positions 0..P-2) and produced one token at each of
        ``decode_positions`` with the routing ``decode_counts``."""
        flops = nbytes = 0.0
        for length, counts in prompts:
            for pos in range(length - 1):
                flops += self.token_flops(pos)
                nbytes += self.token_bytes(pos)
            nbytes += self.dense_read_bytes + self.expert_read_bytes(counts)
        for pos in decode_positions:
            flops += self.token_flops(pos)
            nbytes += self.token_bytes(pos)
        if decode_positions:
            nbytes += self.dense_read_bytes + self.expert_read_bytes(decode_counts)
        return flops, nbytes


def moe_shape(cfg: Dict) -> MoEShape:
    """A :class:`MoEShape` from a configuration file of ``bench/configs``."""
    return MoEShape(
        kinds=cfg["layer_types"][:cfg["num_hidden_layers"]], d=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"], expert_ff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], window=cfg["sliding_window"],
        weight_bytes=SIZES[cfg["torch_dtype"]], kv_bytes=SIZES[cfg["compute_dtype"]])
