"""Operations and bytes, computed from the shapes a configuration file
states: the yardstick every roofline and utilisation share divides by.

Only the logical work counts: the projection matmuls (the TimeFloats
scalar products, two operations per multiply-add), attention's scores and
weighted values over the positions a token really attends to, and the
output head for each token whose logits are used. Quantisation passes,
padding, recomputation and dummy rows are not work.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table["devices"][device_kind]


def _dims(c: dict):
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    return c["num_hidden_layers"], d, f, h, kv, hd, c["vocab_size"]


def layer_matmul_params(c: dict) -> int:
    _, d, f, h, kv, hd, _ = _dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def forward_flops(c: dict, tokens: int, attended: int,
                  head_tokens: int) -> float:
    """Forward operations for ``tokens`` positions that together attend to
    ``attended`` (position, key) pairs, with the head applied at
    ``head_tokens`` of them."""
    n_layers, d, _, h, _, hd, vocab = _dims(c)
    mm = 2.0 * tokens * n_layers * layer_matmul_params(c)
    att = 4.0 * n_layers * h * hd * attended
    return mm + att + 2.0 * head_tokens * d * vocab


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal sequence of ``seq`` positions attends."""
    return seq * (seq + 1) // 2


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Forward and backward (three forwards' worth) of one step."""
    return 3.0 * forward_flops(c, batch * seq, batch * causal_pairs(seq),
                               batch * seq)

