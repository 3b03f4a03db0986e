"""The yardstick every roofline and utilisation share divides by: the
chip's peaks, by device kind, and what every architecture's count of
operations shares. Each architecture counts its own operations from the
shapes its configuration file states, in ``archs/<name>.py``
(``train_flops``).

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


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal sequence of ``seq`` positions attends."""
    return seq * (seq + 1) // 2
