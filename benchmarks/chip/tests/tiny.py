"""Tiny versions of the benchmark's cells, for runs on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import spec  # noqa: E402

TINY = dict(num_hidden_layers=1, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, intermediate_size=256,
            vocab_size=4096)
OVERRIDES = dict(n_layers=1, d_model=128, n_heads=4, n_kv_heads=2,
                 head_dim=64, d_ff=256, vocab_size=4096)
PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11}


def cell(name: str, limits: dict):
    """The cell ``name`` (``<config>.<mix>``) at a size the CPU runs in
    seconds, with the given limits. It need not be in BENCHMARK.json: the
    configuration and the mix are read from their files."""
    config_name, mix = name.rsplit(".", 1)
    file = spec.HERE / "configs" / f"{config_name}.json"
    config = json.loads(file.read_text())
    c = spec.Cell(
        name=name, chips=1, config_name=config_name, config=config,
        arch=spec.arch(config, str(file)), traffic_name=mix,
        traffic=json.loads((spec.HERE / "traffic" / f"{mix}.json")
                           .read_text()),
        limits={}, end_to_end=[], per_layer=[])
    prog = dict(c.config["program"])
    prog["overrides"] = dict(prog.get("overrides", {}), **OVERRIDES)
    c.config = dict(c.config, program=prog, **TINY)
    c.end_to_end = [{"name": n, "unit": ""} for n in (
        "train_tokens_per_s", "setup_s")]
    c.traffic = dict(c.traffic, batch=4, seq=32, mesh=[1])
    c.limits = dict(c.limits, **limits)
    return c
