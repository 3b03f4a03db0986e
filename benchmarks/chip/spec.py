"""Find a cell's parts by name: the workload entry of BENCHMARK.json, its
configuration file, its traffic file, its limits, the runner of its mix's
kind and its per-layer metric readers. Nothing here lists a cell, a mix or a metric: each is a file of
its own under this directory, found from the names in BENCHMARK.json.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"

# Hugging Face config keys -> repro ModelConfig fields. Every width the
# configuration file states must equal the program's configuration.
HF_TO_REPRO = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "resolved_head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file
    traffic_name: str
    traffic: dict           # the traffic mix's parameters
    limits: dict            # the numbers compared, each with its limit
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]   # the per-layer metrics this cell reports


def load_benchmark(path: Path = BENCHMARK) -> dict:
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or load_benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits, end_to_end=e2e, per_layer=per_layer)


def runner(kind: str):
    """The module that runs mixes of ``kind``: ``runners/<kind>.py``, with
    ``run(cell, seed, seconds, trace, *, device, chips, t_start)``."""
    if not (HERE / "runners" / f"{kind}.py").exists():
        raise KeyError(f"no runner for mixes of kind {kind!r}")
    return importlib.import_module(f"benchmarks.chip.runners.{kind}")


def reader(metric_name: str) -> Callable:
    """The per-layer reader for ``metric_name``: ``layer_metrics/<name>.py``
    or, for a qualified name such as ``mfu.train``, the file of its stem
    (``layer_metrics/mfu.py``). Each file defines ``read(ctx)``, which
    returns a number or None when it finds nothing to read."""
    d = HERE / "layer_metrics"
    path = d / f"{metric_name}.py"
    if not path.exists():
        path = d / f"{metric_name.split('.')[0]}.py"
    mod_name = "chip_layer_metric_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_config(config: dict):
    """The program's ModelConfig for a configuration file, checked key by
    key against the file's published sizes."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["arch"], **prog.get("overrides", {}))
    for hf_key, field in HF_TO_REPRO.items():
        if hf_key not in config:
            continue
        got = getattr(cfg, field)
        want = config[hf_key]
        if (float(got) if isinstance(want, float) else got) != want:
            raise ValueError(f"{config['name']}: {hf_key}={want} but the "
                             f"program's {field} is {got}")
    return cfg
