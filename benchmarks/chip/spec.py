"""Find a cell's parts by name: the workload entry of BENCHMARK.json, its
configuration file, the architecture module that file names, its traffic
file, its limits, the runner of its mix's kind and its per-layer metric
readers. Nothing here lists a cell, an architecture, a mix or a metric:
each is a file of its own under this directory, found from the names in
BENCHMARK.json and the configuration file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"

# Hugging Face config keys -> repro ModelConfig fields, a dotted path into
# a nested group (``mla``, ``moe``). Every width the configuration file
# states must equal the program's configuration.
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
    "kv_lora_rank": "mla.kv_lora_rank",
    "q_lora_rank": "mla.q_lora_rank",
    "qk_nope_head_dim": "mla.qk_nope_head_dim",
    "qk_rope_head_dim": "mla.qk_rope_head_dim",
    "v_head_dim": "mla.v_head_dim",
    "moe_intermediate_size": "moe.d_expert",
    "n_routed_experts": "moe.num_experts",
    "num_experts_per_tok": "moe.top_k",
    "n_shared_experts": "moe.num_shared",
    "first_k_dense_replace": "moe.first_k_dense",
}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file
    arch: ModuleType        # the architecture module it names
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
    arch_mod = arch(config, cfg_entry["file"])
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, arch=arch_mod, traffic_name=w["traffic"],
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)


def arch(config: dict, file: str) -> ModuleType:
    """The architecture module that the configuration file ``file`` names
    under ``"reference"``: ``archs/<name>.py``. It defines
    ``model_spec(config)``, the plain reference's
    ``forward(params, tokens, real, spec, man_bits, remat)``, the weights
    plan (``CONTRACT``, ``ONES``, ``EMBED``) and
    ``train_flops(config, batch, seq)``. A file that names none, or names
    one that is not there, is an error: there is no default."""
    name = config.get("reference")
    if not name:
        raise KeyError(f"{file} names no architecture module under "
                       f"\"reference\"")
    path = HERE / "archs" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{file} names the architecture module "
                                f"{name!r}, and {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.archs.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


def _override(obj, changes: dict):
    """``obj`` (a frozen dataclass) with ``changes``; a dict given for a
    nested group (``{"moe": {"num_experts": 8}}``) changes fields of it."""
    return dataclasses.replace(obj, **{
        k: _override(getattr(obj, k), v) if isinstance(v, dict) else v
        for k, v in changes.items()})


def _field(cfg, hf_key: str) -> str:
    """The dotted ModelConfig field that the file's ``hf_key`` states.
    Where leading dense layers have a width of their own
    (``moe.dense_d_ff``, as ``models/model.py`` builds them), the file's
    ``intermediate_size`` is that width."""
    if hf_key == "intermediate_size" and cfg.moe is not None \
            and cfg.moe.dense_d_ff:
        return "moe.dense_d_ff"
    return HF_TO_REPRO[hf_key]


def _get(cfg, dotted: str):
    obj = cfg
    for part in dotted.split("."):
        if obj is None:
            raise ValueError(f"the program's configuration has no "
                             f"{dotted.split('.')[0]} group")
        obj = getattr(obj, part)
    return obj


def model_config(config: dict):
    """The program's ModelConfig for a configuration file, checked key by
    key against the file's published sizes: each key of ``HF_TO_REPRO``
    that the file has, a ``null`` there being ``None`` in the program."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = _override(get_config(prog["arch"]), prog.get("overrides", {}))
    for hf_key in HF_TO_REPRO:
        if hf_key not in config:
            continue
        field = _field(cfg, hf_key)
        got = _get(cfg, field)
        want = config[hf_key]
        if want is None or got is None:
            same = want is got
        else:
            same = (float(got) if isinstance(want, float) else got) == want
        if not same:
            raise ValueError(f"{config['name']}: {hf_key}={want} but the "
                             f"program's {field} is {got}")
    return cfg
