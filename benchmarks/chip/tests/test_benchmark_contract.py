"""BENCHMARK.json against the rules a benchmark file keeps: its keys and
their limits, names and units, the cells and configurations it names and
the files it finds by those names, the metrics each cell reports, and the
chip time a full check of 24 cells at ``run_seconds`` takes. A later change
that adds a cell, a mix or a metric is held to the same rules."""
from __future__ import annotations

import json
import math
import re

import pytest

import tiny  # noqa: F401  (puts the program and the benchmark on the path)
from benchmarks.chip import spec
from benchmarks.chip.runners import train

BENCH = spec.load_benchmark()
ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
# Keys that state a width, which no cut to a chip may change: hidden,
# intermediate, latent, state, projection and window sizes, head counts and
# sizes, ranks, expansion factors and the experts each token is routed to.
# Counts of layers, of experts and of vocabulary rows are scale, and a cut
# may change them if ``reduced`` lists them.
WIDTH = re.compile(r"hidden_size|intermediate|latent|state|proj|window|"
                   r"head|_dim$|_rank$|expan|experts_per_tok|top_?k|"
                   r"d_model|n_embd|d_ff|conv")
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def _e2e_of(cell: str):
    return [m for m in BENCH["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def _per_layer_of(cell: str):
    names = {m["name"] for m in _e2e_of(cell)}
    return [m for m in BENCH["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def rule_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def rule_paths_are_the_benchmarks_own_directories():
    paths = BENCH["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/")
        assert ".." not in p.split("/")
        assert (ROOT / p).is_dir()


def rule_command_names_only_files_under_paths():
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        assert not w.startswith("/") and ".." not in w.split("/")
        if (ROOT / w).is_file():
            assert any(w.startswith(p.rstrip("/") + "/")
                       for p in BENCH["paths"]), w
    assert any((ROOT / w).is_file() for w in cmd)


def rule_run_seconds_fits_a_full_check_of_24_cells():
    s = BENCH["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (s + 60) + cells * 2 * 90 + 1200 <= 43200


def rule_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m


def rule_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names)), names


def rule_entries_have_only_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m


def config_matches_its_file(entry: dict, on_disk: dict):
    assert entry["source"].startswith("https://") and _line(entry["source"])
    assert _line(entry["why"])
    assert on_disk["source"] == entry["source"]
    assert sorted(on_disk["reduced"]) == sorted(entry["reduced"])


def config_reduces_no_width(entry: dict, on_disk: dict):
    assert len(entry["reduced"]) <= 16
    for k in entry["reduced"]:
        assert NAME.match(k), (entry["name"], k)
        assert not WIDTH.search(k), (entry["name"], k)


def config_publishes_what_it_reduced(entry: dict, on_disk: dict):
    """Each key in ``reduced`` is a key of the file, whose ``published``
    gives the source's value of exactly those keys, each a value the
    file's own differs from."""
    published = on_disk.get("published", {})
    assert set(published) == set(entry["reduced"]), (entry["name"],
                                                     published)
    for k in entry["reduced"]:
        assert k in on_disk, (entry["name"], k)
        assert published[k] != on_disk[k], (entry["name"], k)


CONFIG_RULES = (config_matches_its_file, config_reduces_no_width,
                config_publishes_what_it_reduced)


def _each_config(check):
    for c in BENCH["configs"]:
        check(c, json.loads((ROOT / c["file"]).read_text()))


def rule_configs_are_files_under_paths_used_by_a_cell():
    confs = BENCH["configs"]
    assert 1 <= len(confs) <= 24
    files = [c["file"] for c in confs]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    for c in confs:
        assert c["name"] in used, c["name"]
        assert any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in BENCH["paths"])
    _each_config(config_matches_its_file)


def rule_reduced_names_no_width():
    _each_config(config_reduces_no_width)


def rule_reduced_keys_are_published_and_differ():
    _each_config(config_publishes_what_it_reduced)


def rule_cells_name_a_config_a_mix_and_their_chips():
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    confs = {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    for w in cells:
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (spec.HERE / "traffic" / f"{w['traffic']}.json").is_file()
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)


def rule_end_to_end_metrics_and_bounds():
    e2e = BENCH["end_to_end"]
    assert 1 <= len(e2e) <= 16
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in e2e:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in E2E_SOURCES, m
        assert set(m.get("workloads", [])) <= set(CELLS), m


def rule_per_layer_metrics_move_an_end_to_end_metric():
    pl = BENCH["per_layer"]
    assert 1 <= len(pl) <= 128
    for m in pl:
        assert m["source"] in SOURCES and _line(m["layer"]), m
        assert m["moves"] in E2E, m
        for cell in m.get("workloads", []):
            assert cell in CELLS, m
            assert m["moves"] in {x["name"] for x in _e2e_of(cell)}, m


def rule_shares_are_percent():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"] \
                or "share" in m["name"]:
            assert m["unit"] == "%", m


def rule_every_cell_reports_setup_another_metric_and_a_layer():
    for cell in CELLS:
        names = {m["name"] for m in _e2e_of(cell)}
        assert "setup_s" in names and len(names) >= 2, cell
        assert _per_layer_of(cell), cell


def rule_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    lowered = {x.lower() for x in layers}
    assert len(lowered) == len(layers), layers


def rule_every_cell_has_limits_the_runner_reads():
    for cell, w in CELLS.items():
        lim = json.loads((spec.HERE / "limits" / f"{cell}.json").read_text())
        kind = json.loads((spec.HERE / "traffic" / f"{w['traffic']}.json")
                          .read_text())["kind"]
        assert (spec.HERE / "runners" / f"{kind}.py").is_file()
        if kind == "train":
            assert "leaf_cut" in lim
            assert set(lim) - {"leaf_cut"} <= set(train.READ), lim
            assert set(lim) & set(train.READ), lim


def rule_every_per_layer_metric_has_a_reader_file():
    d = spec.HERE / "layer_metrics"
    for m in BENCH["per_layer"]:
        stem = m["name"].split(".")[0]
        assert ((d / f"{m['name']}.py").is_file()
                or (d / f"{stem}.py").is_file()), m["name"]


def rule_files_under_paths_have_plain_names():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            rel = f.relative_to(ROOT).as_posix()
            assert PATH.match(rel), rel


def rule_peaks_table_names_its_source():
    table = json.loads((spec.HERE / "peaks.json").read_text())
    assert _line(table["source"])
    for kind, peaks in table["devices"].items():
        assert peaks["bf16_flops"] > 0 and peaks["hbm_bytes_per_s"] > 0
        assert all(isinstance(v, (int, float)) and math.isfinite(v)
                   for v in peaks.values()), kind


RULES = [f for n, f in sorted(globals().items()) if n.startswith("rule_")]


@pytest.mark.parametrize("rule", RULES, ids=[f.__name__[5:] for f in RULES])
def test_benchmark_file_keeps_the_rule(rule):
    rule()


# A configuration cut to one chip's share of an expert-parallel deployment:
# six of 27 layers, eight of 64 experts, an eighth of the vocabulary.
SOURCE = ("https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
          "config.json")
CUT = {"num_hidden_layers": (6, 27), "n_routed_experts": (8, 64),
       "vocab_size": (20480, 163840)}
WIDTHS = {"hidden_size": (1024, 2048), "moe_intermediate_size": (704, 1408),
          "kv_lora_rank": (256, 512), "num_experts_per_tok": (3, 6)}


def _cut_config(changes: dict, published: dict = None, absent=()):
    """(entry, file) of a configuration that changes ``changes`` (key ->
    (file's value, source's value)) and lists them in ``reduced``; the
    keys ``absent`` are left out of the file."""
    on_disk = {"name": "cut", "source": SOURCE, "hidden_size": 2048,
               "moe_intermediate_size": 1408, "kv_lora_rank": 512,
               "num_experts_per_tok": 6, "num_hidden_layers": 27,
               "n_routed_experts": 64, "vocab_size": 163840}
    on_disk.update({k: mine for k, (mine, _src) in changes.items()
                    if k not in absent})
    on_disk["reduced"] = list(changes)
    on_disk["published"] = (published if published is not None else
                            {k: src for k, (_mine, src) in changes.items()})
    entry = {"name": "cut", "source": SOURCE, "file": "cut.json",
             "reduced": list(changes), "why": "latent attention, experts"}
    return entry, on_disk


# case -> (entry, file), and the check that refuses it (None: admitted).
REDUCED_CASES = {
    "depth-experts-vocab": (_cut_config(CUT), None),
    **{f"width-{k}": (_cut_config({k: v}), config_reduces_no_width)
       for k, v in WIDTHS.items()},
    "key-not-in-file": (_cut_config({"num_nextn_predict_layers": (0, 1)},
                                    absent=("num_nextn_predict_layers",)),
                        config_publishes_what_it_reduced),
    "published-equals-file": (_cut_config(
        CUT, {"num_hidden_layers": 6, "n_routed_experts": 64,
              "vocab_size": 163840}), config_publishes_what_it_reduced),
    "changed-and-not-published": (_cut_config(CUT, {}),
                                  config_publishes_what_it_reduced),
}


@pytest.mark.parametrize("case", REDUCED_CASES)
def test_reduced_admits_depth_and_refuses_widths(case):
    (entry, on_disk), refuses = REDUCED_CASES[case]
    for check in CONFIG_RULES:
        if check is refuses:
            with pytest.raises(AssertionError):
                check(entry, on_disk)
        else:
            check(entry, on_disk)
