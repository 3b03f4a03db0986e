"""The chip benchmark's parts that need no chip: finding cells, configs,
mixes, runners and readers by name; the reduction from a recorded trace
to numbers; the reference's arithmetic; and the refusal to run without a
TPU."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the program and the benchmark on the path)
from benchmarks.chip import reference, spec, tracing, weights, work

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_parts_found_by_name(name):
    c = spec.load_cell(name)
    assert c.config["name"] == c.config_name
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert callable(spec.runner(c.traffic["kind"]).run)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_file_matches_the_program(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    config = json.loads((CHIP.parents[1] / entry["file"]).read_text())
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    spec.model_config(config)   # raises on any width that differs


@pytest.mark.parametrize("name", METRICS)
def test_every_per_layer_metric_has_a_reader(name):
    assert callable(spec.reader(name))


def test_readers_find_nothing_in_an_empty_context():
    ctx = {"trace": None, "span_s": 1.0, "flops": 0.0,
           "config": spec.load_cell(CELLS[0]).config, "peaks": tiny.PEAKS,
           "chips": 1}
    for name in METRICS:
        assert spec.reader(name)(ctx) is None, name


def test_no_runner_for_an_unknown_kind():
    with pytest.raises(KeyError):
        spec.runner("no-such-kind")


# A recorded trace: the first 60 ms of a traced decode-backlog window on a
# TPU v5 lite (device ops and the benchmark's host spans).
REC = HERE / "data" / "recorded_trace.json"


def _rec():
    return json.loads(REC.read_text())


def test_trace_window_and_busy_share():
    tr = _rec()
    lo, hi = tracing.window(tr)
    assert hi > lo
    busy = tracing.busy_s(tr)
    assert 0 < busy <= hi - lo
    # The union never counts an overlap twice.
    evs = [(s, e) for evs in tr["device"].values() for _, s, e in evs]
    assert busy <= sum(min(e, hi) - max(s, lo) for s, e in evs
                       if e > lo and s < hi) / len(tr["device"]) + 1e-12


def test_trace_top_ops_and_idle_gaps_add_up():
    tr = _rec()
    lo, hi = tracing.window(tr)
    ops = tracing.top_ops(tr, k=10)
    assert len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = tracing.idle_gaps(tr, k=1000)
    first = sorted(tr["device"])[0]
    busy0 = sum(e - s for s, e in tracing.union(
        (s, e) for _, s, e in tracing._clip(tr["device"][first], lo, hi)))
    assert sum(t for _, t in gaps) == pytest.approx((hi - lo) - busy0)


def test_trace_kernel_found_by_name_and_loops_not_counted_as_ops():
    tr = _rec()
    assert tracing.op_seconds(tr, r"^no such op$") is None
    kernel = tracing.op_seconds(tr, r"^_gqa_pallas(\.\d+)?$")
    assert 0 < kernel < tracing.busy_s(tr)
    names = [n for n, _ in tracing.top_ops(tr, 50)]
    assert "_gqa_pallas" in names
    assert "while" not in names         # the layer loop holds the others


def test_union_merges_overlaps():
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_e4m4_alignment_on_known_values():
    # Block of 64: one element at 1.0 and one at 3/64 of it. After the
    # prescale 1.0 sits at 2^7; 3/64 is 6 binades down, so its shifted
    # significand is dropped (shift > 4 bits); 0.5 keeps half its bits.
    x = np.zeros((1, 64), np.float32)
    x[0, 0], x[0, 1], x[0, 2] = 1.0, 3.0 / 64, 0.5
    vals, s = reference.aligned(x, 1)
    got = np.asarray(vals)[0] / float(s)
    assert got[0] == 1.0 and got[1] == 0.0 and got[2] == 0.5
    # Rounding to four mantissa bits: 1 + 1/32 is a tie and rounds to even.
    x[0, 3] = 1.0 + 1.0 / 32
    vals, s = reference.aligned(x, 1)
    assert np.asarray(vals)[0, 3] / float(s) == 1.0


def test_reference_matmul_is_exact_on_aligned_operands():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 128)).astype(np.float32)
    w = rng.normal(size=(128, 16)).astype(np.float32)
    xa, sx = reference.aligned(x, 1)
    wa, sw = reference.aligned(w, 0)
    want = (np.asarray(xa, np.float64) @ np.asarray(wa, np.float64)
            / (float(sx) * float(sw)))
    got = np.asarray(reference.tf_matmul(x, w), np.float64)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_work_counts_follow_the_shapes():
    cell = spec.load_cell(CELLS[0])
    c, arch = cell.config, cell.arch
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    assert arch.layer_matmul_params(c) == (d * h * hd + 2 * d * kv * hd
                                           + h * hd * d + 3 * d * f)
    assert work.causal_pairs(4) == 10
    per_token = 2 * c["num_hidden_layers"] * arch.layer_matmul_params(c)
    head = 2 * d * c["vocab_size"]
    pairs = 4 * c["num_hidden_layers"] * h * hd * work.causal_pairs(4)
    assert arch.train_flops(c, 1, 4) == 3 * (4 * per_token + pairs
                                             + 4 * head)


ARCH_PARTS = ("model_spec", "forward", "CONTRACT", "ONES", "EMBED",
              "train_flops")


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_architecture_module_found_by_name(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    config = json.loads((CHIP.parents[1] / entry["file"]).read_text())
    arch = spec.arch(config, entry["file"])
    assert Path(arch.__file__) == CHIP / "archs" / f"{config['reference']}.py"
    assert all(hasattr(arch, part) for part in ARCH_PARTS)
    cell = next(w["name"] for w in BENCH["workloads"] if w["config"] == name)
    assert spec.load_cell(cell).arch.__file__ == arch.__file__


@pytest.mark.parametrize("reference, named", [
    (None, "qwen3-0.6b.json"), ("no_such_arch", "archs/no_such_arch.py")],
    ids=["names-none", "module-missing"])
def test_missing_architecture_module_stops_the_run(
        reference, named, tmp_path, capsys, monkeypatch):
    from benchmarks.chip import run

    bench = json.loads(json.dumps(BENCH))
    entry = {c["name"]: c for c in bench["configs"]}[
        spec.load_cell(CELLS[0]).config_name]
    config = json.loads((CHIP.parents[1] / entry["file"]).read_text())
    config.pop("reference")
    if reference:
        config["reference"] = reference
    entry["file"] = str(tmp_path / Path(entry["file"]).name)
    Path(entry["file"]).write_text(json.dumps(config))
    monkeypatch.setattr(spec, "load_benchmark", lambda: bench)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "no result" in out.err and named in out.err, out.err


def _program_leaves(cfg):
    import jax

    from repro.models import model as model_lib

    shapes = jax.eval_shape(lambda k: model_lib.init(cfg, k),
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_flatten_with_path(shapes)[0]


def test_weights_plan_covers_every_leaf_of_the_program():
    c = tiny.cell(CELLS[0], {})
    flat = _program_leaves(spec.model_config(c.config))
    kinds = weights.plan(flat, c.arch)     # raises on a leaf it lacks
    assert len(kinds) == len(flat)
    assert {k for k, _ in kinds} == {"ones", "normal"}


def test_weights_plan_lacking_a_leaf_names_it():
    import types

    c = tiny.cell(CELLS[0], {})
    stub = types.ModuleType("stub_arch")
    stub.ONES, stub.EMBED = c.arch.ONES, c.arch.EMBED
    stub.CONTRACT = {k: v for k, v in c.arch.CONTRACT.items() if k != "wq"}
    with pytest.raises(ValueError, match="'wq'"):
        weights.plan(_program_leaves(spec.model_config(c.config)), stub)


# A tiny deepseek-v3 program (latent attention, routed and shared experts,
# one leading dense layer) and the file that states its widths.
TINY_MLA_MOE = {
    "num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 384, "vocab_size": 512,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "moe_intermediate_size": 64,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "first_k_dense_replace": 1}
TINY_MLA_MOE_PROGRAM = {
    "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
    "d_ff": 256, "vocab_size": 512,
    "mla": {"q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
            "qk_rope_head_dim": 16, "v_head_dim": 32},
    "moe": {"num_experts": 8, "top_k": 2, "d_expert": 64, "num_shared": 1,
            "first_k_dense": 1, "dense_d_ff": 384, "shared_d_ff": 64}}


def _tiny_mla_moe(file_change=None, q_lora_rank=None):
    program = dict(TINY_MLA_MOE_PROGRAM,
                   mla=dict(TINY_MLA_MOE_PROGRAM["mla"],
                            q_lora_rank=q_lora_rank))
    return dict(TINY_MLA_MOE, **(file_change or {}), name="tiny-mla-moe",
                program={"arch": "deepseek-v3-671b", "overrides": program})


def test_latent_and_expert_widths_equal_to_the_programs_pass():
    cfg = spec.model_config(_tiny_mla_moe())
    assert cfg.mla.q_lora_rank is None and cfg.mla.kv_lora_rank == 32
    assert cfg.moe.num_experts == 8 and cfg.moe.dense_d_ff == 384


# One width differs. The file's intermediate_size is the leading dense
# layer's width (384), so the program's d_ff (256) is no match for it; a
# null in the file is None in the program, and nothing else.
@pytest.mark.parametrize("key, value, q_lora_rank", [
    ("kv_lora_rank", 64, None), ("qk_nope_head_dim", 64, None),
    ("qk_rope_head_dim", 32, None), ("v_head_dim", 64, None),
    ("moe_intermediate_size", 128, None), ("n_routed_experts", 16, None),
    ("num_experts_per_tok", 4, None), ("n_shared_experts", 2, None),
    ("first_k_dense_replace", 2, None), ("intermediate_size", 256, None),
    ("q_lora_rank", 16, None), ("q_lora_rank", None, 1536)],
    ids=lambda v: str(v))
def test_latent_and_expert_width_that_differs_is_refused(key, value,
                                                         q_lora_rank):
    config = _tiny_mla_moe({key: value}, q_lora_rank)
    with pytest.raises(ValueError, match=key):
        spec.model_config(config)


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError):
        work.peaks("no such chip")
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_no_chip_no_result(capsys, monkeypatch):
    from benchmarks.chip import run

    # main() points the compile cache at the checkout; keep that out of
    # the other tests of this process.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no result" in out.err


def test_trace_leaves_drop_the_ops_that_hold_others():
    evs = [("while", 0.0, 10.0), ("fusion.1", 1.0, 2.0),
           ("fusion.2", 3.0, 4.0), ("copy", 11.0, 12.0)]
    assert [n for n, _, _ in tracing.leaves(evs)] == ["fusion.1", "fusion.2",
                                                      "copy"]


def test_trace_breakdown_by_op_and_by_host_span():
    tr = {"device": {"0": [("fusion.1", 1.0, 2.0), ("fusion.7", 3.0, 3.5),
                           ("dot.2", 5.0, 8.0)]},
          "host": [("bench.window", 0.0, 10.0), ("bench.step", 0.0, 4.0),
                   ("bench.feed", 4.0, 5.0)]}
    assert tracing.top_ops(tr) == [["dot", 3.0], ["fusion", 1.5]]
    gaps = dict((n, t) for n, t in tracing.idle_gaps(tr))
    # Gaps [0, 1] and [2, 3] fall in the step, [3.5, 5] in the feed.
    assert gaps == {"bench.step": pytest.approx(2.0),
                    "bench.feed": pytest.approx(1.5),
                    "host: no bench span": pytest.approx(2.0)}
    assert tracing.busy_s(tr) == pytest.approx(4.5)


def test_unknown_workload_no_result(capsys, monkeypatch):
    from benchmarks.chip import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = run.main(["--workload", "no-such-cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""


def test_no_result_with_only_the_benchmarks_files(tmp_path):
    import shutil
    import subprocess
    import sys

    root = CHIP.parents[1]
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(root / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable if w in ("python", "python3") else w
           for w in BENCH["command"]]
    proc = subprocess.run(
        cmd + ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
               "--trace", "0"], cwd=tmp_path, capture_output=True,
        text=True, timeout=120, env={"PATH": "/usr/bin:/bin",
                                     "HOME": str(tmp_path),
                                     "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
