"""How a run is judged, on small made-up readings: the gaps between the
program's and the reference's norms, the rows of a token table that
count, the leaves left out, the checks against the limits; and the seeds
that make a run's weights and data."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the program and the benchmark on the path)
from benchmarks.chip import reference, run, weights
from benchmarks.chip.runners import train

V = 6   # vocabulary of the made-up tables


def _readings(grad, change, rest=None, losses=(2.0, 1.9, 1.8)):
    rest = change if rest is None else rest
    return {"losses": list(losses), "grad": dict(grad),
            "change": dict(change), "rest": dict(rest)}


REF = _readings(grad={"a": 1.0, "b": 2.0, "embed": 1.5},
                change={"a": 0.1, "b": 0.2, "embed": 0.3},
                rest={"a": 0.1, "b": 0.2, "embed": 0.05})


def test_identical_readings_have_no_gap():
    g = train.compare(REF, REF, 1e-3)
    assert g["loss_gap"] == g["grad_gap"] == g["change_gap"] == 0.0
    assert g["leaves_out"] == 0


def test_loss_gap_is_relative_to_the_reference_at_the_worst_step():
    prog = dict(REF, losses=[2.0, 1.9 * 1.01, 1.8 * 0.999])
    assert train.compare(prog, REF, 1e-3)["loss_gap"] == pytest.approx(0.01)


def test_gap_of_norms_not_norm_of_difference():
    prog = dict(REF, grad={"a": 1.0, "b": 2.2, "embed": 1.5})
    g = train.compare(prog, REF, 1e-3)
    assert g["grad_gap"] == pytest.approx(0.1)
    assert g["worst"]["grad"][0] == "['b']"


def test_small_leaf_gap_is_measured_against_the_median_leaf():
    ref = _readings(grad={"a": 1.0, "b": 2.0, "tiny": 1e-3},
                    change={"a": 0.1, "b": 0.2, "tiny": 1e-3})
    prog = dict(ref, change={"a": 0.1, "b": 0.2, "tiny": 2e-3})
    g = train.compare(prog, ref, 1e-4)
    assert g["change_gap"] == pytest.approx(1e-3 / 0.1)


def test_leaf_with_no_gradient_is_left_out_of_the_change():
    ref = _readings(grad={"a": 1.0, "b": 2.0, "bias": 1e-9},
                    change={"a": 0.1, "b": 0.2, "bias": 1e-9})
    prog = dict(ref, change={"a": 0.1, "b": 0.2, "bias": 5.0})
    g = train.compare(prog, ref, 1e-3)
    assert g["leaves_out"] == 1 and g["change_gap"] == 0.0


def test_absent_rows_are_read_and_not_compared():
    prog = dict(REF, rest={"a": 0.1, "b": 0.2, "embed": 0.5})
    g = train.compare(prog, REF, 1e-3)
    assert g["change_gap"] == 0.0
    assert g["rest"] == {"['embed']": [0.5, 0.05]}


def test_unchanged_state_reads_one():
    prog = dict(REF, change={"a": 0.0, "b": 0.0, "embed": 0.0})
    assert train.compare(prog, REF, 1e-3)["change_gap"] == 1.0


def test_present_rows_mark_inputs_and_labels():
    b = {"tokens": np.array([[0, 1]]), "labels": np.array([[1, 4]])}
    rows = reference.present_rows([b], V)
    assert rows.tolist() == [True, True, False, False, True, False]


def test_row_mask_applies_to_token_tables_only():
    rows = jnp.asarray([True, False, True, False, False, False])
    tree = {"embed": jnp.ones((V, 3)), "w": jnp.ones((4, 3)),
            "head": jnp.ones((3, V))}
    n = reference.leaf_norms(tree, rows)
    assert float(n["embed"]) == pytest.approx(math.sqrt(2 * 3))
    assert float(n["w"]) == pytest.approx(math.sqrt(12))
    assert float(n["head"]) == pytest.approx(math.sqrt(3 * V))


def test_change_norms_split_present_and_absent_rows():
    rows = jnp.asarray([True, True, False, False, False, False])
    old = {"embed": jnp.zeros((V, 2))}
    new = {"embed": jnp.arange(V * 2, dtype=jnp.float32).reshape(V, 2)}
    present, absent = reference.change_norms(new, old, rows)
    full = float(jnp.linalg.norm(new["embed"]))
    assert float(present["embed"]) ** 2 + float(absent["embed"]) ** 2 == \
        pytest.approx(full ** 2)
    assert float(present["embed"]) == pytest.approx(
        float(jnp.linalg.norm(new["embed"][:2])))


def test_checks_hold_what_the_limits_name():
    gaps = {"loss_gap": 1e-4, "grad_gap": 0.01, "change_gap": 0.02,
            "change_gap_median": 1e-4}
    got = train.checks(gaps, True, {"leaf_cut": 1e-3, "grad_gap": 0.02})
    assert got == {"finite_loss": (1, 1, ">="),
                   "grad_gap": (0.01, 0.02, "<=")}


@pytest.mark.parametrize("checks, ok", [
    ({"g": (0.01, 0.02, "<=")}, True),
    ({"g": (0.02, 0.02, "<=")}, True),
    ({"g": (0.03, 0.02, "<=")}, False),
    ({"g": (float("nan"), 0.02, "<=")}, False),
    ({"finite_loss": (0, 1, ">=")}, False),
    ({"finite_loss": (1, 1, ">="), "g": (0.01, 0.02, "<=")}, True),
    ({"finite_loss": (1, 1, ">="), "g": (0.5, 0.02, "<=")}, False),
], ids=["under", "at", "over", "nan", "loss-not-finite", "both-hold",
        "one-fails"])
def test_judge(checks, ok):
    assert run.judge(checks) is ok


@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**31, 2**32 + 5,
                                  3_000_000_000_017, 2**62 + 3])
def test_data_seed_is_31_bits_from_the_whole_seed(seed):
    d = train.data_seed(seed)
    assert 0 <= d < 2**31 and d == train.data_seed(seed)
    assert d != train.data_seed(seed + 2**32)


def test_weight_key_reads_bits_above_32():
    a = np.asarray(weights.seed_key(5, 1))
    b = np.asarray(weights.seed_key(5 + 2**32, 1))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, np.asarray(weights.seed_key(5, 1)))


@pytest.mark.parametrize("step, want", [
    (0, 0.0), (50, 1.5e-4), (100, 3e-4), (10_000, 3e-5)])
def test_reference_learning_rate(step, want):
    opt = {"lr": 3e-4, "warmup": 100, "total_steps": 10_000,
           "min_lr_ratio": 0.1}
    assert reference._lr(opt, step) == pytest.approx(want)
