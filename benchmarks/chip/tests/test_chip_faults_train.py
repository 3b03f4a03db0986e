"""Whole training runs of the benchmark on the CPU at a tiny size. The look
for a chip is skipped and the rest of a run is driven, once sound and once
with each fault a one-chip training cell can have planted in its step: a
step that returns its state unchanged, half of the batch left out with the
mean taken over the rest, and an update that leaves one weight unmoved.
``correct`` must come out false for each. The control, the reference one
precision step below the configuration (E4M3 scalar products where it
states E4M4), must come out not correct too, judged as a run is.

The tiny cell draws its tokens as the real cell does (uniform over the
vocabulary), so the half batch shows where it shows on the chip: in the
change of the embedding rows of the tokens present. The limits are this
tiny size's: sound runs read first-gradient norms within 0.8% and changes
within 0.2% of the reference; the control reads 2% to 4% on the first
gradient, the half batch 3% to 5% on it and 2.6% to 4.2% on the change."""
from __future__ import annotations

import jax
import pytest

import tiny
from benchmarks.chip import control, run, spec, work

LIMITS = {"leaf_cut": 1e-3, "grad_gap": 0.012, "change_gap": 0.008}
SEED = 3_000_000_000_011


@pytest.fixture(autouse=True)
def _cpu_peaks(monkeypatch):
    monkeypatch.setattr(work, "peaks", lambda kind: tiny.PEAKS)


def _train(fault=None):
    c = tiny.cell("qwen3-0.6b.train", LIMITS)
    return run.run_cell(c, SEED, 0.3, False, require_chip=False, fault=fault)


def _state_unchanged(fn):
    return jax.jit(lambda s, b: (s, fn(s, b)[1]))


def _half_batch(fn):
    return jax.jit(lambda s, b: fn(s, {k: v[: v.shape[0] // 2]
                                       for k, v in b.items()}),
                   donate_argnums=(0,))


def _one_weight_unmoved(fn):
    def step(s, b):
        new, m = fn(s, b)
        groups = list(new.params["groups"])
        p = dict(groups[0]["params"])
        p["mixer"] = dict(p["mixer"], wq=s.params["groups"][0]["params"]
                          ["mixer"]["wq"])
        groups[0] = dict(groups[0], params=p)
        return new._replace(params=dict(new.params, groups=groups)), m

    return jax.jit(step)


def test_sound_run_is_correct():
    out = _train()
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _one_weight_unmoved],
                         ids=["state_unchanged", "half_batch",
                              "one_weight_unmoved"])
def test_fault_in_the_step_is_not_correct(fault):
    out = _train(fault)
    assert not out["correct"], out["checks"]
    if fault is not _half_batch:
        assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_control_is_not_correct():
    c = tiny.cell("qwen3-0.6b.train", LIMITS)
    res = spec.runner("train").run(
        c, SEED, 0.3, False, device=run.device_info(1, False), chips=1,
        t_start=run.T_START)
    got = control.training_control(c, SEED, res)
    assert not got["control"]["correct"], got
    assert not got["half_batch"]["correct"], got
