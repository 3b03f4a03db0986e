"""Digital twin (DESIGN.md §6): mapper conservation/utilization, mapper vs
weight-cache rule agreement on every pool config, census-driven energy
(the paper's 22.1 TOPS/W headline), and trainer telemetry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, reduced_for_smoke
from repro.configs.timefloats_mlp import CONFIG as MLP_CFG
from repro.core import energy as core_energy
from repro.core import timefloats as tf
from repro.core.timefloats import TFConfig
from repro.hw import energy as hw_energy
from repro.hw import schedule as sched
from repro.hw.arrays import TileGeometry
from repro.hw.mapper import map_edge_mlp, map_model, map_params
from repro.models import common
from repro.models import model as M


def _tf_cfg(cfg):
    return dataclasses.replace(cfg, quant="timefloats",
                               tf=TFConfig(mode="separable"))


# ---------------------------------------------------------------------------
# Mapper invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_placement_conservation_and_utilization(arch):
    """Every eligible leaf's rows x cols cells are covered exactly once per
    copy, and utilization is in (0, 1] at leaf and model level."""
    pl = map_model(get_config(arch))
    assert pl.leaves
    for lp in pl.leaves:
        geom = pl.geometry
        assert lp.cells_used_per_copy == lp.rows * lp.cols
        alloc = lp.tiles_r * geom.rows * lp.tiles_c * geom.cols
        assert alloc >= lp.rows * lp.cols          # covered
        assert (lp.tiles_r - 1) * geom.rows < lp.rows      # no overshoot
        assert (lp.tiles_c - 1) * geom.cols < lp.cols
        assert 0.0 < lp.utilization(geom) <= 1.0
    assert 0.0 < pl.utilization <= 1.0
    assert pl.tiles > 0 and pl.macros > 0
    # macros cover the tiles at the configured banking factor
    assert pl.macros * pl.geometry.tiles_per_macro >= pl.tiles


def test_mapped_params_match_spec_counts():
    """Mapped cells + excluded leaves account for every parameter."""
    from repro.models.common import param_count
    from repro.models.model import _strip_kind, model_param_specs

    cfg = get_config("qwen3-0.6b")
    pl = map_model(cfg)
    total = param_count(_strip_kind(model_param_specs(cfg)))
    # qwen3 ties embeddings: the table is gather-read off-chip AND placed
    # as the transposed LM head, so mapped <= total but must cover all
    # dense weights: total - mapped == embed params - head placement.
    assert pl.cells_used <= total + cfg.vocab_size * cfg.d_model
    assert pl.cells_used > 0.9 * total


def test_duplication_scales_tiles_and_writes():
    cfg = get_config("qwen3-0.6b")
    base = map_model(cfg)
    dup = map_model(cfg, geom=TileGeometry(duplication=2))
    assert dup.tiles == 2 * base.tiles
    assert dup.cells_written_per_update == 2 * base.cells_written_per_update
    assert dup.cells_used == base.cells_used  # distinct params unchanged


def test_tile_height_must_match_alignment_block():
    cfg = get_config("qwen3-0.6b")
    with pytest.raises(AssertionError):
        map_model(cfg, geom=TileGeometry(rows=128))


# ---------------------------------------------------------------------------
# Mapper / weight-cache rule agreement (every pool config)
# ---------------------------------------------------------------------------


def _param_and_cache_shapes(cfg):
    """Shapes of the parameters and of their weight cache, with nothing
    allocated or quantized: the mapper and the cache rules read shapes only."""
    params = jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda p: common.build_weight_cache(p, cfg), params)
    return params, cache


@pytest.mark.parametrize("arch", ARCHS)
def test_mapper_agrees_with_weight_cache_rules(arch):
    """The mapper places EXACTLY the leaves build_weight_cache prepares —
    flat keys (incl. the tied-embedding transposed head) and per-group
    stacked keys — so the crossbar inventory and the §3 quantized-operand
    cache can never disagree about what lives in the arrays."""
    cfg = _tf_cfg(reduced_for_smoke(get_config(arch)))
    params, cache = _param_and_cache_shapes(cfg)
    pl = map_params(params, cfg)

    flat_placed = {lp.key for lp in pl.leaves if lp.group is None}
    assert flat_placed == set(cache.flat)
    for gi in range(len(cache.groups)):
        placed = {lp.key for lp in pl.leaves if lp.group == gi}
        cached = set(cache.groups[gi] or ())
        assert placed == cached, (arch, gi)
    # nothing is both placed and excluded — except the tied embedding
    # table, which is gather-read off-chip AND placed as the transposed
    # LM head (exactly mirroring the cache's "['embed']" entry).
    overlap = flat_placed & {k for k, _ in pl.unmapped}
    assert overlap <= ({"['embed']"} if cfg.tie_embeddings else set())


def test_mapper_shapes_match_prepared_operands():
    """Placed (rows, cols) equal the stored int8 plane geometry of the
    cache entry for flat dense/dense_in leaves (separable mode)."""
    cfg = _tf_cfg(reduced_for_smoke(get_config("deepseek-v3-671b")))
    params, cache = _param_and_cache_shapes(cfg)
    pl = map_params(params, cfg)
    by_key = {lp.key: lp for lp in pl.leaves if lp.group is None}
    for key, ent in cache.flat.items():
        lp = by_key[key]
        c, b, n = ent.q.q.shape  # (C, B, N): C*B = padded K
        assert n == lp.cols
        assert (c - 1) * b < lp.rows <= c * b
        # tile rows == quantization block: the K tiling IS the chunking
        assert lp.tiles_r == c


def test_shape_only_mapping_equals_param_mapping():
    cfg = _tf_cfg(reduced_for_smoke(get_config("hymba-1.5b")))
    params = M.init(cfg, jax.random.PRNGKey(0))
    a = map_params(params, cfg)
    b = map_model(cfg)
    assert [(l.key, l.rows, l.cols, l.copies, l.group) for l in a.leaves] == \
           [(l.key, l.rows, l.cols, l.copies, l.group) for l in b.leaves]
    assert a.unmapped == b.unmapped


# ---------------------------------------------------------------------------
# Op census
# ---------------------------------------------------------------------------


def test_census_forward_counts_scanned_families():
    """Primal-path census coverage is exact through layer scans, the MoE
    expert vmap, and grad-accumulation contexts (the per-family counts
    behind the §6 cost model)."""
    import collections

    cfg = _tf_cfg(reduced_for_smoke(get_config("qwen3-0.6b")))
    params = M.init(cfg, jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.zeros((2, 16), jnp.int32),
        "labels": jnp.zeros((2, 16), jnp.int32),
        "mask": jnp.ones((2, 16), jnp.float32),
    }
    ev = sched.capture_census(lambda p, b: M.loss_fn(p, b, cfg),
                              params, batch)
    mults = collections.Counter(e.mult for e in ev if e.tag == "fwd")
    # 7 denses per layer (wq wk wv wo + swiglu 3) x 2 scanned layers,
    # plus the tied head at mult 1.
    assert mults == {cfg.n_layers: 7, 1: 1}
    assert all(e.tag == "fwd" for e in ev)

    moe_cfg = _tf_cfg(reduced_for_smoke(get_config("deepseek-v3-671b")))
    moe_params = M.init(moe_cfg, jax.random.PRNGKey(0))
    ev = sched.capture_census(lambda p, b: M.loss_fn(p, b, moe_cfg),
                              moe_params, batch)
    mo = moe_cfg.moe
    n_moe_layers = sum(1 for k in moe_cfg.layer_kinds() if k == "moe")
    expert_records = [e for e in ev if e.mult == n_moe_layers * mo.num_experts]
    assert len(expert_records) == 3  # wg, wu, wd through the expert vmap


def test_backward_census_is_structural():
    ev = [tf.OpRecord("fwd", 4, 64, 8, 3)]
    full = tf.backward_census(ev)
    assert tf.OpRecord("bwd_dx", 4, 8, 64, 3) in full
    assert tf.OpRecord("bwd_dw", 64, 4, 8, 3) in full
    assert len(full) == 3


def test_census_scale_nesting():
    with tf.op_census() as ev:
        with tf.census_scale(3):
            with tf.census_scale(4):
                tf._record_op("fwd", 1, 64, 1)
            tf._record_op("fwd", 1, 64, 1)
    assert [e.mult for e in ev] == [12, 3]
    # no active census -> no recording, no error
    tf._record_op("fwd", 1, 64, 1)


# ---------------------------------------------------------------------------
# Census-driven energy: the paper headline
# ---------------------------------------------------------------------------


def _mlp_forward_census():
    dims = (MLP_CFG.in_dim, *MLP_CFG.hidden, MLP_CFG.n_classes)

    def fwd(ws, x):
        h = x
        for w in ws:
            h = tf.linear(h, w, MLP_CFG.tf)
        return h

    ws = [jax.ShapeDtypeStruct((k, n), "float32")
          for k, n in zip(dims[:-1], dims[1:])]
    x = jax.ShapeDtypeStruct((MLP_CFG.batch, MLP_CFG.in_dim), "float32")
    return sched.capture_census(fwd, ws, x)


def test_census_energy_reproduces_paper_tops_per_watt():
    """Acceptance gate: the census-driven training-step projection of the
    paper-scale MLP reproduces the 22.1 TOPS/W headline within 1%."""
    events = tf.backward_census(_mlp_forward_census())
    cost = sched.census_cost(events)
    assert abs(cost.hardware_tops_per_watt - 22.1) / 22.1 < 0.01
    # padding waste (10-class head) drags the useful-MAC figure below it
    assert cost.effective_tops_per_watt < cost.hardware_tops_per_watt


def test_census_energy_matches_table1_model():
    """Forward-only census energy == core.energy.model_energy on the same
    shapes (the two models share the Table I constants by construction)."""
    events = _mlp_forward_census()
    cost = sched.census_cost(events)
    shapes = [(e.m, e.k, e.n) for e in events for _ in range(e.mult)]
    ref = core_energy.model_energy(shapes)
    assert cost.energy_pj_by_tag["fwd"] == pytest.approx(ref.total_pj)
    assert cost.macs == ref.macs


def test_adc_free_backward_reads_cost_less():
    fwd_only = sched.census_cost([tf.OpRecord("fwd", 8, 128, 8, 1)])
    bwd_only = sched.census_cost([tf.OpRecord("bwd_dx", 8, 128, 8, 1)])
    assert bwd_only.chunks == fwd_only.chunks
    delta = fwd_only.energy_pj - bwd_only.energy_pj
    assert delta == pytest.approx(
        fwd_only.chunks * hw_energy.TABLE1_PJ["adc"])


def test_core_energy_is_hw_energy():
    """Satellite: core.energy re-exports hw.energy's objects (no drift)."""
    assert core_energy.TABLE1_PJ is hw_energy.TABLE1_PJ
    assert core_energy.chunk_energy_pj is hw_energy.chunk_energy_pj
    assert core_energy.chunk_energy_pj() == pytest.approx(5.804)
    assert core_energy.tops_per_watt() == pytest.approx(22.1, abs=0.1)


# ---------------------------------------------------------------------------
# Schedule + trainer telemetry
# ---------------------------------------------------------------------------


def test_schedule_step_books_writes_only_for_training():
    pl = map_edge_mlp(MLP_CFG)
    events = tf.backward_census(_mlp_forward_census())
    train = sched.schedule_step(pl, events, train=True)
    serve = sched.schedule_step(pl, events, train=False)
    assert train.cells_written == pl.cells_used == 25856
    assert train.write_energy_pj == pytest.approx(
        pl.cells_used * hw_energy.WRITE_PJ_PER_CELL)
    assert serve.cells_written == 0 and serve.write_energy_pj == 0.0
    assert serve.energy_pj == serve.read.energy_pj
    assert train.latency_ns > serve.latency_ns


def test_hw_monitor_accumulates_in_run_loop():
    from repro.data.pipeline import DataPipeline
    from repro.hw.schedule import HwMonitor
    from repro.train.step import TrainConfig, init_state, make_train_step
    from repro.train.trainer import LoopConfig, run_loop

    cfg = _tf_cfg(reduced_for_smoke(get_config("qwen3-0.6b")))
    cfg = dataclasses.replace(cfg, n_layers=1)
    tcfg = TrainConfig(accum=1)
    state = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, tcfg))
    pipe = DataPipeline(cfg, batch=2, seq=16, kind="lm", prefetch=0)
    monitor = HwMonitor.for_training(state.params, pipe.batch_at(0), cfg)

    seen = []
    loop = LoopConfig(total_steps=3, log_every=1, ckpt_every=1000)
    _, report = run_loop(state, step, pipe.batch_at, loop,
                         on_metrics=lambda s, m: seen.append(m),
                         hw_monitor=monitor)
    assert report.hw is not None
    assert report.hw["steps"] == 3
    assert report.hw["writes_per_tile"] == 3
    assert report.hw["total_cell_writes"] == \
        3 * monitor.step_schedule.cells_written
    assert report.hw["total_energy_j"] > 0
    per_step = [m["hw_cum_cell_writes"] for m in seen]
    assert per_step == sorted(per_step) and per_step[0] > 0
    assert seen[-1]["hw_endurance_frac"] == pytest.approx(
        3 / hw_energy.ENDURANCE_WRITES)
    # census-backed: step energy equals the schedule built from the census
    assert seen[0]["hw_step_energy_uj"] == pytest.approx(
        monitor.step_schedule.energy_pj * 1e-6)


# ---------------------------------------------------------------------------
# Per-tile wear telemetry (DESIGN.md §13)
# ---------------------------------------------------------------------------


def test_tile_spans_partition_the_inventory():
    cfg = _tf_cfg(reduced_for_smoke(get_config("qwen3-0.6b")))
    params = M.init(cfg, jax.random.PRNGKey(0))
    pl = map_params(params, cfg)
    spans = pl.tile_spans()
    assert len(spans) == len(pl.leaves)
    cursor = 0
    for (key, start, stop), lp in zip(spans, pl.leaves):
        assert start == cursor, f"{key} not contiguous"
        assert stop - start == lp.tiles(pl.geometry)
        cursor = stop
    assert cursor == pl.tiles  # every physical tile owned exactly once


def test_tile_wear_conservation_invariant():
    """CI-pinned integer conservation: under uniform training traffic
    ``writes.sum() * cells_written_per_step == hw_cum_cell_writes *
    n_tiles`` EXACTLY, and the scalar ``writes_per_tile`` stays pinned to
    the vector max."""
    from repro.data.pipeline import DataPipeline
    from repro.hw.schedule import HwMonitor

    cfg = _tf_cfg(reduced_for_smoke(get_config("qwen3-0.6b")))
    cfg = dataclasses.replace(cfg, n_layers=1)
    pipe = DataPipeline(cfg, batch=2, seq=16, kind="lm", prefetch=0)
    params = M.init(cfg, jax.random.PRNGKey(0))
    monitor = HwMonitor.for_training(params, pipe.batch_at(0), cfg)
    last = None
    for _ in range(3):
        last = monitor.on_step()
    book = monitor.wear
    assert book.writes.min() == book.writes.max() == 3
    assert monitor.writes_per_tile == book.writes_max == 3
    lhs = book.writes_sum * monitor.step_schedule.cells_written
    rhs = int(last["hw_cum_cell_writes"]) * book.n_tiles
    assert isinstance(book.writes_sum, int) and lhs == rhs
    assert last["hw_tile_writes_max"] == 3.0
    assert last["hw_tile_writes_sum"] == float(3 * book.n_tiles)
    assert last["hw_max_tile_endurance_frac"] == pytest.approx(
        3 / hw_energy.ENDURANCE_WRITES)
    s = monitor.summary()
    assert s["tile_writes_max"] == 3.0
    assert s["tiles_tracked"] == float(book.n_tiles)
    assert s["tile_reads_sum"] > 0.0  # train census reads were booked


def test_resume_projection_equals_stepping():
    """Fast-forward regression: project-then-step == step-then-step, for
    the on_step dict, the wear vector, and the summary."""
    from repro.data.pipeline import DataPipeline
    from repro.hw.schedule import HwMonitor

    cfg = _tf_cfg(reduced_for_smoke(get_config("qwen3-0.6b")))
    cfg = dataclasses.replace(cfg, n_layers=1)
    pipe = DataPipeline(cfg, batch=2, seq=16, kind="lm", prefetch=0)
    params = M.init(cfg, jax.random.PRNGKey(0))

    resumed = HwMonitor.for_training(params, pipe.batch_at(0), cfg)
    resumed.resume_at(5)
    stepped = HwMonitor.for_training(params, pipe.batch_at(0), cfg)
    for _ in range(5):
        stepped.on_step()
    a, b = resumed.on_step(), stepped.on_step()
    assert a == b
    np.testing.assert_array_equal(resumed.wear.writes, stepped.wear.writes)
    sa, sb = resumed.summary(), stepped.summary()
    assert sa.keys() == sb.keys()
    for k in sa:
        if k.startswith("tile_reads"):  # one fused projection vs 5 adds
            assert sa[k] == pytest.approx(sb[k]), k
        else:
            assert sa[k] == sb[k], k
    # resume_at floors, never erases: wear already above the step count
    # survives the projection.
    resumed.wear.writes[0] = 100
    resumed.resume_at(7)
    assert resumed.wear.writes[0] == 100 and resumed.wear.writes[1] == 7


def test_serve_energy_model_books_tile_reads():
    from repro.hw.schedule import ServeEnergyModel, TileWearBook

    cfg = _tf_cfg(reduced_for_smoke(get_config("qwen3-0.6b")))
    params = M.init(cfg, jax.random.PRNGKey(0))
    pl = map_params(params, cfg)
    book = TileWearBook(pl, cfg)
    sem = ServeEnergyModel(slots=2, wear=book)
    sem.on_prefill(1.0, tokens=16)
    sem.on_decode_step(2, tokens=2)
    one_token = book._token_read.sum()
    assert one_token > 0.0
    assert book.reads_sum == pytest.approx(18 * one_token)
    assert sem.prefill_read_tokens == 16 and sem.decode_read_tokens == 2
    tele = sem.telemetry()
    assert tele["tile_read_chunks_sum"] == pytest.approx(book.reads_sum)
    assert tele["tiles_tracked"] == float(pl.tiles)
    assert tele["prefill_read_tokens"] == 16.0
    # no wear book -> telemetry keeps the §11 shape (no tile keys)
    assert "tile_read_chunks_sum" not in ServeEnergyModel(2).telemetry()
