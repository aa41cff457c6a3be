"""2-D partitioner layouts (docs/PARTITIONING.md "2-D layouts"): plan
decisions over data × model meshes, blocked-carry streamed-fit parity,
per-axis collective accounting, rung pricing on per-device state,
cross-mesh durable resume, and model-axis shard-loss salvage.

The invariant throughout: IDENTICAL pipeline code on 1×1, 1×8, 2×4 and
4×2 virtual-device meshes, parity ≤ 1e-5, 0 steady-state compiles."""

import numpy as np
import pytest

import jax

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.ops.learning.least_squares import LeastSquaresEstimator
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.parallel.partitioner import (
    ALL_REASON_KEYS,
    R_BELOW_WIDTH_FLOOR,
    R_MODEL_INDIVISIBLE,
    Partitioner,
    demote_model_axis,
    last_partition_report,
    partition_disabled,
)
from keystone_tpu.reliability import enable_checkpointing, faultinject
from keystone_tpu.reliability.faultinject import FaultSpec
from keystone_tpu.reliability.recovery import get_recovery_log
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.pipeline import BatchTransformer
from keystone_tpu.workflow.streaming import last_stream_report

N, D, K, CHUNK = 512, 64, 3, 64  # D wide enough for 8 model shards
rng = np.random.default_rng(11)
X = rng.normal(size=(N, D)).astype(np.float32)
W = rng.normal(size=(D, K)).astype(np.float32)
Y = (X @ W + 0.01 * rng.normal(size=(N, K))).astype(np.float32)
PROBE = rng.normal(size=(32, D)).astype(np.float32)


class Scale(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    def apply_arrays(self, a):
        return a * self.c


def build(x=X, y=Y, est=None):
    est = est or LinearMapEstimator(reg=1e-3)
    return Scale(2.0).to_pipeline().then_label_estimator(
        est, ArrayDataset(x), ArrayDataset(y)
    )


def preds(fitted):
    return np.asarray(fitted.apply_batch(ArrayDataset(PROBE)).data)


def rel_err(a, b):
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture()
def grid2d(monkeypatch):
    """2×4 layout: 4 model shards on the 8-virtual-device mesh, width
    floor lowered so D=64 clears it (64 ≥ 4 × 8)."""
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))
    monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", "4")
    monkeypatch.setenv("KEYSTONE_PARTITION_MIN_WIDTH", "8")


@pytest.fixture()
def reference(monkeypatch):
    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", str(CHUNK))
    PipelineEnv.reset()
    with partition_disabled():
        out = preds(build().fit())
    PipelineEnv.reset()
    return out


# ------------------------------------------------------------- decisions


def test_2d_stream_decision_shape_and_spec(grid2d):
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=D, model_ok=True
    )
    assert d.eligible and d.reason == "sharded"
    assert (d.shards, d.model_shards) == (2, 4)
    assert d.total_shards == 8
    assert d.mesh_shape == (2, 4)
    assert d.carry_axes == ("data", "model")
    assert "data" in d.spec and "model" in d.spec
    assert d.to_json()["model_shards"] == 4


def test_width_floor_demotes_to_row_only(grid2d, monkeypatch):
    monkeypatch.setenv("KEYSTONE_PARTITION_MIN_WIDTH", "512")
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=D, model_ok=True
    )
    assert d.eligible and d.model_shards == 1
    assert d.shards == len(jax.devices())
    assert d.model_fallback == R_BELOW_WIDTH_FLOOR
    assert "model" not in d.spec


def test_indivisible_width_demotes(grid2d):
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=D - 2, model_ok=True
    )
    assert d.eligible and d.model_shards == 1
    assert d.model_fallback == R_MODEL_INDIVISIBLE


def test_model_shards_must_divide_device_count(grid2d, monkeypatch):
    monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", "3")
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=66, model_ok=True
    )
    assert d.eligible and d.model_shards == 1
    assert d.model_fallback == R_MODEL_INDIVISIBLE


def test_estimator_without_protocol_stays_row_only(grid2d):
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=D, model_ok=False
    )
    assert d.eligible and d.model_shards == 1 and not d.model_fallback


def test_demote_model_axis_keeps_row_sharding(grid2d):
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=D, model_ok=True
    )
    dem = demote_model_axis(d, R_MODEL_INDIVISIBLE, "test")
    assert dem.eligible and dem.model_shards == 1 and dem.shards == 2
    assert dem.model_fallback == R_MODEL_INDIVISIBLE
    assert "model" not in dem.spec


def test_demote_on_1x8_turns_ineligible(grid2d, monkeypatch):
    monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", "8")
    d = Partitioner().decide_stream(
        "t", CHUNK, rows=N, record=False, width=D, model_ok=True
    )
    assert d.eligible and (d.shards, d.model_shards) == (1, 8)
    dem = demote_model_axis(d, R_BELOW_WIDTH_FLOOR)
    assert not dem.eligible and dem.reason == R_BELOW_WIDTH_FLOOR


def test_every_reason_key_reaches_the_docs_matrix():
    assert R_MODEL_INDIVISIBLE in ALL_REASON_KEYS
    assert R_BELOW_WIDTH_FLOOR in ALL_REASON_KEYS
    assert len(ALL_REASON_KEYS) == len(set(ALL_REASON_KEYS))


# ------------------------------------------- the carry's additive contract


@pytest.mark.parametrize("np_mod", ["numpy", "jax.numpy"])
@pytest.mark.parametrize(
    "row_shards,model_shards,drop_row",
    [(4, 1, None), (4, 1, 3), (4, 1, 0), (2, 4, None), (2, 4, 1), (4, 2, 0), (1, 8, 0)],
)
def test_merge_blocks_is_the_sum_of_the_kept_row_groups(
    np_mod, row_shards, model_shards, drop_row
):
    """`_merge_blocks` against the merge done by hand, block by block: the
    kept row groups summed, feature leaves reassembled along their axis,
    the feature-free leaf summed over every block; a 1-D layout keeps all
    leaves whole. Dropping the only row group leaves zeros."""
    import importlib

    from keystone_tpu.workflow.streaming import _merge_blocks

    blocked = model_shards > 1
    layout = (1, 0, None) if blocked else (None, None, None)
    whole = [(D, D), (D, K), (K,)]
    r = np.random.default_rng(row_shards * 10 + model_shards)

    def block_shape(shape, ax):
        if ax is None:
            return shape
        return shape[:ax] + (shape[ax] // model_shards,) + shape[ax + 1:]

    # blocks[leaf][i][j]: row group i, model column j (flat index i·p_m + j)
    blocks = [
        [
            [r.normal(size=block_shape(shape, ax)).astype(np.float32)
             for _ in range(model_shards)]
            for _ in range(row_shards)
        ]
        for shape, ax in zip(whole, layout)
    ]
    stacked = tuple(
        np.stack([b for group in leaf for b in group]) for leaf in blocks
    )
    kept = [i for i in range(row_shards) if i != drop_row]
    by_hand = []
    for leaf, shape, ax in zip(blocks, whole, layout):
        columns = [
            sum((leaf[i][j] for i in kept), np.zeros(block_shape(shape, ax), np.float32))
            for j in range(model_shards)
        ]
        by_hand.append(
            sum(columns) if ax is None else np.concatenate(columns, axis=ax)
        )
    merged = _merge_blocks(
        stacked, row_shards, model_shards, layout,
        importlib.import_module(np_mod), drop_row=drop_row,
    )
    for got, want, shape in zip(merged, by_hand, whole):
        assert got.shape == shape
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    if not kept:
        assert all(not np.asarray(got).any() for got in merged)


@pytest.mark.parametrize("shards,model_shards", [(8, 1), (2, 1), (2, 4), (4, 2), (1, 8)])
def test_reduction_collective_bytes_is_a_function_of_the_plan(shards, model_shards):
    """The numbers the fold stores on its report, from the plan alone:
    `bytes × (shards − 1)` on the data axis of a row-only layout (what
    tests/workflow/test_partition.py expects of the report), the per-axis
    split of the 2-D cases below."""
    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.partitioner import reduction_collective_bytes

    carry = linalg.gram_stream_init(D, K)
    nbytes = [a.nbytes for a in carry]
    b_f, b_r = 4 * (D * D + D * K + D), 4 * K
    assert sum(nbytes) == b_f + b_r
    if model_shards == 1:
        layout = (None,) * len(carry)  # a row-only fold keeps every leaf whole
        want = ((b_f + b_r) * (shards - 1), 0)
    else:
        layout = linalg.gram_stream_step.model_layout
        want = (
            (b_f + model_shards * b_r) * (shards - 1),
            (b_f // model_shards + b_r) * (model_shards - 1),
        )
    assert reduction_collective_bytes(nbytes, layout, shards, model_shards) == want


# ----------------------------------------------------- streamed execution


@pytest.mark.parametrize("model_shards,mesh_shape", [(4, (2, 4)), (2, (4, 2)), (8, (1, 8))])
def test_2d_fit_stream_parity_and_axis_accounting(
    grid2d, reference, monkeypatch, model_shards, mesh_shape
):
    monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", str(model_shards))
    PipelineEnv.reset()
    fitted = build().fit()
    rep = last_stream_report()
    assert rep.mesh_shape == mesh_shape
    assert (rep.shards, rep.model_shards) == mesh_shape
    assert rep.compiles_steady_state == 0
    # per-axis collective payload is a pure function of the plan
    b_f = 4 * (D * D + D * K + D)
    b_r = 4 * K
    p_d, p_m = mesh_shape
    assert rep.collective_bytes_data == (b_f + p_m * b_r) * (p_d - 1)
    assert rep.collective_bytes_model == (b_f // p_m + b_r) * (p_m - 1)
    assert rep.collective_bytes == (
        rep.collective_bytes_data + rep.collective_bytes_model
    )
    # per-device state: one feature block + the replicated remainder
    assert rep.state_bytes_per_device == b_f // p_m + b_r
    assert rel_err(preds(fitted), reference) <= 1e-5


def test_per_device_state_shrinks_with_model_shards(grid2d, monkeypatch):
    state = {}
    for p_m in (1, 2, 4):
        monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", str(p_m))
        PipelineEnv.reset()
        build().fit()
        state[p_m] = last_stream_report().state_bytes_per_device
    assert state[1] > state[2] > state[4]
    # feature state dominates at D=64: each doubling roughly halves it
    assert state[1] > 1.9 * state[2] and state[2] > 1.9 * state[4]


def test_sketched_rung_2d_parity(grid2d, monkeypatch, reference):
    # Force the sketch rung under the 2-D layout: the 5-leaf carry's
    # SA/Σx leaves block over the model axis.
    monkeypatch.setenv("KEYSTONE_SKETCH_MIN_WIDTH", "16")
    monkeypatch.setenv("KEYSTONE_SKETCH_SIZE", "512")
    PipelineEnv.reset()
    fitted = build(est=LeastSquaresEstimator(reg=1e-3)).fit()
    rep = last_stream_report()
    assert (rep.shards, rep.model_shards) == (2, 4)
    assert rep.compiles_steady_state == 0
    # sketched solve at s=512 ≥ 8·D is near-exact on this problem
    assert rel_err(preds(fitted), reference) <= 5e-2


def test_rung_pricing_scales_sketch_floor_per_device(monkeypatch):
    monkeypatch.setenv("KEYSTONE_SKETCH_MIN_WIDTH", "32")
    est = LeastSquaresEstimator(reg=1e-3)
    from keystone_tpu.sketch.solvers import SketchedLeastSquaresEstimator

    assert isinstance(est._stream_solver(64), SketchedLeastSquaresEstimator)
    # feature-sharded 4 ways, the same width stays on the exact rung
    assert not isinstance(
        est._stream_solver(64, model_shards=4), SketchedLeastSquaresEstimator
    )
    assert isinstance(
        est._stream_solver(128, model_shards=4), SketchedLeastSquaresEstimator
    )


def test_plan_report_carries_model_fallback(grid2d, monkeypatch):
    # An indivisible width demotes at plan time; the decision stays
    # eligible row-sharded and the report explains the demotion.
    x = np.ascontiguousarray(X[:, : D - 2])
    PipelineEnv.reset()
    fitted = build(x=x).fit()
    rep = last_stream_report()
    assert rep.shards == len(jax.devices()) and rep.model_shards == 1
    decisions = [d for d in last_partition_report() if d.eligible]
    assert decisions and decisions[0].model_fallback == R_MODEL_INDIVISIBLE
    narrow = ArrayDataset(np.ascontiguousarray(PROBE[:, : D - 2]))
    assert np.isfinite(np.asarray(fitted.apply_batch(narrow).data)).all()


# --------------------------------------------------------------- verifier


def test_kv304_accounts_model_axis_blocking(grid2d):
    from keystone_tpu.workflow.verify import verify_graph

    pipe = build()
    report = verify_graph(pipe.graph, device_memory_bytes=64, context="test")
    errors = report.by_code("KV304")
    assert errors, report.render()
    assert errors[0].details.get("model_shards") == 4
    # the 2-D decision rides the report for check --pipeline --json
    assert any(p.get("model_shards") == 4 for p in report.partition)


# ------------------------------------------------------ durable cross-mesh


def _crash_at(store_dir, call, env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    PipelineEnv.reset()
    enable_checkpointing(str(store_dir))
    with pytest.raises(ConnectionError):
        with faultinject.injected(
            FaultSpec(match="streaming.chunk", kind="transient", calls=(call,))
        ):
            build().fit()


@pytest.mark.parametrize(
    "first,second", [("8", "4"), ("4", "8")], ids=["1x8-to-2x4", "2x4-to-1x8"]
)
def test_cross_mesh_durable_resume_parity(
    tmp_path, reference, monkeypatch, first, second
):
    """A fit checkpointed under one 2-D layout resumes under another:
    snapshots commit MERGED (mesh-independent), the layout is cursor
    metadata only."""
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_CHUNKS", "2")
    monkeypatch.setenv("KEYSTONE_PARTITION_MIN_WIDTH", "8")
    _crash_at(
        tmp_path, 5, {"KEYSTONE_PARTITION_MODEL_SHARDS": first}, monkeypatch
    )
    monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", second)
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    fitted = build().fit()
    rep = last_stream_report()
    assert rep.resumed_from_chunk == 4
    assert rep.model_shards == int(second)
    assert rel_err(preds(fitted), reference) <= 1e-6


def test_2d_checkpoint_resumes_single_device(
    tmp_path, reference, monkeypatch
):
    monkeypatch.setenv("KEYSTONE_STREAM_CKPT_CHUNKS", "2")
    monkeypatch.setenv("KEYSTONE_PARTITION_MIN_WIDTH", "8")
    _crash_at(
        tmp_path, 5, {"KEYSTONE_PARTITION_MODEL_SHARDS": "4"}, monkeypatch
    )
    PipelineEnv.reset()
    enable_checkpointing(str(tmp_path))
    with partition_disabled():
        fitted = build().fit()
    rep = last_stream_report()
    assert rep.resumed_from_chunk == 4 and rep.shards == 1
    assert rel_err(preds(fitted), reference) <= 1e-6


# -------------------------------------------------------- shard loss (2-D)


def test_model_axis_shard_loss_salvages_surviving_row_group(
    grid2d, reference
):
    """Losing flat shard 7 on the 2×4 mesh = (data row 1, model col 3).
    A feature column cannot be salvaged alone: the whole data row-group
    drops, the survivors' blocks reassemble, only row group 1's windows
    re-ingest."""
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(3,))
    ):
        fitted = build().fit()
    rep = last_stream_report()
    assert rep.shard_losses == 1
    assert rep.shards == 7 and rep.model_shards == 1  # row-only re-plan
    assert rep.reingested_chunks > 0
    assert rel_err(preds(fitted), reference) <= 1e-5
    kinds = {e.kind for e in get_recovery_log().events()}
    assert {"shard_loss", "shard_resume"} <= kinds


def test_seed_bearing_block_loss_readds_seed_2d(
    grid2d, reference, monkeypatch
):
    # Flat shard 0 = (data row 0, model col 0): the dropped row group
    # includes the seed block, which must re-add host-side.
    monkeypatch.setenv("KEYSTONE_SHARD_LOSS_INDEX", "0")
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(4,))
    ):
        fitted = build().fit()
    assert last_stream_report().shard_losses == 1
    assert rel_err(preds(fitted), reference) <= 1e-5


def test_1x8_loss_reingests_everything(grid2d, reference, monkeypatch):
    # On 1×8 every device is in the single data row-group: a loss keeps
    # nothing, the fold restarts from the seed — correct, just slow.
    monkeypatch.setenv("KEYSTONE_PARTITION_MODEL_SHARDS", "8")
    PipelineEnv.reset()
    with faultinject.injected(
        FaultSpec(match="parallel.shard_loss", kind="transient", calls=(3,))
    ):
        fitted = build().fit()
    rep = last_stream_report()
    assert rep.shard_losses == 1
    assert rel_err(preds(fitted), reference) <= 1e-5
