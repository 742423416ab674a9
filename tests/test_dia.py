"""The gather-free diagonal (DIA) path: layout, offer rule, kernel, plan.

Everything runs on the CPU, the kernel in Pallas interpret mode, against
the dense float64 oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import telemetry
from repro.core.graph import OperatorGraph, run_graph
from repro.core.matrices import (SparseMatrix, banded_matrix,
                                 powerlaw_matrix, random_uniform_matrix)
from repro.core.search import DesignSpace, SearchConfig, run_search
from repro.design.space import offers_diagonal
from repro.kernels import ops, ref
from repro.kernels.dia_spmv import X_VMEM_BUDGET, geometry

mk = repro.OpSpec.make
DIA_GRAPH = OperatorGraph((mk("COMPRESS"),),
                          ((mk("DIAG_BLOCK"), mk("DIAG_SUM_RED")),))


def stencil_matrix(shape, points: int, seed: int) -> SparseMatrix:
    """A 2-D 5-point or 3-D 27-point stencil on a grid of ``shape``, with
    fewer neighbours at the boundary; random coefficients."""
    grid = np.indices(shape).reshape(len(shape), -1).T
    if points == 27:
        steps = [(a, b, c) for a in (-1, 0, 1) for b in (-1, 0, 1)
                 for c in (-1, 0, 1)]
    else:
        steps = [(0, 0), (-1, 0), (1, 0), (0, -1), (0, 1)]
    strides = np.cumprod((1,) + tuple(shape[:0:-1]))[::-1]
    rows, cols = [], []
    for st in steps:
        nb = grid + np.asarray(st)
        ok = np.all((nb >= 0) & (nb < np.asarray(shape)), axis=1)
        rows.append((grid[ok] * strides).sum(1))
        cols.append((nb[ok] * strides).sum(1))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    vals = np.random.default_rng(seed).uniform(-1.5, -0.5, rows.size)
    vals[rows == cols] += 27.0
    n = int(np.prod(shape))
    return SparseMatrix(n, n, rows.astype(np.int32), cols.astype(np.int32),
                        vals.astype(np.float32)).canonical()


def diagonal_matrix(n_rows, n_cols, offsets, seed, holes=0.1):
    """Entries on the given diagonals, a share ``holes`` of them missing."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(n_rows), len(offsets))
    c = r + np.tile(np.asarray(offsets), n_rows)
    keep = (c >= 0) & (c < n_cols) & (rng.random(r.size) >= holes)
    return SparseMatrix(n_rows, n_cols, r[keep].astype(np.int32),
                        c[keep].astype(np.int32),
                        rng.standard_normal(int(keep.sum())).astype(
                            np.float32)).canonical()


MATRICES = {
    "banded": lambda: banded_matrix(300, 3, seed=12),
    # 40 rows of 128: two whole 16-row chunks and a short last one
    "banded_5000": lambda: banded_matrix(5000, 2, seed=9),
    "stencil27_12": lambda: stencil_matrix((12, 12, 12), 27, seed=1),
    "stencil5_2d": lambda: stencil_matrix((40, 33), 5, seed=2),
    "wide": lambda: diagonal_matrix(200, 333, (-5, 0, 7, 130), seed=3),
    "tall": lambda: diagonal_matrix(400, 300, (-60, -1, 0, 2), seed=4),
}


def _layout(m):
    meta = run_graph(m, DIA_GRAPH)
    (b,) = meta.blocks
    return b.layout


def _padded_x(x, offsets, n_rows):
    pad_left, x_rows = geometry(n_rows, offsets)
    xp = np.zeros((x_rows * 128,) + x.shape[1:], np.float32)
    n = min(x.shape[0], x_rows * 128 - pad_left)
    xp[pad_left:pad_left + n] = x[:n]
    return xp, pad_left


def _rel_err(y, want):
    return np.abs(np.asarray(y, np.float64) - want).max() / (
        np.abs(want).max() + 1e-30)


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_kernel_and_ref_match_oracle(name):
    m = MATRICES[name]()
    lay = _layout(m)
    x = np.random.default_rng(5).standard_normal(m.n_cols).astype(np.float32)
    xp, pad_left = _padded_x(x, lay.offsets, m.n_rows)
    want = m.spmv_dense_oracle(x)
    geom = dict(offsets=lay.offsets, pad_left=pad_left, n_rows=m.n_rows)
    y_ref = ref.dia_spmv_ref(jnp.asarray(lay.vals), jnp.asarray(xp), **geom)
    y_ker = ops.dia_spmv(jnp.asarray(lay.vals),
                         jnp.asarray(xp.reshape(-1, 128)), interpret=True,
                         **geom)
    assert y_ref.shape == y_ker.shape == (m.n_rows,)
    assert _rel_err(y_ref, want) < 1e-6
    assert _rel_err(y_ker, want) < 1e-6


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("name", ["stencil27_12", "tall"])
def test_plan_with_multi_rhs_x(name, backend):
    """(n, B) x through a compiled dia plan (the reference formulation)."""
    m = MATRICES[name]()
    plan = repro.compile(m, repro.Target(backend=backend), graph=DIA_GRAPH)
    x = np.random.default_rng(6).standard_normal(
        (m.n_cols, 3)).astype(np.float32)
    y = np.asarray(plan(x))
    assert y.shape == (m.n_rows, 3)
    assert _rel_err(y, m.spmm_dense_oracle(x)) < 1e-6
    x1 = x[:, 0]
    assert _rel_err(plan(x1), m.spmv_dense_oracle(x1)) < 1e-6


def test_layout_and_report():
    m = stencil_matrix((12, 12, 12), 27, seed=1)
    lay = _layout(m)
    assert len(lay.offsets) == 27
    assert lay.offsets == tuple(sorted(dz * 144 + dy * 12 + dx
                                       for dz in (-1, 0, 1)
                                       for dy in (-1, 0, 1)
                                       for dx in (-1, 0, 1)))
    assert lay.vals.shape == (27, 14, 128)     # 1,728 rows -> 14 x 128
    plan = repro.compile(m, repro.Target(backend="pallas"), graph=DIA_GRAPH)
    (step,) = plan.spec["steps"]
    assert step["kind"] == "dia" and step["slots"] == 27 * m.n_rows
    assert step["report"] == {"kernel": "dia", "diagonals": 27,
                              "fill": round(m.nnz / (27 * m.n_rows), 4),
                              "combine": "direct"}
    # one array: no column index, no row map
    assert sorted(plan.fmt) == ["b0d_vals"]
    assert plan.stored_bytes == 27 * 14 * 128 * 4


@pytest.mark.parametrize("name", sorted(MATRICES))
def test_offer_rule_true_on_diagonal_matrices(name):
    assert offers_diagonal(MATRICES[name]())


@pytest.mark.parametrize("m", [
    powerlaw_matrix(400, 350, 6.0, 1.0, seed=11),
    random_uniform_matrix(256, 256, 0.02, seed=13),
    random_uniform_matrix(2048, 2048, 4 / 2048, seed=14),
], ids=["powerlaw", "uniform", "uniform_large"])
def test_offer_rule_false_on_scattered_matrices(m):
    assert not offers_diagonal(m)
    cfg = SearchConfig()
    labels = [s.label() for s in DesignSpace(m, cfg).seed_structures()]
    assert not any("DIAG_BLOCK" in lab for lab in labels)


def test_offer_rule_false_on_sparse_fill():
    """Two diagonals with a tenth of their slots used: too much padding."""
    m = diagonal_matrix(1000, 1000, (-400, 0), seed=7, holes=0.0)
    sparse = SparseMatrix(m.n_rows, m.n_cols, m.rows[::10], m.cols[::10],
                          m.vals[::10])
    assert offers_diagonal(m)
    assert not offers_diagonal(sparse)


def test_offer_rule_false_past_the_vmem_budget():
    n = X_VMEM_BUDGET // 4 + 4096       # the padded x passes the budget
    i = np.arange(n, dtype=np.int32)
    big = SparseMatrix(n, n, i, i, np.ones(n, np.float32))
    assert not offers_diagonal(big)
    # only the kernel holds x in VMEM, and it takes one right-hand side:
    # a batch, which runs the XLA reference, does not withhold the seed
    small = banded_matrix(300, 3, seed=12)
    _, x_rows = geometry(300, range(-3, 4))
    batch = X_VMEM_BUDGET // (x_rows * 128 * 4) + 1
    cfg = SearchConfig(batch_size=batch)
    assert len(DesignSpace(small, cfg).seed_structures()) == 5


def test_seed_offered_last_and_search_evaluates_it():
    m = banded_matrix(300, 3, seed=12)
    space = DesignSpace(m, SearchConfig())
    seeds = space.seed_structures()
    assert len(seeds) == 5
    assert seeds[-1].chains == (("DIAG_BLOCK", "DIAG_SUM_RED"),)
    # never woven into the enumerated space
    assert not any("DIAG_BLOCK" in s.label() for s in space.structures())
    cfg = SearchConfig(max_seconds=60, max_structures=0, coarse_samples=1,
                       fine_eval_budget=0, timing_repeats=1,
                       use_cost_model=False)
    res = run_search(m, cfg)
    assert any("DIAG_BLOCK" in r.graph.label() for r in res.records)


def test_sharded_search_never_offers_it(monkeypatch):
    from repro.dist import search as dsearch
    from repro.dist.search import ShardedSearchConfig, dist_search
    seen = []
    real = dsearch.run_search

    def spy(matrix, config, **kw):
        seen.append(DesignSpace(matrix, config).seed_structures())
        return real(matrix, config, **kw)

    monkeypatch.setattr(dsearch, "run_search", spy)
    m = banded_matrix(300, 3, seed=12)
    assert offers_diagonal(m)
    cfg = ShardedSearchConfig(
        search=SearchConfig(max_seconds=20, max_structures=0,
                            coarse_samples=1, fine_eval_budget=0,
                            timing_repeats=1, use_cost_model=False),
        min_nnz_for_search=1)
    res = dist_search(m, jax.make_mesh((1,), ("data",)), cfg)
    assert seen and all(len(s) == 4 for s in seen)
    x = np.random.default_rng(8).standard_normal(m.n_cols).astype(np.float32)
    assert _rel_err(res.program(x), m.spmv_dense_oracle(x)) < 1e-5


def test_save_load_round_trip_bit_identical(tmp_path):
    m = stencil_matrix((12, 12, 12), 27, seed=1)
    plan = repro.compile(m, repro.Target(backend="pallas"), graph=DIA_GRAPH)
    path = tmp_path / "dia.plan.npz"
    plan.save(path)
    loaded = repro.SpmvPlan.load(path)
    assert loaded.spec == plan.spec
    assert loaded.spec["steps"][0]["offsets"] == list(_layout(m).offsets)
    for k, a in plan.fmt.items():
        np.testing.assert_array_equal(np.asarray(loaded.fmt[k]),
                                      np.asarray(a))
    x = np.random.default_rng(9).standard_normal(m.n_cols).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(loaded(x)), np.asarray(plan(x)))


def test_capacity_reports_free_dia_slots():
    from repro.dyn.capacity import capacity_report
    m = diagonal_matrix(200, 333, (-5, 0, 7, 130), seed=3)
    plan = repro.compile(m, repro.Target(backend="jax"), graph=DIA_GRAPH)
    rep = capacity_report(plan)
    # every diagonal lies whole on this wide matrix but for -5's 5 rows
    on_matrix = 4 * 200 - 5
    assert rep["frozen_steps"] == 0
    (step,) = rep["steps"]
    assert step["kind"] == "dia" and step["diagonals"] == 4
    assert step["used"] == m.nnz
    assert step["free_slots"] == rep["dia_headroom"] == on_matrix - m.nnz > 0
    assert rep["ell_slack"] == rep["seg_headroom"] == 0
    assert f"dia_headroom={on_matrix - m.nnz}" in plan.describe()


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_patcher_updates_a_dia_plan_in_place(backend):
    """Revalues, removals and adds on stored diagonals patch the plan in
    place, bit-identical to a fresh compile; an add on another diagonal
    does not fit."""
    from repro.dyn import CapacityError, PatternDelta, PlanPatcher
    m = diagonal_matrix(200, 333, (-5, 0, 7, 130), seed=3)
    target = repro.Target(backend=backend)
    plan = repro.compile(m, target, graph=DIA_GRAPH)
    have = set(zip(m.rows.tolist(), m.cols.tolist()))
    hole = next((r, r + 7) for r in range(200) if (r, r + 7) not in have)
    dense = m.to_dense().astype(np.float32)
    dense *= 2.0                                 # every value changes
    dense[m.rows[0], m.cols[0]] = 0.0            # a removal
    dense[hole] = 0.75                           # an add into a zero slot
    r, c = np.nonzero(dense)
    mutated = SparseMatrix(m.n_rows, m.n_cols, r.astype(np.int32),
                           c.astype(np.int32), dense[r, c]).canonical()
    patcher = PlanPatcher(plan)
    patched = patcher.apply(PatternDelta.from_matrices(m, mutated))
    assert patched.spec == plan.spec
    assert jax.tree_util.tree_structure(patched) == \
        jax.tree_util.tree_structure(plan)
    fresh = repro.compile(mutated, target, graph=DIA_GRAPH)
    for k, a in fresh.fmt.items():
        np.testing.assert_array_equal(np.asarray(patched.fmt[k]),
                                      np.asarray(a))
    x = np.random.default_rng(5).standard_normal(m.n_cols).astype(np.float32)
    assert _rel_err(patched(x), mutated.spmv_dense_oracle(x)) < 1e-5
    off_diagonal = PatternDelta.from_matrices(mutated, SparseMatrix(
        m.n_rows, m.n_cols, np.append(mutated.rows, 3).astype(np.int32),
        np.append(mutated.cols, 4).astype(np.int32),
        np.append(mutated.vals, 1.0).astype(np.float32)).canonical())
    with pytest.raises(CapacityError, match="diagonal 1 not stored"):
        patcher.apply(off_diagonal)
    assert patcher.plan is patched              # rolled back, unchanged


def test_dyn_manager_patches_a_dia_plan():
    """A value-only delta to a dia plan is patched in place; a delta that
    needs a new diagonal goes to a re-search."""
    from repro.dyn import DynamicSparsityManager, PatternDelta
    m = banded_matrix(64, 2, seed=3)
    plan = repro.compile(m, repro.Target(backend="jax"), graph=DIA_GRAPH)
    mgr = DynamicSparsityManager(
        m, plan, research_budget=SearchConfig(
            max_seconds=20, max_structures=0, coarse_samples=1,
            fine_eval_budget=0, timing_repeats=1, use_cost_model=False))
    doubled = SparseMatrix(m.n_rows, m.n_cols, m.rows, m.cols, m.vals * 2)
    out = mgr.apply(PatternDelta.from_matrices(m, doubled))
    assert out["action"] == "update"
    x = np.random.default_rng(6).standard_normal(m.n_cols).astype(np.float32)
    assert _rel_err(mgr.plan(x), doubled.spmv_dense_oracle(x)) < 1e-5
    wider = SparseMatrix(
        m.n_rows, m.n_cols, np.append(doubled.rows, 0).astype(np.int32),
        np.append(doubled.cols, 40).astype(np.int32),
        np.append(doubled.vals, 1.0).astype(np.float32)).canonical()
    out = mgr.apply(PatternDelta.from_matrices(doubled, wider))
    assert out["action"] == "research"
    assert mgr.quiesce(60)
    assert mgr.researches_landed == 1


def test_engagement_counters():
    from repro.api import _dense_kernel
    m = stencil_matrix((12, 12, 12), 27, seed=1)
    x = np.ones(m.n_cols, np.float32)
    dia = repro.compile(m, repro.Target(backend="jax"), graph=DIA_GRAPH)
    ell = repro.compile(m, repro.Target(backend="jax"), graph=OperatorGraph(
        (mk("COMPRESS"),), ((mk("TILE_ROW_BLOCK", rows=8),
                             mk("LANE_ROW_BLOCK"),
                             mk("LANE_TOTAL_RED", combine="scatter")),)))
    _dense_kernel.cache_clear()
    telemetry.reset()
    dia(x)
    c = telemetry.snapshot()["counters"]
    assert c["repro.plan.nnz_gather_free"] == 27 * m.n_rows
    assert c["repro.plan.nnz_gathered"] == 0
    telemetry.reset()
    ell(x)
    c = telemetry.snapshot()["counters"]
    assert c["repro.plan.nnz_gather_free"] == 0
    assert c["repro.plan.nnz_gathered"] == sum(
        int(np.prod(a.shape)) for k, a in ell.fmt.items()
        if k.endswith("_vals"))
