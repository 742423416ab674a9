"""Fused-combine megatile kernels + mixed-precision storage (perf-opt PR).

Covers the tentpole: (1) in-kernel combine (ELL revisited-output-block
fused kernels, seg carry-last-segment scheme) against the scatter path
and the dense oracle; (2) megatile ``tiles_per_step``; (3) bf16/int16
storage with fp32 accumulation, including the ``SpmvPlan`` save/load
round trip and the dist family stacks; (4) the SET_RESOURCES search
knobs (DesignSpace weaving, branched-join propagation, cost features).

Satellites: the GRID_ACC direct-variant precondition (non-affine rowmap
must fall back, never write wrong rows) and the 1-RHS onehot kernel's
explicit fp32 cast for non-fp32 vals.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.graph import OperatorGraph, run_graph
from repro.core.kernel_builder import build_program, plan_format
from repro.core.matrices import (banded_matrix, powerlaw_matrix,
                                 random_uniform_matrix)
from repro.core.operators import OpSpec
from repro.core.search import SearchConfig

from conftest import assert_spmv_matches

ELL = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
    OpSpec.make("LANE_ROW_BLOCK"), OpSpec.make("LANE_TOTAL_RED"))
SEG_SCAN = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
    OpSpec.make("SEG_SCAN_RED"))
SEG_ONEHOT = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
    OpSpec.make("ONEHOT_MXU_RED"))
SEG_ATOM = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
    OpSpec.make("GMEM_ATOM_RED"))


def _mats():
    return {"banded": banded_matrix(120, 3, seed=1),
            "uniform": random_uniform_matrix(120, 120, 0.05, seed=2),
            "powerlaw": powerlaw_matrix(120, 120, 5.0, 1.2, seed=3)}


# ------------------------- in-kernel combine parity -------------------------

@pytest.mark.parametrize("graph", [ELL, SEG_SCAN, SEG_ONEHOT, SEG_ATOM],
                         ids=["ell", "seg_scan", "onehot", "gmem_atom"])
@pytest.mark.parametrize("tiles", [1, 3])
def test_fused_combine_matches_oracle(graph, tiles):
    for name, m in _mats().items():
        meta = run_graph(m, graph)
        fused = build_program(meta, backend="pallas",
                              tiles_per_step=tiles)
        assert any(s.get("fused") for s in fused.spec["steps"]), name
        assert fused.spec["tiles_per_step"] == tiles
        assert_spmv_matches(m, fused)
        # bit-for-bit question is dtype: fused outputs are fp32
        x = np.random.default_rng(1).standard_normal(
            m.n_cols).astype(np.float32)
        assert np.asarray(fused(x)).dtype == np.float32


def test_fused_spmm_matches_per_column():
    m = random_uniform_matrix(100, 90, 0.06, seed=5)
    for graph in (ELL, SEG_SCAN, SEG_ONEHOT):
        meta = run_graph(m, graph)
        prog = build_program(meta, backend="pallas",
                             tiles_per_step=2)
        X = np.random.default_rng(0).standard_normal(
            (m.n_cols, 3)).astype(np.float32)
        fused = np.asarray(prog(X))
        percol = np.stack([np.asarray(prog(X[:, b])) for b in range(3)],
                          axis=1)
        np.testing.assert_allclose(fused, percol, atol=1e-5, rtol=1e-5)


def test_fused_vs_scatter_same_numbers():
    """fuse_combine=False (the historical path) and the fused path agree."""
    m = powerlaw_matrix(150, 140, 5.0, 1.2, seed=7)
    meta = run_graph(m, SEG_SCAN)
    x = np.random.default_rng(0).standard_normal(m.n_cols).astype(np.float32)
    base = build_program(meta, backend="pallas",
                         fuse_combine=False)
    fused = build_program(meta, backend="pallas",
                          tiles_per_step=4)
    assert not any(s.get("fused") for s in base.spec["steps"])
    np.testing.assert_allclose(np.asarray(base(x)), np.asarray(fused(x)),
                               atol=1e-5, rtol=1e-5)


def test_seg_fused_rejected_on_reordered_rows():
    """SORT destroys per-tile row contiguity: the seg step must NOT be
    marked fused (the carry scheme would write wrong rows) and the
    scatter path must still produce correct output."""
    m = powerlaw_matrix(130, 120, 5.0, 1.2, seed=9)
    graph = OperatorGraph.chain(
        OpSpec.make("COMPRESS"), OpSpec.make("SORT"),
        OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
        OpSpec.make("SEG_SCAN_RED"))
    meta = run_graph(m, graph)
    prog = build_program(meta, backend="pallas",
                         tiles_per_step=2)
    assert not any(s.get("fused") for s in prog.spec["steps"])
    assert all(f"{s['key']}_r0" not in prog.fmt
               for s in prog.spec["steps"])
    assert_spmv_matches(m, prog)


# --------------- satellite: GRID_ACC direct-variant precondition -------------

def test_grid_acc_rejected_on_nonaffine_rowmap():
    """A grid_acc combine on a non-affine rowmap (SORT permuted the rows)
    must be rejected by the kernel builder — demoted to the scatter
    combine — rather than silently writing wrong rows."""
    m = powerlaw_matrix(140, 130, 5.0, 1.2, seed=4)
    graph = OperatorGraph.chain(
        OpSpec.make("COMPRESS"), OpSpec.make("SORT"),
        OpSpec.make("TILE_ROW_BLOCK", rows=16),
        OpSpec.make("LANE_ROW_BLOCK"),
        OpSpec.make("LANE_TOTAL_RED", combine="grid_acc"))
    meta = run_graph(m, graph)
    fmt, spec = plan_format(meta)
    demoted = [s for s in spec["steps"]
               if s["combine"]["mode"] == "rowmap"]
    assert demoted, "expected at least one bucket demoted to scatter"
    for s in demoted:
        assert "grid_acc-fallback" in s["report"]["combine"]
    for backend in ("jax", "pallas"):
        prog = build_program(meta, backend=backend)
        assert_spmv_matches(m, prog)


def test_grid_acc_affine_keeps_direct():
    """Control: an un-reordered matrix has the affine rowmap and keeps the
    direct/fused combine."""
    m = banded_matrix(96, 2, seed=3)
    graph = OperatorGraph.chain(
        OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
        OpSpec.make("LANE_ROW_BLOCK"),
        OpSpec.make("LANE_TOTAL_RED", combine="grid_acc"))
    meta = run_graph(m, graph)
    _, spec = plan_format(meta)
    assert all(s["combine"]["mode"] == "affine" for s in spec["steps"])


# ------------- satellite: onehot kernel explicit cast (non-fp32) -------------

def test_onehot_kernel_nonfp32_vals_cast():
    """bf16 vals through the 1-RHS onehot kernel: fp32 output, matching
    the fp32 reference within bf16 storage tolerance (regression for the
    implicit-cast store into out_ref)."""
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    t, s, l, m_rows, n_cols = 3, 4, 8, 8, 64
    c = s * l
    local = np.sort(rng.integers(0, m_rows, (t, c)), axis=1)
    local = (local - local[:, :1]).reshape(t, s, l).astype(np.int32)
    vals32 = rng.standard_normal((t, s, l)).astype(np.float32)
    cols = rng.integers(0, n_cols, (t, s, l)).astype(np.int32)
    x = rng.standard_normal(n_cols).astype(np.float32)
    vals16 = jnp.asarray(vals32, jnp.bfloat16)
    seg_end = np.zeros((t, m_rows), np.int32)   # unused by onehot
    got = np.asarray(ops.seg_spmv(vals16, jnp.asarray(cols),
                                  jnp.asarray(local), jnp.asarray(seg_end),
                                  jnp.asarray(x), m_rows,
                                  mode="onehot_mxu", interpret=True))
    assert got.dtype == np.float32
    want = np.asarray(ref.seg_spmv_ref(
        jnp.asarray(vals16), jnp.asarray(cols), jnp.asarray(local),
        jnp.asarray(seg_end), jnp.asarray(x), m_rows, mode="onehot_mxu"))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    # bf16 storage rounding is the only difference vs the fp32 twin
    exact = np.asarray(ref.seg_spmv_ref(
        jnp.asarray(vals32), jnp.asarray(cols), jnp.asarray(local),
        jnp.asarray(seg_end), jnp.asarray(x), m_rows, mode="onehot_mxu"))
    scale = np.abs(exact).max() + 1e-30
    assert np.abs(got - exact).max() / scale < 2e-2


# --------------------------- mixed-precision plans ---------------------------

def test_bf16_plan_roundtrip_bit_identical(tmp_path):
    import repro
    m = random_uniform_matrix(128, 120, 0.05, seed=6)
    plan = repro.compile(m, repro.Target(backend="pallas",
                                         dtype="bfloat16"), graph=ELL)
    # storage narrowed: bf16 vals, int16 cols (n_cols < 32768)
    dts = {str(np.asarray(v).dtype) for v in plan.fmt.values()}
    assert "bfloat16" in dts and "int16" in dts
    assert plan.spec["storage_dtype"] == "bfloat16"
    # parity vs the fp64 oracle within bf16 tolerance
    x = np.random.default_rng(0).standard_normal(m.n_cols).astype(np.float32)
    oracle = m.spmv_dense_oracle(x)
    scale = np.abs(oracle).max() + 1e-30
    y = np.asarray(plan(x))
    assert y.dtype == np.float32
    assert np.abs(y - oracle).max() / scale < 2e-2
    # save -> load: bit-identical arrays (dtype included) and outputs
    path = tmp_path / "bf16.plan.npz"
    plan.save(path)
    loaded = repro.SpmvPlan.load(path)
    assert sorted(loaded.fmt) == sorted(plan.fmt)
    for k in plan.fmt:
        a, b = np.asarray(plan.fmt[k]), np.asarray(loaded.fmt[k])
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8)), k
    assert loaded.spec_json == plan.spec_json
    np.testing.assert_array_equal(y, np.asarray(loaded(x)))


def test_bf16_halves_stored_bytes():
    m = banded_matrix(128, 3, seed=8)
    meta = run_graph(m, ELL)
    f32 = build_program(meta, backend="pallas")
    b16 = build_program(meta, backend="pallas",
                        storage_dtype="bfloat16")
    assert b16.stored_bytes < 0.65 * f32.stored_bytes


def test_dist_stacks_carry_narrowed_dtypes():
    import jax
    from repro.dist.spmv import shard_map_spmv
    m = random_uniform_matrix(96, 96, 0.06, seed=10)
    mesh = jax.make_mesh((1,), ("data",))
    f32 = shard_map_spmv(m, mesh)
    b16 = shard_map_spmv(m, mesh, storage_dtype="bfloat16")
    vals_dts = {str(np.asarray(v).dtype)
                for k, v in b16.stacks.items() if k.endswith("_vals")}
    assert vals_dts == {"bfloat16"}
    assert b16.per_device_format_bytes < f32.per_device_format_bytes
    x = np.random.default_rng(0).standard_normal(m.n_cols).astype(np.float32)
    oracle = m.spmv_dense_oracle(x)
    scale = np.abs(oracle).max() + 1e-30
    assert np.abs(np.asarray(b16(x)) - oracle).max() / scale < 2e-2


# ----------------------- search knobs (SET_RESOURCES) ------------------------

def test_set_resources_knobs_reach_plan_format():
    m = banded_matrix(96, 2, seed=1)
    graph = OperatorGraph.chain(
        OpSpec.make("COMPRESS"),
        OpSpec.make("SET_RESOURCES", tiles_per_step=4, dtype="bfloat16"),
        OpSpec.make("TILE_ROW_BLOCK", rows=16),
        OpSpec.make("LANE_ROW_BLOCK"), OpSpec.make("LANE_TOTAL_RED"))
    meta = run_graph(m, graph)
    assert meta.tiles_per_step == 4 and meta.storage_dtype == "bfloat16"
    _, spec = plan_format(meta)
    assert spec["tiles_per_step"] == 4
    assert spec["storage_dtype"] == "bfloat16"
    prog = build_program(meta, backend="pallas")
    assert_spmv_matches(m, prog, rtol=2e-2)


def test_set_resources_survives_branched_join():
    m = powerlaw_matrix(150, 140, 5.0, 1.2, seed=2)
    knob = OpSpec.make("SET_RESOURCES", tiles_per_step=2, dtype="bfloat16")
    ell = (knob, OpSpec.make("TILE_ROW_BLOCK", rows=16),
           OpSpec.make("LANE_ROW_BLOCK"), OpSpec.make("LANE_TOTAL_RED"))
    seg = (knob, OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
           OpSpec.make("SEG_SCAN_RED"))
    graph = OperatorGraph(
        converting=(OpSpec.make("COMPRESS"), OpSpec.make("BIN", n_bins=2)),
        branch_chains=(ell, seg), shared=False)
    meta = run_graph(m, graph)
    assert meta.tiles_per_step == 2 and meta.storage_dtype == "bfloat16"


def test_design_space_weaves_knob_choices(small_uniform):
    from repro.design.space import DesignSpace
    base_cfg = SearchConfig(seed=0)
    cfg = dataclasses.replace(base_cfg,
                              tiles_per_step_choices=(1, 4),
                              dtype_choices=("float32", "bfloat16"))
    space0 = DesignSpace(small_uniform, base_cfg)
    space1 = DesignSpace(small_uniform, cfg)
    s = space0.seed_structures()[0]
    g0 = space0.bind(s, "coarse")
    g1 = space1.bind(s, "coarse")
    # parity with default choices; 4x knob variants otherwise
    assert all("SET_RESOURCES" not in g.op_names() for g in g0)
    assert len(g1) == 4 * len(g0)
    assert all(g.op_names().count("SET_RESOURCES") == 1 for g in g1)
    dtypes = {g.all_ops()[1].param("dtype") for g in g1}
    assert dtypes == {"float32", "bfloat16"}
    # every woven candidate is a valid, runnable design
    for g in g1[:4]:
        g.validate()
        assert space1.features(g) is not None


def test_target_widen_knob_choices():
    from repro.api import _as_search_config
    import repro
    cfg = _as_search_config(None, repro.Target(backend="pallas",
                                               dtype="bfloat16"))
    assert cfg.tiles_per_step_choices == (1, 4, 8)
    assert cfg.dtype_choices == ("float32", "bfloat16")
    # explicit choices in the budget are respected
    mine = SearchConfig(tiles_per_step_choices=(2,))
    cfg2 = _as_search_config(mine, repro.Target(backend="pallas"))
    assert cfg2.tiles_per_step_choices == (2,)
    # explicitly pinning the single-default choice DISABLES the widening
    pinned = SearchConfig(tiles_per_step_choices=(1,),
                          dtype_choices=("float32",))
    cfg_p = _as_search_config(pinned, repro.Target(backend="pallas",
                                                   dtype="bfloat16"))
    assert cfg_p.tiles_per_step_choices == (1,)
    assert cfg_p.dtype_choices == ("float32",)
    from repro.design.space import DesignSpace
    m = banded_matrix(64, 2, seed=0)
    space = DesignSpace(m, cfg_p)
    assert space._knob_specs() == ()      # knobs pinned off -> no weaving
    # jax/fp32 targets keep the parity defaults (None = auto, unwoven)
    cfg3 = _as_search_config(None, repro.Target())
    assert cfg3.tiles_per_step_choices is None
    assert cfg3.dtype_choices is None


def test_search_selects_dtype_per_matrix(small_uniform):
    """End to end: with both precisions searchable, bf16 candidates are
    timed (not rejected by the oracle gate) and the winner round-trips."""
    import repro
    cfg = SearchConfig(max_seconds=6, max_structures=1, coarse_samples=4,
                       fine_eval_budget=0, timing_repeats=1, seed=0,
                       dtype_choices=("float32", "bfloat16"))
    plan = repro.compile(small_uniform, repro.Target(backend="pallas"),
                         budget=cfg)
    res = plan.search_result
    timed_dtypes = {g.param("dtype")
                    for r in res.records for g in r.graph.all_ops()
                    if g.name == "SET_RESOURCES"}
    assert timed_dtypes == {"float32", "bfloat16"}
    assert plan.spec["storage_dtype"] in ("float32", "bfloat16")
    assert_spmv_matches(small_uniform, plan, rtol=2e-2)


# ------------------------------ cost features --------------------------------

def test_cost_features_fused_and_storage():
    from repro.core.cost_model import FEATURE_NAMES, program_features
    i_saved = FEATURE_NAMES.index("combine_bytes_saved")
    i_ratio = FEATURE_NAMES.index("storage_bytes_ratio")
    m = banded_matrix(120, 3, seed=1)
    meta = run_graph(m, ELL)
    fused = build_program(meta, backend="pallas", jit=False)
    base = build_program(meta, backend="pallas", jit=False,
                         fuse_combine=False)
    b16 = build_program(meta, backend="pallas", jit=False,
                        storage_dtype="bfloat16")
    f_fused = program_features(meta, fused)
    f_base = program_features(meta, base)
    f_b16 = program_features(meta, b16)
    assert f_fused.shape == (len(FEATURE_NAMES),)
    assert f_fused[i_saved] > 0 and f_base[i_saved] == 0
    assert f_base[i_ratio] == pytest.approx(1.0)
    assert f_b16[i_ratio] < 0.65
