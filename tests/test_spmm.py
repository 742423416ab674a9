"""Fused multi-RHS (SpMM) parity and batching-protocol tests.

Three-way agreement at kernel level: batched Pallas (interpret) vs the
jax-backend einsum oracle vs a per-column loop of the 1-RHS kernel — for
ELL (scatter + direct) and both SEG modes, including the B=1 degenerate
tile and a B that is not a multiple of any lane width. Program level:
``SpmvProgram``/``ShardedSpmvProgram`` dispatch on x.ndim, the
``supports_batch`` protocol in ``SparseLinear``, and the search-time
``batch_size`` / ``ProgramCache`` plumbing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# B sweep: degenerate single-RHS tile, non-multiple-of-lane, serving default
BATCHES = [1, 3, 8]


def _rand_ell(rng, t, r, w, n_cols):
    vals = rng.standard_normal((t, r, w)).astype(np.float32)
    keep = rng.integers(0, w + 1, (t, r, 1))
    vals = vals * (np.arange(w)[None, None, :] < keep)
    cols = rng.integers(0, n_cols, (t, r, w)).astype(np.int32)
    return jnp.asarray(vals), jnp.asarray(cols)


def _rand_seg(rng, t, s, l, m, n_cols):
    c = s * l
    local = np.sort(rng.integers(0, m, (t, c)), axis=1)
    local = np.minimum(local - local[:, :1], m - 1)
    vals = rng.standard_normal((t, c)).astype(np.float32)
    cols = rng.integers(0, n_cols, (t, c)).astype(np.int32)
    seg_end = np.full((t, m), c, np.int32)
    for ti in range(t):
        for seg in range(m):
            nxt = np.where(local[ti] > seg)[0]
            seg_end[ti, seg] = (nxt[0] if nxt.size else c)
    sh = (t, s, l)
    return (jnp.asarray(vals.reshape(sh)), jnp.asarray(cols.reshape(sh)),
            jnp.asarray(local.astype(np.int32).reshape(sh)),
            jnp.asarray(seg_end))


# ------------------------- kernel-level parity ------------------------------

@pytest.mark.parametrize("b", BATCHES)
def test_ell_spmm_three_way(b):
    rng = np.random.default_rng(b)
    vals, cols = _rand_ell(rng, 3, 8, 16, 100)
    x = jnp.asarray(rng.standard_normal((100, b)).astype(np.float32))
    pallas = np.asarray(ops.ell_spmm(vals, cols, x, interpret=True))
    oracle = np.asarray(ref.ell_spmm_ref(vals, cols, x))
    percol = np.stack([np.asarray(ref.ell_spmv_ref(vals, cols, x[:, i]))
                       for i in range(b)], axis=-1)
    np.testing.assert_allclose(pallas, oracle, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(oracle, percol, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", [1, 3])
def test_ell_spmm_megatile_three_way(b):
    rng = np.random.default_rng(10 + b)
    vals, cols = _rand_ell(rng, 4, 16, 5, 128)
    x = jnp.asarray(rng.standard_normal((128, b)).astype(np.float32))
    pallas = np.asarray(ops.ell_spmm(vals, cols, x, tiles_per_step=2,
                                     interpret=True))
    oracle = np.asarray(ref.ell_spmm_ref(vals, cols, x))
    percol = np.stack(
        [np.asarray(ref.ell_spmv_ref(vals, cols, x[:, i]))
         for i in range(b)], axis=-1)
    assert pallas.shape == (4, 16, b)
    np.testing.assert_allclose(pallas, oracle, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(oracle, percol, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["seg_scan", "onehot_mxu"])
@pytest.mark.parametrize("b", BATCHES)
def test_seg_spmm_three_way(mode, b):
    rng = np.random.default_rng(20 + b)
    vals, cols, local, seg_end = _rand_seg(rng, 2, 4, 8, 8, 90)
    x = jnp.asarray(rng.standard_normal((90, b)).astype(np.float32))
    pallas = np.asarray(ops.seg_spmm(vals, cols, local, seg_end, x, 8,
                                     mode=mode, interpret=True))
    oracle = np.asarray(ref.seg_spmm_ref(vals, cols, local, seg_end, x, 8,
                                         mode=mode))
    percol = np.stack(
        [np.asarray(ref.seg_spmv_ref(vals, cols, local, seg_end, x[:, i], 8,
                                     mode=mode)) for i in range(b)], axis=-1)
    np.testing.assert_allclose(pallas, oracle, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(oracle, percol, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["seg_scan", "onehot_mxu"])
@pytest.mark.parametrize("t,s,l,m", [(1, 2, 8, 8), (3, 4, 16, 16),
                                     (2, 8, 8, 24)])
def test_seg_spmm_shape_sweep(mode, t, s, l, m):
    rng = np.random.default_rng(t * 100 + s + l + m)
    vals, cols, local, seg_end = _rand_seg(rng, t, s, l, m, 200)
    x = jnp.asarray(rng.standard_normal((200, 8)).astype(np.float32))
    got = np.asarray(ops.seg_spmm(vals, cols, local, seg_end, x, m,
                                  mode=mode, interpret=True))
    want = np.asarray(ref.seg_spmm_ref(vals, cols, local, seg_end, x, m,
                                       mode=mode))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------- program-level dispatch ---------------------------

def _graphs():
    from repro.core.graph import OperatorGraph
    from repro.core.operators import OpSpec
    return {
        "ell_grid_acc": OperatorGraph.chain(
            OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
            OpSpec.make("LANE_ROW_BLOCK"),
            OpSpec.make("LANE_TOTAL_RED", combine="grid_acc")),
        "seg_scan": OperatorGraph.chain(
            OpSpec.make("COMPRESS"),
            OpSpec.make("LANE_NNZ_BLOCK", chunk=128, lanes=16),
            OpSpec.make("SEG_SCAN_RED")),
        "gmem_atom": OperatorGraph.chain(
            OpSpec.make("COMPRESS"),
            OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
            OpSpec.make("GMEM_ATOM_RED")),
    }


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_program_batched_matches_oracle(backend, small_irregular):
    from repro.core.graph import run_graph
    from repro.core.kernel_builder import build_spmv
    m = small_irregular
    rng = np.random.default_rng(0)
    X = rng.standard_normal((m.n_cols, 3)).astype(np.float32)
    oracle = m.spmm_dense_oracle(X)
    scale = np.abs(oracle).max() + 1e-30
    for name, g in _graphs().items():
        prog = build_spmv(run_graph(m, g), backend=backend)
        assert prog.supports_batch
        Y = np.asarray(prog(jnp.asarray(X)))
        assert Y.shape == (m.n_rows, 3)
        np.testing.assert_allclose(Y, oracle, atol=1e-4 * scale, rtol=0,
                                   err_msg=f"{name}/{backend}")
        # 1-RHS path still live on the same program
        y = np.asarray(prog(jnp.asarray(X[:, 0])))
        np.testing.assert_allclose(y, oracle[:, 0], atol=1e-4 * scale,
                                   rtol=0)


def test_sparse_linear_fused_dispatch_no_vmap(monkeypatch):
    """Batched SparseLinear must take the fused path for supports_batch
    programs and only vmap for unknown program types."""
    from repro.serve import sparse_linear as sl_mod
    rng = np.random.default_rng(3)
    w = rng.standard_normal((64, 48)).astype(np.float32)
    sl = sl_mod.sparsify_linear(w, density=0.2, do_search=False)
    assert getattr(sl.program, "supports_batch", False)

    def boom(*a, **k):
        raise AssertionError("vmap fallback used for a supports_batch "
                             "program")
    monkeypatch.setattr(sl_mod.jax, "vmap", boom)
    X = rng.standard_normal((4, 48)).astype(np.float32)
    Y = np.asarray(sl(X))
    want = X @ sl.matrix.to_dense().T.astype(np.float32)
    np.testing.assert_allclose(Y, want, rtol=1e-4, atol=1e-4)
    monkeypatch.undo()

    class LegacyProgram:          # no supports_batch attribute
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, x):
            return self.inner(x)

    legacy = sl_mod.SparseLinear(sl.matrix, sl.graph,
                                 LegacyProgram(sl.program))
    np.testing.assert_allclose(np.asarray(legacy(X)), want,
                               rtol=1e-4, atol=1e-4)


def test_sharded_program_batched_convention():
    """ShardedSpmvProgram takes (n_cols, B) tiles like SpmvProgram."""
    from repro.core.matrices import powerlaw_matrix
    from repro.dist.spmv import shard_map_spmv
    m = powerlaw_matrix(120, 90, 4.0, 1.0, seed=8)
    mesh = jax.make_mesh((1,), ("data",))
    for mode in ("row", "col"):
        prog = shard_map_spmv(m, mesh, mode=mode)
        assert prog.supports_batch
        X = np.random.default_rng(1).standard_normal(
            (m.n_cols, 5)).astype(np.float32)
        want = m.spmm_dense_oracle(X)
        scale = np.abs(want).max() + 1e-30
        Y = np.asarray(prog(X))
        assert Y.shape == (m.n_rows, 5)
        np.testing.assert_allclose(Y, want, atol=1e-4 * scale, rtol=0)


# ------------------- batched search + program cache -------------------------

_CACHE_CFG = dict(max_seconds=10, max_structures=2, coarse_samples=2,
                  fine_eval_budget=0, timing_repeats=1,
                  use_cost_model=False, seed=5)


def test_search_batch_size_times_spmm(small_uniform):
    from repro.core.search import SearchConfig, search
    cfg = SearchConfig(batch_size=4, **_CACHE_CFG)
    res = search(small_uniform, cfg)
    m = small_uniform
    X = np.random.default_rng(2).standard_normal(
        (m.n_cols, 4)).astype(np.float32)
    want = m.spmm_dense_oracle(X)
    scale = np.abs(want).max() + 1e-30
    Y = np.asarray(res.best_program(jnp.asarray(X)))
    np.testing.assert_allclose(Y, want, atol=1e-4 * scale, rtol=0)
    # gflops accounts for all B right-hand sides
    assert res.gflops > 0
    # batch-aware features recorded for the cost model
    from repro.core.cost_model import FEATURE_NAMES
    i = FEATURE_NAMES.index("batch_size")
    assert all(r.features[i] == 4.0 for r in res.records)


def test_program_cache_hit_memory_and_disk(small_uniform, tmp_path):
    from repro.core.search import ProgramCache, SearchConfig, search
    cfg = SearchConfig(batch_size=2, **_CACHE_CFG)
    cache = ProgramCache(str(tmp_path))
    r1 = search(small_uniform, cfg, cache=cache)
    assert (cache.hits, cache.misses) == (0, 1)
    r2 = search(small_uniform, cfg, cache=cache)
    assert r2 is r1 and cache.hits == 1       # in-memory hit
    # fresh cache over the same dir = process restart: disk hit rebuilds
    # the program from the stored graph without re-searching
    restart = ProgramCache(str(tmp_path))
    r3 = search(small_uniform, cfg, cache=restart)
    assert r3.cached and r3.best_graph == r1.best_graph
    m = small_uniform
    X = np.random.default_rng(0).standard_normal(
        (m.n_cols, 2)).astype(np.float32)
    want = m.spmm_dense_oracle(X)
    scale = np.abs(want).max() + 1e-30
    np.testing.assert_allclose(np.asarray(r3.best_program(jnp.asarray(X))),
                               want, atol=1e-4 * scale, rtol=0)
    # batch_size is part of the key: different B = different entry
    assert (ProgramCache.key(m, dataclasses.replace(cfg, batch_size=8))
            != ProgramCache.key(m, cfg))
