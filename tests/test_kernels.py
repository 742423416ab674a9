"""Pallas kernel sweeps: shapes x dtypes against the pure-jnp ref oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand_ell(rng, t, r, w, dtype, n_cols):
    vals = rng.standard_normal((t, r, w)).astype(dtype)
    # random padding: zero out a suffix of each row
    keep = rng.integers(0, w + 1, (t, r, 1))
    mask = np.arange(w)[None, None, :] < keep
    vals = vals * mask
    cols = rng.integers(0, n_cols, (t, r, w)).astype(np.int32)
    return vals, cols


@pytest.mark.slow
@pytest.mark.parametrize("t,r,w", [(1, 8, 4), (3, 8, 16), (5, 16, 1),
                                   (2, 32, 33), (7, 8, 128)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_ell_kernel_sweep(t, r, w, dtype):
    rng = np.random.default_rng(t * 100 + r + w)
    n_cols = 300
    vals, cols = _rand_ell(rng, t, r, w, dtype, n_cols)
    x = rng.standard_normal(n_cols).astype(dtype)
    got = np.asarray(ops.ell_spmv(jnp.asarray(vals), jnp.asarray(cols),
                                  jnp.asarray(x), interpret=True))
    want = np.asarray(ref.ell_spmv_ref(jnp.asarray(vals), jnp.asarray(cols),
                                       jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,r,w", [(2, 8, 8), (4, 16, 5)])
def test_ell_megatile_kernel(t, r, w):
    """Several tiles' row bands per grid step (tiles_per_step > 1)."""
    rng = np.random.default_rng(42)
    n_cols = 128
    vals, cols = _rand_ell(rng, t, r, w, np.float32, n_cols)
    x = rng.standard_normal(n_cols).astype(np.float32)
    got = np.asarray(ops.ell_spmv(jnp.asarray(vals), jnp.asarray(cols),
                                  jnp.asarray(x), tiles_per_step=4,
                                  interpret=True))
    want = np.asarray(ref.ell_spmv_ref(jnp.asarray(vals), jnp.asarray(cols),
                                       jnp.asarray(x)))
    assert got.shape == (t, r)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _rand_seg(rng, t, s, l, m, n_cols):
    """Build a consistent random seg layout: sorted local rows per tile."""
    c = s * l
    local = np.sort(rng.integers(0, m, (t, c)), axis=1)
    # force segment ids to start at 0 per tile (builder invariant)
    local = local - local[:, :1]
    local = np.minimum(local, m - 1)
    vals = rng.standard_normal((t, c)).astype(np.float32)
    cols = rng.integers(0, n_cols, (t, c)).astype(np.int32)
    seg_end = np.full((t, m), c, np.int32)
    for ti in range(t):
        for seg in range(m):
            idx = np.where(local[ti] == seg)[0]
            nxt = np.where(local[ti] > seg)[0]
            seg_end[ti, seg] = (nxt[0] if nxt.size else c)
    shape3 = (t, s, l)
    return (vals.reshape(shape3), cols.reshape(shape3),
            local.astype(np.int32).reshape(shape3), seg_end)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["seg_scan", "onehot_mxu"])
@pytest.mark.parametrize("t,s,l,m", [(1, 2, 8, 8), (3, 4, 16, 16),
                                     (2, 8, 8, 24)])
def test_seg_kernel_sweep(mode, t, s, l, m):
    rng = np.random.default_rng(t + s + l + m)
    n_cols = 200
    vals, cols, local, seg_end = _rand_seg(rng, t, s, l, m, n_cols)
    x = rng.standard_normal(n_cols).astype(np.float32)
    got = np.asarray(ops.seg_spmv(jnp.asarray(vals), jnp.asarray(cols),
                                  jnp.asarray(local), jnp.asarray(seg_end),
                                  jnp.asarray(x), m, mode=mode,
                                  interpret=True))
    want = np.asarray(ref.seg_spmv_ref(jnp.asarray(vals), jnp.asarray(cols),
                                       jnp.asarray(local),
                                       jnp.asarray(seg_end),
                                       jnp.asarray(x), m, mode=mode))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_seg_modes_agree():
    """seg_scan and onehot_mxu are mathematically identical reductions."""
    rng = np.random.default_rng(7)
    vals, cols, local, seg_end = _rand_seg(rng, 3, 2, 16, 8, 100)
    x = rng.standard_normal(100).astype(np.float32)
    a = np.asarray(ref.seg_spmv_ref(jnp.asarray(vals), jnp.asarray(cols),
                                    jnp.asarray(local), jnp.asarray(seg_end),
                                    jnp.asarray(x), 8, mode="seg_scan"))
    b = np.asarray(ref.seg_spmv_ref(jnp.asarray(vals), jnp.asarray(cols),
                                    jnp.asarray(local), jnp.asarray(seg_end),
                                    jnp.asarray(x), 8, mode="onehot_mxu"))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_pallas_backend_end_to_end(small_irregular):
    """Full operator-graph pipeline through the Pallas (interpret) backend."""
    from repro.core.graph import OperatorGraph, run_graph
    from repro.core.kernel_builder import build_spmv
    from repro.core.operators import OpSpec
    from conftest import assert_spmv_matches

    m = small_irregular
    for chain in [
        (OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
         OpSpec.make("LANE_ROW_BLOCK"),
         OpSpec.make("LANE_TOTAL_RED", combine="grid_acc")),
        (OpSpec.make("COMPRESS"),
         OpSpec.make("LANE_NNZ_BLOCK", chunk=128, lanes=16),
         OpSpec.make("SEG_SCAN_RED")),
        (OpSpec.make("COMPRESS"),
         OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
         OpSpec.make("ONEHOT_MXU_RED")),
    ]:
        meta = run_graph(m, OperatorGraph.chain(*chain))
        prog = build_spmv(meta, backend="pallas")
        assert_spmv_matches(m, prog)


def test_ell_layout_caps_width_buckets():
    """A skewed matrix with more distinct tile widths than the cap keeps
    at most 16 width buckets (one kernel each), pads no tile narrower than
    its widest row, and still computes y exactly."""
    from repro.core.graph import OperatorGraph, run_graph
    from repro.core.kernel_builder import build_program
    from repro.core.matrices import powerlaw_matrix
    from repro.core.operators import OpSpec, _MAX_ELL_BUCKETS
    from conftest import assert_spmv_matches

    m = powerlaw_matrix(2048, 2048, 8.0, 1.0, seed=5)
    lengths = m.row_lengths()
    assert np.unique(lengths.reshape(-1, 8).max(axis=1)).size > \
        _MAX_ELL_BUCKETS
    meta = run_graph(m, OperatorGraph.chain(
        OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=8),
        OpSpec.make("LANE_ROW_BLOCK"), OpSpec.make("LANE_TOTAL_RED")))
    buckets = meta.blocks[0].layout.buckets
    assert len(buckets) <= _MAX_ELL_BUCKETS
    for bk in buckets:
        rows = bk.rowmap[bk.rowmap >= 0]
        assert lengths[rows].max() <= bk.width
    assert_spmv_matches(m, build_program(meta, backend="jax"))
