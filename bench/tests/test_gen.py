"""The generators and the float64 reference, on the CPU."""
import numpy as np
import pytest

from bench.gen import hpcg27, kronecker
from bench.lib.csr import CSR, rel_err, spmv_f64
from bench.lib.floor import floor_bytes


def dense(m: CSR) -> np.ndarray:
    d = np.zeros((m.n_rows, m.n_cols))
    d[m.coo_rows(), m.cols] = m.vals
    return d


def test_hpcg_104_count():
    # (3 * 104 - 2) ** 3 couplings on HPCG's default local grid
    m = hpcg27.generate({"nx": 104, "ny": 104, "nz": 104, "seed": 0})
    assert (m.n_rows, m.nnz) == (1_124_864, 29_791_000)


def test_hpcg_16_matches_brute_force():
    n = 16
    m = hpcg27.generate({"nx": n, "ny": n, "nz": n, "seed": 3})
    pattern = np.zeros((n ** 3, n ** 3), bool)
    for iz in range(n):
        for iy in range(n):
            for ix in range(n):
                r = ix + n * (iy + n * iz)
                for dz in (-1, 0, 1):
                    for dy in (-1, 0, 1):
                        for dx in (-1, 0, 1):
                            jx, jy, jz = ix + dx, iy + dy, iz + dz
                            if 0 <= jx < n and 0 <= jy < n and 0 <= jz < n:
                                pattern[r, jx + n * (jy + n * jz)] = True
    d = dense(m)
    assert np.array_equal(d != 0, pattern)
    assert (np.diag(d) >= 26).all()
    off = d[pattern & ~np.eye(n ** 3, dtype=bool)]
    assert ((off <= -0.5) & (off > -1.5)).all()
    # CSR invariants: columns strictly increasing within every row
    for r in range(0, n ** 3, 97):
        c = m.cols[m.indptr[r]:m.indptr[r + 1]]
        assert (np.diff(c) > 0).all()


KRON = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def test_kronecker_deterministic_per_seed():
    a = kronecker.generate(dict(KRON, seed=5))
    b = kronecker.generate(dict(KRON, seed=5))
    c = kronecker.generate(dict(KRON, seed=6))
    for f in ("indptr", "cols", "vals"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert a.nnz != c.nnz or not np.array_equal(a.cols, c.cols)


def test_kronecker_symmetric_simple_graph():
    m = kronecker.generate(dict(KRON, seed=1))
    d = dense(m)
    assert m.n_rows == 1 << KRON["scale"]
    assert np.array_equal(d, d.T)
    assert not np.diag(d).any()
    assert ((m.vals > 0) & (m.vals <= 1)).all()
    # sorted, unique columns per row
    key = m.coo_rows().astype(np.int64) * m.n_cols + m.cols
    assert (np.diff(key) > 0).all()
    # skewed: the longest row is far above the mean
    assert m.row_lengths().max() > 10 * m.nnz / m.n_rows


@pytest.mark.parametrize("block", [1, 37, 1 << 22])
def test_reference_equals_dense_product(block):
    m = kronecker.generate(dict(KRON, scale=8, seed=2))
    x = np.random.default_rng(0).standard_normal(m.n_cols)
    assert rel_err(spmv_f64(m, x, block_nnz=block), dense(m) @ x) < 1e-13


def test_rel_err_refuses_bad_output():
    ref = np.ones(4)
    assert rel_err(np.array([1, 1, np.nan, 1]), ref) == float("inf")
    assert rel_err(np.ones(5), ref) == float("inf")


def test_floor_bytes():
    assert floor_bytes(10, 3, 4, 1) == 40 + 28
    assert floor_bytes(10, 3, 4, 8) == 40 + 8 * 28


def test_csr_roundtrip(tmp_path):
    m = hpcg27.generate({"nx": 3, "ny": 4, "nz": 5, "seed": 1})
    m.save(tmp_path / "m.npz")
    back = CSR.load(tmp_path / "m.npz")
    assert (back.n_rows, back.n_cols) == (m.n_rows, m.n_cols)
    for f in ("indptr", "cols", "vals"):
        assert np.array_equal(getattr(back, f), getattr(m, f))
