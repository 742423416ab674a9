"""The benchmark's own sparse matrix: CSR arrays in numpy, and the float64
reference product. Nothing here imports the program under test."""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class CSR:
    """Rows sorted, columns sorted within a row, no duplicates."""

    n_rows: int
    n_cols: int
    indptr: np.ndarray   # int64[n_rows + 1]
    cols: np.ndarray     # int32[nnz]
    vals: np.ndarray     # float32[nnz]

    @property
    def nnz(self) -> int:
        return int(self.cols.shape[0])

    def row_lengths(self) -> np.ndarray:
        return np.diff(self.indptr)

    def coo_rows(self) -> np.ndarray:
        """int32 row index of every stored entry."""
        return np.repeat(np.arange(self.n_rows, dtype=np.int32),
                         self.row_lengths())

    def save(self, path) -> None:
        np.savez(path, shape=np.asarray([self.n_rows, self.n_cols], np.int64),
                 indptr=self.indptr, cols=self.cols, vals=self.vals)

    @staticmethod
    def load(path) -> "CSR":
        with np.load(path) as z:
            n_rows, n_cols = (int(v) for v in z["shape"])
            return CSR(n_rows, n_cols, z["indptr"], z["cols"], z["vals"])


def from_sorted_coo(n_rows: int, n_cols: int, rows, cols, vals) -> CSR:
    """CSR from COO triplets already sorted by (row, col), unique."""
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
    return CSR(n_rows, n_cols, indptr, np.asarray(cols, np.int32),
               np.asarray(vals, np.float32))


def spmv_f64(m: CSR, x: np.ndarray, block_nnz: int = 1 << 22) -> np.ndarray:
    """y = A @ x in float64, a block of rows at a time so that the
    temporaries stay a few tens of MB whatever the matrix."""
    x = np.asarray(x, np.float64)
    y = np.zeros(m.n_rows, np.float64)
    r0 = 0
    while r0 < m.n_rows:
        # rows [r0, r1) hold about block_nnz entries (at least one row)
        r1 = int(np.searchsorted(m.indptr, m.indptr[r0] + block_nnz,
                                 side="right"))
        r1 = min(max(r1 - 1, r0 + 1), m.n_rows)
        a, b = int(m.indptr[r0]), int(m.indptr[r1])
        prod = m.vals[a:b].astype(np.float64) * x[m.cols[a:b]]
        local = np.repeat(np.arange(r1 - r0), np.diff(m.indptr[r0:r1 + 1]))
        y[r0:r1] = np.bincount(local, weights=prod, minlength=r1 - r0)
        r0 = r1
    return y


def rel_err(y, ref: np.ndarray) -> float:
    """max |y - ref| over max |ref|; inf for a wrong shape or a non-finite
    output, so that neither can pass a limit."""
    y = np.asarray(y, np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return float("inf")
    return float(np.abs(y - ref).max() / (np.abs(ref).max() + 1e-30))
