"""HPCG's operator: the 27-point stencil on an nx x ny x nz local grid.

Row i = ix + nx*(iy + ny*iz) couples to every grid point (ix+dx, iy+dy,
iz+dz), dx, dy, dz in {-1, 0, 1}, that lies inside the grid (HPCG's
GenerateProblem with no neighbouring ranks). Coefficients are drawn from
``seed`` instead of HPCG's constant 26 / -1: the diagonal is 26 + U[0, 1)
and every off-diagonal -U[0.5, 1.5), so no format can drop the values.
"""
from __future__ import annotations

import numpy as np

from bench.lib.csr import CSR


def generate(params: dict) -> CSR:
    nx, ny, nz = (int(params[k]) for k in ("nx", "ny", "nz"))
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    ix, iy, iz = idx % nx, (idx // nx) % ny, idx // (nx * ny)
    # offsets in (dz, dy, dx) order give increasing columns within a row
    offs = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
            for dx in (-1, 0, 1)]
    cols = np.empty((n, len(offs)), np.int32)
    keep = np.empty((n, len(offs)), bool)
    for k, (dz, dy, dx) in enumerate(offs):
        keep[:, k] = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                      & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
        cols[:, k] = idx + dx + nx * (dy + ny * dz)
    row_len = keep.sum(axis=1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(row_len, out=indptr[1:])
    flat_cols = cols[keep]                      # row-major: sorted CSR
    rng = np.random.default_rng(int(params["seed"]))
    vals = -rng.uniform(0.5, 1.5, flat_cols.shape[0]).astype(np.float32)
    # (0, 0, 0) is always inside; its slot follows the kept offsets before it
    diag_at = indptr[:-1] + keep[:, :len(offs) // 2].sum(axis=1)
    vals[diag_at] = (26.0 + rng.random(n)).astype(np.float32)
    return CSR(n, n, indptr, flat_cols, vals)
