"""Graph500's Kronecker generator (reference kronecker_generator.m).

2**scale vertices and edgefactor * 2**scale edges; each edge picks one
quadrant per bit with probabilities A, B, C, 1 - A - B - C; vertex labels
and edge order are permuted. The graph is then symmetrised with
self-loops and duplicate edges dropped (the first drawn weight of a
duplicate is kept). Edge weights are U(0, 1], as Graph500's SSSP weights
are U[0, 1) with the zero left out so that no stored value is 0.
"""
from __future__ import annotations

import numpy as np

from bench.lib.csr import CSR, from_sorted_coo


def edges(scale: int, edgefactor: int, a: float, b: float, c: float,
          rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m, dtype=np.float32) > ab
        thresh = np.where(ii_bit, np.float32(c_norm), np.float32(a_norm))
        jj_bit = rng.random(m, dtype=np.float32) > thresh
        ii += ii_bit.astype(np.int64) << bit
        jj += jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    ii, jj = perm[ii], perm[jj]
    order = rng.permutation(m)
    return ii[order], jj[order]


def generate(params: dict) -> CSR:
    scale = int(params["scale"])
    n = 1 << scale
    rng = np.random.default_rng(int(params["seed"]))
    ii, jj = edges(scale, int(params["edgefactor"]), float(params["A"]),
                   float(params["B"]), float(params["C"]), rng)
    w = (1.0 - rng.random(ii.shape[0], dtype=np.float32)).astype(np.float32)
    loop = ii == jj
    u = np.minimum(ii, jj)[~loop]
    v = np.maximum(ii, jj)[~loop]
    w = w[~loop]
    key, first = np.unique(u * n + v, return_index=True)
    u, v, w = key // n, key % n, w[first]
    rows = np.concatenate([u, v])
    cols = np.concatenate([v, u])
    vals = np.concatenate([w, w])
    order = np.argsort(rows * n + cols, kind="stable")
    return from_sorted_coo(n, n, rows[order], cols[order], vals[order])
