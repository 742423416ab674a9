"""SparseLinear: AlphaSparse-generated SpMV as a serving-time layer.

This is the paper's technique as a *first-class framework feature*
(DESIGN.md §4): a magnitude-pruned linear layer's decode-time matvec
``y = W_sparse @ x`` is exactly SpMV. The recommended path prunes a dense
weight and compiles it through the one compile API::

    plan = repro.compile(prune_magnitude(w, 0.1), target, budget=...)
    layer = SparseLinear.from_plan(plan)

``sparsify_linear`` / ``sparsify_linear_sharded`` remain as deprecated
one-call shims over that path.

For batched decode (B small), the layer hands the whole activation batch
to the plan's fused multi-RHS (SpMM) path: the (B, n_cols) batch is
transposed to the plan's (n_cols, B) tile convention, the format arrays
stream once for all B columns, and the result transposes back to
(B, n_rows). Plans/programs advertise this with ``supports_batch = True``;
unknown program types fall back to a vmap over the 1-RHS path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from repro.core import ProgramCache, SearchConfig, SparseMatrix
from repro.core.deprecation import warn_once
from repro.core.graph import OperatorGraph
from repro.core.operators import OpSpec

__all__ = ["SparseLinear", "sparsify_linear", "sparsify_linear_sharded",
           "prune_magnitude"]


def prune_magnitude(w: np.ndarray, density: float) -> SparseMatrix:
    """Keep exactly k = max(1, floor(size * density)) largest-|w| entries.

    Ties at the magnitude threshold break deterministically toward the
    lower row-major flat index, so the result is exactly-k nnz and
    reproducible — a ``>= thresh`` cut would keep *every* tied entry and
    overshoot the requested density."""
    flat = np.abs(w).ravel()
    k = max(1, int(flat.size * density))
    order = np.lexsort((np.arange(flat.size), -flat))
    keep = np.sort(order[:k])
    rows, cols = np.unravel_index(keep, w.shape)
    return SparseMatrix(w.shape[0], w.shape[1], rows.astype(np.int32),
                        cols.astype(np.int32),
                        w[rows, cols].astype(np.float32)).canonical()


@dataclasses.dataclass
class SparseLinear:
    """y = A @ x with A in an AlphaSparse machine-designed format."""

    matrix: SparseMatrix
    graph: Optional[OperatorGraph]
    program: object            # SpmvPlan | SpmvProgram | ShardedSpmvPlan
    search_gflops: Optional[float] = None

    @classmethod
    def from_plan(cls, plan, matrix: Optional[SparseMatrix] = None
                  ) -> "SparseLinear":
        """Wrap a compiled ``repro.SpmvPlan`` as a serving layer."""
        return cls(matrix, getattr(plan, "graph", None), plan,
                   getattr(plan, "search_gflops", None))

    def update(self, delta) -> "SparseLinear":
        """Dynamic-sparsity step: patch the plan in place (``repro.dyn``).

        Applies a ``repro.dyn.PatternDelta`` to the wrapped plan (same
        treedef, no retrace — see ``SpmvPlan.update``) and to the
        attached matrix, returning a new layer. Raises
        ``repro.dyn.CapacityError`` when the delta does not fit the
        format; escalate to ``repro.dyn.DynamicSparsityManager`` (which
        re-searches in the background) or a fresh ``repro.compile``."""
        new_program = self.program.update(delta)
        new_matrix = (delta.apply_to(self.matrix)
                      if self.matrix is not None else None)
        return dataclasses.replace(self, matrix=new_matrix,
                                   program=new_program)

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (n_cols,) or (B, n_cols) -> (n_rows,) or (B, n_rows)."""
        if x.ndim == 1:
            return self.program(x)
        if getattr(self.program, "supports_batch", False):
            # fused multi-RHS: program convention is (n_cols, B) columns
            return self.program(x.T).T
        return jax.vmap(lambda xi: self.program(xi))(x)

    @property
    def density(self) -> Optional[float]:
        """nnz / (n_rows * n_cols). Prefers the wrapped matrix; a layer
        built with ``from_plan(plan)`` (no matrix) derives it from the
        plan's stored geometry. None — with a warning — when neither
        carries it (e.g. an opaque program object)."""
        if self.matrix is not None:
            return self.matrix.nnz / (self.matrix.n_rows * self.matrix.n_cols)
        nnz = getattr(self.program, "nnz", None)
        n_rows = getattr(self.program, "n_rows", None)
        n_cols = getattr(self.program, "n_cols", None)
        if nnz is not None and n_rows and n_cols:
            return nnz / (n_rows * n_cols)
        import warnings
        warnings.warn(
            "SparseLinear.density is unknown: no matrix is attached and "
            f"the program ({type(self.program).__name__}) does not carry "
            "nnz/n_rows/n_cols; pass the matrix to from_plan(plan, matrix)",
            RuntimeWarning, stacklevel=2)
        return None


_DEFAULT_GRAPH = OperatorGraph.chain(
    OpSpec.make("COMPRESS"),
    OpSpec.make("TILE_ROW_BLOCK", rows=8),
    OpSpec.make("SORT_TILE", window=8),
    OpSpec.make("LANE_ROW_BLOCK"),
    OpSpec.make("LANE_TOTAL_RED", combine="scatter"))


def sparsify_linear(w: np.ndarray, density: float = 0.1,
                    search_config: Optional[SearchConfig] = None,
                    do_search: bool = True,
                    cache: Optional[ProgramCache] = None) -> SparseLinear:
    """Deprecated shim: prune + ``repro.compile`` + ``SparseLinear``.

    do_search=False skips the (minutes-long) AlphaSparse search and uses a
    sensible default graph — handy in tests; production path searches.
    ``cache`` (a ``repro.core.ProgramCache``, optionally disk-backed) lets
    serving restarts reuse a prior search for the same pruned weight; set
    ``search_config.batch_size`` to the serving decode batch so the design
    is tuned for the fused multi-RHS path."""
    warn_once("sparsify_linear",
              "sparsify_linear is deprecated; use repro.compile("
              "prune_magnitude(w, density), target) and "
              "SparseLinear.from_plan(plan)")
    from repro.api import Target, compile as _compile
    m = prune_magnitude(np.asarray(w), density)
    if do_search:
        cfg = search_config or SearchConfig(max_seconds=30, max_structures=8)
        plan = _compile(m, Target(backend=cfg.backend,
                                  batch_size=max(cfg.batch_size, 1)),
                        budget=cfg, cache=cache)
        return SparseLinear(m, plan.graph, plan, plan.search_gflops)
    plan = _compile(m, Target(), graph=_DEFAULT_GRAPH)
    return SparseLinear(m, _DEFAULT_GRAPH, plan)


def sparsify_linear_sharded(w: np.ndarray, mesh, density: float = 0.1,
                            do_search: bool = False,
                            dist_config=None) -> SparseLinear:
    """Deprecated shim: prune + sharded ``repro.compile``.

    The pruned weight is partitioned over the mesh's ``data`` axis and
    each shard gets its own design (heuristic by default; ``do_search=True``
    runs one AlphaSparse search per shard). The returned layer's program is
    a sharded plan — one SPMD shard_map program whose per-family stacked
    formats are sharded operands (1/n_shards stored per device).
    """
    warn_once("sparsify_linear_sharded",
              "sparsify_linear_sharded is deprecated; use repro.compile("
              "prune_magnitude(w, density), Target(mesh=mesh)) and "
              "SparseLinear.from_plan(plan)")
    from repro.api import Target, compile as _compile
    from repro.dist.search import ShardedSearchConfig

    m = prune_magnitude(np.asarray(w), density)
    cfg = dist_config or ShardedSearchConfig()
    target = Target(backend=cfg.backend, mesh=mesh,
                    axis_name=cfg.axis_name, partition=cfg.mode,
                    balance=cfg.balance)
    plan = _compile(m, target, budget=cfg if do_search else None)
    return SparseLinear(m, None, plan)
