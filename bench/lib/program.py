"""The one door to the program under test.

The benchmark reaches the program only through its public entry points:
``repro.SparseMatrix``, ``repro.SearchConfig``, ``repro.Target``,
``repro.compile``, ``repro.PlanStore`` and the plan's ``__call__`` and
``stored_bytes``. It imports ``repro`` from the checkout's ``src/`` and
nowhere else, so a directory that holds only the benchmark cannot run.
"""
from __future__ import annotations

import sys
from pathlib import Path

from bench.lib.csr import CSR
from bench.lib.registry import ROOT, BenchError


def repro():
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise BenchError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro as mod
    if Path(mod.__file__).resolve().parents[1] != src.resolve():
        raise BenchError(f"repro imported from {mod.__file__}, not {src}")
    return mod


def sparse_matrix(m: CSR):
    return repro().SparseMatrix(m.n_rows, m.n_cols, m.coo_rows(), m.cols,
                                m.vals)


def plan(matrix, batch: int, search: dict, store_dir: Path):
    """The plan ``repro.compile`` makes for ``matrix`` under a search bound
    by counts alone (``max_seconds`` far beyond any run, no deadline),
    kept in a ``PlanStore`` so that only the first run in a checkout
    searches."""
    r = repro()
    budget = r.SearchConfig(max_seconds=1e9, **search)
    store = r.PlanStore(store_dir)
    target = r.Target(backend="pallas", batch_size=batch, dtype="float32")
    return r.compile(matrix, target, budget=budget, store=store)
