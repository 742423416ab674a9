"""AlphaSparse reproduction: machine-designed SpMV formats/kernels in
JAX/Pallas, grown into a sharded / batched / served system.

Public surface (the one compile API)::

    import repro
    plan = repro.compile(matrix, repro.Target(backend="pallas"))
    y = plan(x)                       # (n_cols,) or (n_cols, B)
    plan.save("matrix.plan.npz")
    plan2 = repro.SpmvPlan.load("matrix.plan.npz")

The design space is open (``repro.design``): register out-of-tree
operators with ``@repro.design.register_operator`` and pick the search
policy with ``repro.compile(..., strategy="anneal" | "grid" |
"cost_model" | <SearchStrategy>)`` — see docs/API.md "Extending
AlphaSparse".

Attribute access is lazy (PEP 562): ``import repro`` imports neither jax
nor numpy, so launchers (``repro.launch.dryrun``, benchmarks) can still
set ``XLA_FLAGS`` before the first jax import.
"""

_EXPORTS = {
    # the compile API
    "compile": "repro.api",
    "Target": "repro.api",
    "SpmvPlan": "repro.api",
    "ShardedSpmvPlan": "repro.api",
    "PlanIntegrityError": "repro.api",
    "PlanStore": "repro.api",
    "PlanWatch": "repro.api",
    "load_plan": "repro.api",
    # core containers & search surface
    "SparseMatrix": "repro.core.matrices",
    "read_matrix_market": "repro.core.matrices",
    "make_suite": "repro.core.matrices",
    "OperatorGraph": "repro.core.graph",
    "SearchConfig": "repro.core.search",
    "SearchResult": "repro.core.search",
    "ProgramCache": "repro.core.search",
    "run_search": "repro.core.search",
    # the pluggable design space (repro.design)
    "design": None,                     # submodule, imported lazily
    "register_operator": "repro.design.registry",
    "unregister_operator": "repro.design.registry",
    "Operator": "repro.design.registry",
    "OpSpec": "repro.design.registry",
    "DesignSpace": "repro.design.space",
    "SearchStrategy": "repro.design.strategies",
    "AnnealStrategy": "repro.design.strategies",
    "GridStrategy": "repro.design.strategies",
    "CostModelGuidedStrategy": "repro.design.strategies",
    "LearnedStrategy": "repro.design.strategies",
    "register_strategy": "repro.design.strategies",
    # dynamic sparsity (repro.dyn): patch-in-place plans + drift re-search
    "dyn": None,                        # submodule, imported lazily
    "PatternDelta": "repro.dyn",
    "DriftPolicy": "repro.dyn",
    "DynamicSparsityManager": "repro.dyn",
    "CapacityError": "repro.dyn",
    # fleet corpus harness + learned/portfolio compilation (repro.corpus)
    "corpus": None,                     # submodule, imported lazily
    "CorpusModel": "repro.corpus.model",
    "PortfolioStrategy": "repro.corpus.portfolio",
    # in-process spans and counters (snapshot() / reset())
    "telemetry": None,                  # submodule, imported lazily
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name not in _EXPORTS:
        raise AttributeError(f"module 'repro' has no attribute {name!r}")
    import importlib
    module = _EXPORTS[name]
    if module is None:                  # submodule export (repro.design)
        return importlib.import_module(f"repro.{name}")
    return getattr(importlib.import_module(module), name)


def __dir__():
    return __all__
