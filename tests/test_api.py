"""The one compile API: Target / compile / SpmvPlan / PlanStore.

Covers the ISSUE-3 acceptance criteria:
* plan round trip (save -> load -> __call__) is bit-exact vs the live
  program on all 4 matrix families at B in {1, 8}, for both backends
  (pallas in interpret mode);
* sharded plans run backend="pallas" (interpret) inside shard_map with
  per-device format bytes below the closure-replication baseline;
* the deprecated entrypoints warn once and agree with the new path;
* cost_analysis() shape normalization is shared with launch/dryrun.
"""
import dataclasses
import json
import warnings

import numpy as np
import pytest

import jax

import repro
from repro.core.deprecation import reset_warnings
from repro.core.matrices import (banded_matrix, hyb_friendly_matrix,
                                 powerlaw_matrix, random_uniform_matrix)
from repro.dist.spmv import default_shard_graph


# the 4 benchmark matrix families (regularity axes of the paper's Figure 9
# suite, same as benchmarks/spmm_batch.py) at test scale
def _families():
    n = 160
    return {
        "banded": banded_matrix(n, 3, seed=1),
        "uniform": random_uniform_matrix(n, n, 6.0 / n, seed=2),
        "powerlaw": powerlaw_matrix(n, n, 6.0, 1.2, seed=3),
        "hyb": hyb_friendly_matrix(n, 5, max(n // 64, 2), 60, seed=4),
    }


def _x(m, b, seed=0):
    rng = np.random.default_rng(seed)
    shape = (m.n_cols,) if b == 1 else (m.n_cols, b)
    return rng.standard_normal(shape).astype(np.float32)


# ------------------------- serialization round trip -------------------------

@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_plan_roundtrip_bit_exact_all_families(backend, tmp_path):
    """save -> load -> __call__ bit-exact vs the live plan, 4 families x
    B in {1, 8}, both backends (pallas interpret)."""
    for name, m in _families().items():
        plan = repro.compile(m, repro.Target(backend=backend),
                             graph=default_shard_graph(m))
        path = tmp_path / f"{name}.{backend}.plan.npz"
        plan.save(path)
        loaded = repro.SpmvPlan.load(path)
        assert loaded.target == plan.target
        assert loaded.spec == plan.spec
        for b in (1, 8):
            x = _x(m, b)
            live = np.asarray(plan(x))
            oracle = (m.spmv_dense_oracle(x) if b == 1
                      else m.spmm_dense_oracle(x))
            scale = np.abs(oracle).max() + 1e-30
            np.testing.assert_allclose(live, oracle, atol=1e-4 * scale,
                                       rtol=0, err_msg=f"{name} B={b}")
            got = np.asarray(loaded(x))
            assert np.array_equal(got, live), \
                f"{name}/{backend} B={b}: loaded plan not bit-exact"


def test_searched_plan_roundtrip_bit_exact(small_uniform, tmp_path):
    """Round trip of a live-*searched* plan (graph + arrays, no replay)."""
    cfg = repro.SearchConfig(max_seconds=10, max_structures=1,
                             coarse_samples=1, timing_repeats=1,
                             use_cost_model=False, seed=3)
    plan = repro.compile(small_uniform, budget=cfg)
    assert plan.search_result is not None
    assert plan.search_gflops > 0
    path = tmp_path / "searched.plan.npz"
    plan.save(path)
    loaded = repro.SpmvPlan.load(path)
    assert loaded.graph.label() == plan.graph.label()
    x = _x(small_uniform, 1)
    assert np.array_equal(np.asarray(loaded(x)), np.asarray(plan(x)))


def test_plan_is_pytree(small_uniform):
    plan = repro.compile(small_uniform, graph=default_shard_graph(
        small_uniform))
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    assert len(leaves) == len(plan.fmt) and len(leaves) > 0
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    x = _x(small_uniform, 1)
    assert np.array_equal(np.asarray(rebuilt(x)), np.asarray(plan(x)))
    # leaves are the format arrays: a tree_map survives and stays callable
    doubled = jax.tree_util.tree_map(lambda a: a, plan)
    assert np.array_equal(np.asarray(doubled(x)), np.asarray(plan(x)))


def test_plan_describe_and_geometry(small_uniform):
    plan = repro.compile(small_uniform,
                         graph=default_shard_graph(small_uniform))
    assert plan.n_rows == small_uniform.n_rows
    assert plan.n_cols == small_uniform.n_cols
    assert plan.nnz == small_uniform.nnz
    text = plan.describe()
    assert "SpmvPlan" in text and "backend=jax" in text


# ------------------------------ PlanStore -----------------------------------

def test_plan_store_roundtrip(small_uniform, tmp_path):
    store = repro.PlanStore(tmp_path / "plans")
    g = default_shard_graph(small_uniform)
    p1 = repro.compile(small_uniform, graph=g, store=store)
    p2 = repro.compile(small_uniform, graph=g, store=store)
    assert store.misses == 1 and store.hits == 1
    x = _x(small_uniform, 1)
    assert np.array_equal(np.asarray(p1(x)), np.asarray(p2(x)))
    # a different Target is a different key
    p3 = repro.compile(small_uniform, repro.Target(backend="pallas"),
                       graph=g, store=store)
    assert store.misses == 2
    assert p3.target.backend == "pallas"


def test_plan_store_suggest_empty_and_boundary(small_uniform, tmp_path):
    store = repro.PlanStore(tmp_path / "plans")
    # empty store: None, and (None, inf) with the distance
    assert store.suggest(small_uniform) is None
    g, d = store.suggest(small_uniform, with_distance=True)
    assert g is None and d == float("inf")
    repro.compile(small_uniform, graph=default_shard_graph(small_uniform),
                  store=store)
    # the stored matrix sits at distance exactly 0 (stats round-trip
    # exactly through JSON); max_distance is an inclusive boundary
    g, d = store.suggest(small_uniform, max_distance=0.0, with_distance=True)
    assert g is not None and d == 0.0
    assert store.suggest(small_uniform, max_distance=0.0) is not None


def test_plan_store_suggest_skips_corrupt_sidecar(small_uniform,
                                                  small_regular, tmp_path):
    from repro.api import _matrix_stats
    store = repro.PlanStore(tmp_path / "plans")
    repro.compile(small_uniform, graph=default_shard_graph(small_uniform),
                  store=store)
    repro.compile(small_regular, graph=default_shard_graph(small_regular),
                  store=store)
    inf = float("inf")
    assert store.suggest(small_uniform, max_distance=inf) is not None
    # corrupt the exact match in place. The sidecar index is per-instance
    # (revalidated by directory mtime, which an in-place rewrite does not
    # bump), so a FRESH store must skip it and fall back to the neighbour.
    stats_u = _matrix_stats(small_uniform)
    n_corrupted = 0
    for p in (tmp_path / "plans").glob("*.stats.json"):
        if json.loads(p.read_text())["stats"] == stats_u:
            p.write_text("{ not json")
            n_corrupted += 1
    assert n_corrupted == 1
    fresh = repro.PlanStore(tmp_path / "plans")
    g, d = fresh.suggest(small_uniform, max_distance=inf, with_distance=True)
    assert g is not None and 0.0 < d < inf
    # corrupt everything: nothing left to suggest
    for p in (tmp_path / "plans").glob("*.stats.json"):
        p.write_text("not json at all")
    assert repro.PlanStore(tmp_path / "plans").suggest(
        small_uniform, max_distance=inf) is None


def test_plan_store_suggest_index_tracks_new_entries(small_uniform,
                                                     small_regular, tmp_path):
    """Atomic sidecar writes bump the directory mtime, so the same
    instance's index picks up entries stored after its first suggest()."""
    store = repro.PlanStore(tmp_path / "plans")
    repro.compile(small_regular, graph=default_shard_graph(small_regular),
                  store=store)
    _, d1 = store.suggest(small_uniform, max_distance=float("inf"),
                          with_distance=True)
    assert 0.0 < d1 < float("inf")
    repro.compile(small_uniform, graph=default_shard_graph(small_uniform),
                  store=store)
    g2, d2 = store.suggest(small_uniform, with_distance=True)
    assert g2 is not None and d2 == 0.0


def test_plan_store_suggest_cross_strategy(small_uniform, tmp_path):
    """Sidecars are strategy-agnostic: suggest() reads entries written by
    a searched compile and a fixed-graph compile alike."""
    from repro.core.search import SearchConfig
    store = repro.PlanStore(tmp_path / "plans")
    cfg = SearchConfig(max_seconds=20, max_structures=2, coarse_samples=1,
                       fine_eval_budget=0, timing_repeats=1,
                       use_cost_model=False, seed=3)
    repro.compile(small_uniform, budget=cfg, strategy="grid", store=store)
    repro.compile(small_uniform, graph=default_shard_graph(small_uniform),
                  store=store)
    assert store.misses == 2          # distinct keys, both stored
    assert len(list((tmp_path / "plans").glob("*.stats.json"))) == 2
    g, d = store.suggest(small_uniform, with_distance=True)
    assert g is not None and d == 0.0


# ------------------------------ sharded plans -------------------------------

def _mesh1():
    return jax.make_mesh((1,), ("data",))


@pytest.mark.parametrize("mode", ["row", "col"])
def test_sharded_plan_pallas_in_shard_map(mode, small_irregular):
    """backend="pallas" (interpret) runs inside the shard_map body — the
    ROADMAP "Pallas on-device path for dist" item."""
    m = small_irregular
    t = repro.Target(backend="pallas", mesh=_mesh1(),
                     partition=mode)
    plan = repro.compile(m, t)
    for b in (1, 8):
        x = _x(m, b)
        oracle = (m.spmv_dense_oracle(x) if b == 1
                  else m.spmm_dense_oracle(x))
        scale = np.abs(oracle).max() + 1e-30
        np.testing.assert_allclose(np.asarray(plan(x)), oracle,
                                   atol=1e-4 * scale, rtol=0)


def test_sharded_plan_roundtrip_and_bytes(small_irregular, tmp_path):
    mesh = _mesh1()
    plan = repro.compile(small_irregular, repro.Target(mesh=mesh))
    assert plan.per_device_format_bytes > 0
    assert plan.replicated_format_bytes > 0
    path = tmp_path / "sharded.plan.npz"
    plan.save(path)
    # loading without a mesh yields a plan that refuses to run...
    detached = repro.load_plan(path)
    with pytest.raises(ValueError, match="mesh"):
        detached(_x(small_irregular, 1))
    # ...re-attaching a mesh restores bit-exact execution
    loaded = repro.SpmvPlan.load(path, mesh=mesh)
    for b in (1, 8):
        x = _x(small_irregular, b)
        assert np.array_equal(np.asarray(loaded(x)), np.asarray(plan(x)))


def test_sharded_dedup_vs_closure_baseline():
    """Operand passing stores ~1/N of the formats per device — the ROADMAP
    "dist format memory dedup" item (real 4-way split via fake devices is
    exercised in benchmarks/dist_scaling.py + the 8-device subprocess)."""
    from repro.dist.spmv import shard_map_spmv
    m = powerlaw_matrix(400, 360, 6.0, 1.2, seed=5)
    prog = shard_map_spmv(m, _mesh1(), mode="row")
    # with one device the stacked operand layout must not exceed ~1 shard
    # of padding overhead vs the logical format bytes
    assert prog.per_device_format_bytes <= 4 * prog.replicated_format_bytes
    assert prog.per_device_format_bytes > 0


# ------------------------------ cost analysis -------------------------------

def test_plan_cost_analysis_normalized(small_uniform):
    plan = repro.compile(small_uniform,
                         graph=default_shard_graph(small_uniform))
    ca = plan.cost_analysis()
    assert isinstance(ca, dict)
    assert ca.get("flops", 0) > 0
    ca8 = plan.cost_analysis(batch_size=8)
    assert isinstance(ca8, dict)


# ----------------------------- deprecation shims ----------------------------

def test_search_shim_warns_once_and_matches_compile(small_uniform):
    from repro.core.search import search
    cfg = repro.SearchConfig(max_seconds=10, max_structures=1,
                             coarse_samples=1, timing_repeats=1,
                             use_cost_model=False, seed=9)
    # a shared cache pins both paths to one SearchResult: two independent
    # wall-clock-timed searches may legitimately pick different winners
    shared = repro.ProgramCache()
    reset_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res = search(small_uniform, cfg, cache=shared)
        search(small_uniform, cfg, cache=shared)  # no second warning
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)
           and "repro.compile" in str(w.message)]
    assert len(dep) == 1
    plan = repro.compile(small_uniform, budget=cfg, cache=shared)
    x = _x(small_uniform, 1)
    np.testing.assert_array_equal(np.asarray(res.best_program(x)),
                                  np.asarray(plan(x)))
    assert res.best_graph.label() == plan.graph.label()


def test_build_spmv_shim_warns_and_matches(small_uniform):
    from repro.core.graph import run_graph
    from repro.core.kernel_builder import build_program, build_spmv
    g = default_shard_graph(small_uniform)
    meta = run_graph(small_uniform, g)
    reset_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        old = build_spmv(meta)
    assert any(issubclass(w.category, DeprecationWarning) for w in caught)
    new = build_program(meta)
    x = _x(small_uniform, 1)
    np.testing.assert_array_equal(np.asarray(old(x)), np.asarray(new(x)))


def test_sparsify_linear_shim_warns_and_matches():
    from repro.serve.sparse_linear import (SparseLinear, prune_magnitude,
                                           sparsify_linear)
    rng = np.random.default_rng(4)
    w = rng.standard_normal((96, 80)).astype(np.float32)
    reset_warnings()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sl = sparsify_linear(w, density=0.15, do_search=False)
    assert any(issubclass(w_.category, DeprecationWarning) and
               "repro.compile" in str(w_.message) for w_ in caught)
    # parity with the new surface
    m = prune_magnitude(w, 0.15)
    plan = repro.compile(m, graph=sl.graph)
    sl_new = SparseLinear.from_plan(plan, m)
    X = rng.standard_normal((3, 80)).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(sl(X)), np.asarray(sl_new(X)))


# ------------------------------- Target -------------------------------------

def test_target_validation_and_key():
    with pytest.raises(ValueError):
        repro.Target(backend="cuda")
    with pytest.raises(ValueError):
        repro.Target(partition="diag")
    with pytest.raises(ValueError):
        repro.Target(dtype="float16")
    # bf16 storage + pallas is supported since the fused-combine PR
    assert repro.Target(backend="pallas", dtype="bfloat16").dtype == \
        "bfloat16"
    a, b = repro.Target(), repro.Target(batch_size=8)
    assert a.key() != b.key()
    assert a.key() == repro.Target().key()


def test_compile_budget_seconds(small_uniform):
    cfg = dataclasses.replace(repro.SearchConfig(), max_seconds=7.0)
    from repro.api import _as_search_config
    assert _as_search_config(7.0, repro.Target()).max_seconds == \
        cfg.max_seconds
    assert _as_search_config(None, repro.Target(batch_size=4)).batch_size == 4
    with pytest.raises(TypeError):
        _as_search_config("lots", repro.Target())


def test_plan_json_header_is_versioned(small_uniform, tmp_path):
    plan = repro.compile(small_uniform,
                         graph=default_shard_graph(small_uniform))
    path = tmp_path / "v.plan.npz"
    plan.save(path)
    with np.load(path, allow_pickle=False) as z:
        header = json.loads(str(z["__plan__"]))
    assert header["format_version"] == 1
    assert header["kind"] == "dense"
    assert header["target"]["backend"] == "jax"


def test_plan_saved_with_interpret_key_loads(tmp_path):
    """Plans saved when Target still carried ``interpret`` load: the key
    is ignored, interpret mode comes from the platform."""
    from repro.api import _atomic_savez
    m = banded_matrix(64, 2, seed=1)
    plan = repro.compile(m, repro.Target(backend="pallas"),
                         graph=default_shard_graph(m))
    p = tmp_path / "old.plan.npz"
    plan.save(p)
    with np.load(p) as z:
        header = json.loads(str(z["__plan__"]))
        arrays = {k: z[k] for k in z.files if k != "__plan__"}
    header["target"]["interpret"] = True
    header.pop("checksum")
    _atomic_savez(p, header, arrays)
    loaded = repro.load_plan(p)
    assert loaded.target == plan.target
    assert loaded.target.runs_interpreted        # the CPU
    x = np.ones(m.n_cols, np.float32)
    np.testing.assert_array_equal(np.asarray(loaded(x)), np.asarray(plan(x)))
