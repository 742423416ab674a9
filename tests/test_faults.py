"""Fault-tolerance tests: hardened search, crash-safe PlanStore,
degraded-mode serving.

Covers the failure model end to end: candidate crash/hang/wrong-result
taxonomy and structure quarantine in the search, atomic checksummed plan
persistence with verify/repair, and the serving engine's backpressure /
deadline / retry / rollback / health machinery. The fault-injection
*benchmark* (benchmarks/fault_inject.py) gates the same behaviors under
load; these tests pin the unit semantics.
"""
import math
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

import repro
from repro.api import PlanIntegrityError, ShardedSpmvPlan, load_plan
from repro.core.matrices import banded_matrix
from repro.core.search import (FAILURE_BUCKETS, SearchConfig, fault_hook,
                               run_search, sleep_checking_deadline)
from repro.design.space import DesignSpace
from repro.ft.manager import FaultToleranceManager
from repro.serve import (MatvecRequest, PlanExecutor, ServeConfig,
                         ServingEngine, SpmvEngine, SwapRejected)
from repro.serve.engine import Request
from repro.serve.sparse_linear import _DEFAULT_GRAPH


@pytest.fixture(scope="module")
def matrix():
    return banded_matrix(64, 4, seed=0)


@pytest.fixture(scope="module")
def plan(matrix):
    return repro.compile(matrix, repro.Target(batch_size=4),
                         graph=_DEFAULT_GRAPH)


def _cfg(**kw):
    base = dict(seed=0, max_structures=3, max_seconds=30, backend="jax",
                coarse_samples=3, timing_repeats=1)
    base.update(kw)
    return SearchConfig(**base)


# ------------------------------ search plane --------------------------------

def test_candidate_crash_is_recorded_not_fatal(matrix):
    calls = {"n": 0}

    def hook(graph, y):
        calls["n"] += 1
        if calls["n"] % 2 == 0:
            raise RuntimeError("injected crash")

    with fault_hook(hook):
        res = run_search(matrix, _cfg())
    assert res.failure_counts.get("crash", 0) >= 1
    assert res.n_failed_candidates >= 1
    # failed candidates live in failed_records with the taxonomy status;
    # records stays successful-only (finite seconds, features present)
    assert all(r.status == "crash" for r in res.failed_records
               if r.status not in ("invalid",))
    assert all(math.isinf(r.seconds) and r.features is None
               for r in res.failed_records)
    assert all(math.isfinite(r.seconds) for r in res.records)
    # the search still produced a working plan
    x = np.ones(matrix.n_cols, np.float32)
    assert np.allclose(np.asarray(res.best_program(x)),
                       matrix.spmv_dense_oracle(x), atol=1e-3)


def test_hanging_candidate_killed_by_deadline(matrix):
    def hook(graph, y):
        time.sleep(60)

    t0 = time.perf_counter()
    with fault_hook(hook):
        res = run_search(matrix, _cfg(candidate_timeout_s=0.3))
    wall = time.perf_counter() - t0
    assert res.failure_counts.get("timeout", 0) >= 1
    assert any(r.status == "timeout" for r in res.failed_records)
    # every candidate hangs, so the wall is n_candidates * timeout at
    # worst — nowhere near the 60s a single un-killed hang would cost
    assert wall < 30, f"deadline did not bound the hang: {wall:.1f}s"
    assert res.fallback   # nothing survived; baseline program substituted


def test_wrong_result_candidates_rejected(matrix):
    with fault_hook(lambda g, y: y + 1.0):
        res = run_search(matrix, _cfg())
    assert res.failure_counts.get("wrong_result", 0) >= 1
    assert res.fallback
    x = np.ones(matrix.n_cols, np.float32)
    assert np.allclose(np.asarray(res.best_program(x)),
                       matrix.spmv_dense_oracle(x), atol=1e-3)


def test_lowering_failure_is_hard_not_invalid(matrix, monkeypatch):
    """A generated program the platform refuses to compile (what Mosaic
    raises as ValueError/NotImplementedError) is a warned hard failure."""
    import sys
    search_mod = sys.modules["repro.core.search"]
    real = search_mod.build_program

    class Refused:
        def lower(self, *args):
            raise NotImplementedError("Only 2D gather is supported")

    def refusing(meta, **kw):
        prog = real(meta, **kw)
        if kw.get("backend") == "pallas":   # the baseline stays jax
            prog.fn = Refused()
        return prog

    monkeypatch.setattr(search_mod, "build_program", refusing)
    with pytest.warns(RuntimeWarning, match="LOWERING"):
        res = run_search(matrix, _cfg(backend="pallas"))
    assert res.failure_counts.get("lowering", 0) >= 1
    assert res.fallback


def test_quarantine_unit(matrix):
    space = DesignSpace(matrix, _cfg(quarantine_after=2))
    assert not space.is_quarantined("S1")
    assert not space.note_failure("S1", "crash", threshold=2)
    assert not space.is_quarantined("S1")     # one strike
    assert space.note_failure("S1", "crash", threshold=2)
    assert space.is_quarantined("S1")         # two strikes: banned
    assert not space.is_quarantined("S2")


def test_quarantine_skips_repeat_offenders(matrix):
    with fault_hook(lambda g, y: (_ for _ in ()).throw(
            RuntimeError("boom"))):
        res = run_search(matrix, _cfg(quarantine_after=1))
    # with every candidate crashing and a 1-strike quarantine, later
    # proposals for the same structure are skipped, not re-evaluated
    assert res.n_quarantined >= 1


def test_fallback_plan_describe_and_roundtrip(matrix, tmp_path):
    with fault_hook(lambda g, y: (_ for _ in ()).throw(
            RuntimeError("boom"))):
        plan = repro.compile(matrix, repro.Target(), _cfg())
    counts = dict(plan.failure_counts)
    assert counts["fallback"] == 1 and counts.get("crash", 0) >= 1
    assert set(counts) <= set(FAILURE_BUCKETS)
    assert "search failures:" in plan.describe()
    # failure accounting survives save/load (the plan outlives the run)
    p = tmp_path / "fb.plan.npz"
    plan.save(p)
    loaded = load_plan(p)
    assert dict(loaded.failure_counts) == counts
    assert "search failures:" in loaded.describe()
    x = np.ones(matrix.n_cols, np.float32)
    assert np.allclose(np.asarray(loaded(x)),
                       matrix.spmv_dense_oracle(x), atol=1e-3)


def test_compile_deadline_s_bounds_search(matrix):
    def hook(graph, y):
        time.sleep(60)

    t0 = time.perf_counter()
    with fault_hook(hook):
        plan = repro.compile(matrix, repro.Target(),
                             _cfg(max_seconds=5.0), deadline_s=5.0)
    wall = time.perf_counter() - t0
    # hard deadline: candidates inherit the time remaining, so even
    # pure-hang candidates cannot push the whole compile far past budget
    assert wall < 20, f"compile(deadline_s=5) took {wall:.1f}s"
    x = np.ones(matrix.n_cols, np.float32)
    assert np.allclose(np.asarray(plan(x)),
                       matrix.spmv_dense_oracle(x), atol=1e-3)


def test_deadline_skips_compile_that_cannot_fit(matrix):
    """Under a hard deadline a candidate whose estimated compile (kernel
    steps x the slowest compile per step so far) exceeds the time left is
    not started — it is a timeout, and nothing is compiled for it."""
    from repro.core.search import AlphaSparseSearch
    s = AlphaSparseSearch(matrix, _cfg(hard_deadline=True))
    s._deadline_at = time.perf_counter() + 5.0
    s._compile_s_per_step = 100.0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        assert s._evaluate(_DEFAULT_GRAPH, "default") == math.inf
    assert s.failure_counts == {"timeout": 1}
    # with time to spare the same candidate compiles and is timed
    s2 = AlphaSparseSearch(matrix, _cfg(hard_deadline=True))
    s2._deadline_at = time.perf_counter() + 60.0
    assert math.isfinite(s2._evaluate(_DEFAULT_GRAPH, "default"))
    assert s2._compile_s_per_step > 0


def test_deadline_overrun_is_recorded(matrix):
    """A search that ends past its hard deadline says by how much; one
    that fits reports zero."""
    res = run_search(matrix, _cfg(max_seconds=30.0, hard_deadline=True))
    assert res.deadline_overrun_s == 0.0

    def hook(graph, y):
        time.sleep(0.2)             # uninterruptible: no checkpoint inside

    with fault_hook(hook), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        late = run_search(matrix, _cfg(max_seconds=0.05, hard_deadline=True,
                                       candidate_timeout_s=None))
    assert late.deadline_overrun_s > 0.0


def test_no_faults_means_no_behavior_change(matrix):
    """The robustness knobs default inert: same candidate walk with and
    without the machinery engaged (golden-trace parity holds).

    use_cost_model=False: the cost-model fine phase picks its refinement
    targets from measured timings, so under machine load two otherwise
    identical runs can diverge there — the parity contract is about the
    timing-independent walk."""
    res_a = run_search(matrix, _cfg(use_cost_model=False))
    res_b = run_search(matrix, _cfg(use_cost_model=False))
    assert [r.structure for r in res_a.records] == \
        [r.structure for r in res_b.records]
    assert not res_a.fallback and res_a.n_quarantined == 0
    hard = {"crash", "oom", "timeout", "wrong_result"}
    assert not hard & set(res_a.failure_counts)


def test_pooled_search_timeout_fires_off_main_thread(matrix):
    """Acceptance: per-candidate timeouts fire inside ThreadPoolExecutor
    searches. A planted hang on a pool thread (where SIGALRM is a no-op)
    is killed by the cooperative deadline and recorded as a `timeout`
    EvalRecord — the pooled search is bounded, not hung."""
    def hook(graph, y):
        sleep_checking_deadline(60.0)

    t0 = time.perf_counter()
    with fault_hook(hook), warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="shard-search") as pool:
            res = pool.submit(run_search, matrix,
                              _cfg(candidate_timeout_s=0.3)).result(120)
    wall = time.perf_counter() - t0
    assert res.failure_counts.get("timeout", 0) >= 1
    assert any(r.status == "timeout" for r in res.failed_records)
    assert wall < 30, f"pool-thread hang was not bounded: {wall:.1f}s"


def test_off_main_deadline_warns_once_about_missing_backstop(matrix,
                                                             monkeypatch):
    """Satellite: arming a deadline off the main thread says so (once per
    process) instead of silently dropping the SIGALRM backstop."""
    import sys
    search_mod = sys.modules["repro.core.search"]
    monkeypatch.setattr(search_mod, "_WARNED_NO_BACKSTOP", False)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with ThreadPoolExecutor(max_workers=1) as pool:
            pool.submit(run_search, matrix,
                        _cfg(candidate_timeout_s=5.0)).result(120)
    msgs = [w for w in caught if "SIGALRM backstop" in str(w.message)]
    assert len(msgs) == 1
    # second pooled search: the process-wide flag suppresses a repeat
    with warnings.catch_warnings(record=True) as caught2:
        warnings.simplefilter("always")
        with ThreadPoolExecutor(max_workers=1) as pool:
            pool.submit(run_search, matrix,
                        _cfg(candidate_timeout_s=5.0)).result(120)
    assert not [w for w in caught2 if "SIGALRM backstop" in str(w.message)]


# ------------------------------- dist plane ---------------------------------

def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _dist_cfg(**kw):
    from repro.dist.search import ShardedSearchConfig
    return ShardedSearchConfig(
        search=SearchConfig(max_seconds=20, max_structures=2,
                            coarse_samples=1, fine_eval_budget=0,
                            timing_repeats=1, use_cost_model=False, seed=7),
        min_nnz_for_search=1, **kw)


def test_shard_search_failure_degrades_to_baseline(matrix):
    """A shard whose search raises gets the baseline program substituted:
    the compile degrades (fallback counted, shard reported failed) but
    the sharded program stays oracle-exact."""
    from repro.dist.search import dist_search, shard_fault_hook

    def crash(shard):
        raise RuntimeError("injected shard crash")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with shard_fault_hook(crash):
            res = dist_search(matrix, _mesh1(), _dist_cfg())
    assert res.failed_shards() == [0]
    rep = res.reports[0]
    assert rep.failed and not rep.searched
    assert rep.failure == "crash" and "injected shard crash" in rep.error
    assert res.failure_counts.get("fallback") == 1
    x = np.ones(matrix.n_cols, np.float32)
    assert np.allclose(np.asarray(res.program(x)),
                       matrix.spmv_dense_oracle(x), atol=1e-3)


def test_sharded_plan_failure_counts_roundtrip(matrix, tmp_path):
    """Aggregated failure_counts land on the ShardedSpmvPlan, survive
    save/load, survive pytree flatten/unflatten, and show in describe()."""
    from repro.dist.search import dist_search, shard_fault_hook

    mesh = _mesh1()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with shard_fault_hook(lambda s: (_ for _ in ()).throw(
                MemoryError("injected shard oom"))):
            res = dist_search(matrix, mesh, _dist_cfg())
    assert res.reports[0].failure == "oom"
    target = repro.Target(mesh=mesh)
    plan = ShardedSpmvPlan.from_program(res.program, target,
                                        search_result=res)
    counts = dict(plan.failure_counts)
    assert counts.get("fallback") == 1
    assert "shard-search failures:" in plan.describe()
    p = tmp_path / "sharded.plan.npz"
    plan.save(p)
    loaded = load_plan(p, mesh=mesh)
    assert dict(loaded.failure_counts) == counts
    leaves, treedef = jax.tree_util.tree_flatten(plan)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.failure_counts == plan.failure_counts
    x = np.ones(matrix.n_cols, np.float32)
    assert np.allclose(np.asarray(loaded(x)),
                       matrix.spmv_dense_oracle(x), atol=1e-3)


def test_ft_component_health():
    ft = FaultToleranceManager()
    assert ft.component_health() == {} and ft.degraded_components() == []
    ft.report_component("dyn-research", healthy=False, error="Traceback ...")
    assert ft.degraded_components() == ["dyn-research"]
    health = ft.component_health()["dyn-research"]
    assert not health.healthy and "Traceback" in health.error
    assert health.reports == 1
    ft.report_component("dyn-research", healthy=True)
    assert ft.degraded_components() == []
    assert ft.component_health()["dyn-research"].reports == 2
    assert ft.component_health()["dyn-research"].error is None


# ------------------------------- store plane --------------------------------

def test_atomic_save_leaves_no_temp_droppings(plan, tmp_path):
    p = tmp_path / "x.plan.npz"
    plan.save(p)
    plan.save(p)          # overwrite is atomic too
    assert [f.name for f in tmp_path.iterdir()] == ["x.plan.npz"]
    assert load_plan(p) is not None


def test_checksum_detects_tampering(plan, tmp_path, matrix):
    p = tmp_path / "x.plan.npz"
    plan.save(p)
    # rewrite with one array perturbed and the original header kept:
    # the zip container is valid, only the content checksum can object
    z = np.load(p)
    arrays = {k: z[k] for k in z.files if k != "__plan__"}
    header = str(z["__plan__"])
    akey = next(k for k in sorted(arrays)
                if arrays[k].dtype == np.float32)
    arrays[akey] = arrays[akey] + 1.0
    with p.open("wb") as f:
        np.savez(f, __plan__=np.str_(header), **arrays)
    with pytest.raises(PlanIntegrityError):
        load_plan(p)


def test_truncated_entry_recompiles_watch_retries_verify_quarantines(
        matrix, plan, tmp_path):
    store = repro.PlanStore(tmp_path)
    target = repro.Target(batch_size=4)
    store.put(matrix, target, None, None, plan)
    path = store._path(store.key(matrix, target))
    watch = store.watch(matrix, target)

    raw = path.read_bytes()
    path.write_bytes(raw[: len(raw) // 2])       # half-written entry

    # get(): a corrupt entry is a warned miss -> caller recompiles
    with pytest.warns(RuntimeWarning, match="unusable"):
        assert store.get(matrix, target) is None
    # watch: poll skips the torn file and keeps the old plan serving
    assert watch.poll() is None
    # verify flags it; repair quarantines entry + sidecar
    report = store.verify()
    assert [k for k, _ in report["corrupt"]] == [store.key(matrix, target)]
    quarantined = store.repair()
    assert quarantined == [store.key(matrix, target)]
    assert not path.exists()
    qdir = tmp_path / "quarantine"
    assert len(list(qdir.glob("*.plan.npz"))) == 1
    assert store.verify() == {"ok": [], "corrupt": []}
    # a fresh put lands atomically and the watch picks it up
    store.put(matrix, target, None, None, plan)
    assert watch.poll() is not None


# ------------------------------- serve plane --------------------------------

def _engine(matrix, plan, **kw):
    ex = PlanExecutor(plan, matrix)
    return ex, SpmvEngine(ex, **kw)


def test_backpressure_and_deadline_responses(matrix, plan):
    ex, eng = _engine(matrix, plan, max_queue=4)
    rng = np.random.default_rng(0)
    reqs = [MatvecRequest(i, rng.standard_normal(matrix.n_cols)
                          .astype(np.float32)) for i in range(10)]
    admitted = [r for r in reqs if eng.enqueue(r)]
    rejected = [r for r in reqs if r.status == "rejected"]
    assert len(admitted) == 4 and len(rejected) == 6
    assert all(r.retry_after_s is not None and r.error for r in rejected)

    expired = MatvecRequest(99, rng.standard_normal(matrix.n_cols)
                            .astype(np.float32), deadline_s=1e-4)
    # one slot freed per drained bucket, so this is admitted after a step
    eng.step()
    assert eng.enqueue(expired)
    time.sleep(0.01)
    stats = eng.run([])
    assert expired.status == "timeout" and expired.error
    assert stats["dropped"] == 0
    assert stats["rejected"] == 6 and stats["timed_out"] == 1
    for r in admitted:
        assert r.status == "ok"
        assert np.allclose(r.y, matrix.spmv_dense_oracle(r.x), atol=1e-4)


def test_retry_recovers_and_health_heals(matrix, plan):
    ex, eng = _engine(matrix, plan, max_retries=2, retry_backoff_s=0.001,
                      heal_after=2)
    orig, calls = ex.execute, {"n": 0}

    def flaky(xs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return orig(xs)

    ex.execute = flaky
    r = MatvecRequest(0, np.ones(matrix.n_cols, np.float32))
    eng.enqueue(r)
    eng.step()
    assert r.status == "ok"                      # retry recovered it
    assert eng.health == "degraded"              # but the engine noticed
    assert eng.recovery_latencies and eng.recovery_latencies[0] > 0
    ex.execute = orig
    for i in range(2):                           # heal_after clean steps
        rr = MatvecRequest(1 + i, np.ones(matrix.n_cols, np.float32))
        eng.enqueue(rr)
        eng.step()
    assert eng.health == "healthy"


def test_exhausted_retries_fail_explicitly(matrix, plan):
    ex, eng = _engine(matrix, plan, max_retries=1, retry_backoff_s=0.001)

    def dead(xs):
        raise RuntimeError("permanent")

    ex.execute = dead
    r = MatvecRequest(0, np.ones(matrix.n_cols, np.float32))
    eng.enqueue(r)
    out = eng.step()
    assert r in out
    assert r.status == "failed" and "permanent" in r.error
    assert eng.health == "failed"
    assert eng.failed == 1


def test_swap_rollback_on_wrong_plan(matrix, plan):
    ex = PlanExecutor(plan, matrix)
    ex.warmup()
    bad = repro.compile(matrix, repro.Target(batch_size=4),
                        graph=_DEFAULT_GRAPH)
    bad.fmt = {k: (v + 1.0 if str(v.dtype) == "float32" else v)
               for k, v in bad.fmt.items()}
    with pytest.raises(SwapRejected):
        ex.swap_plan(bad)
    assert ex.rejected_swaps == 1 and ex.swap_count == 0
    # the old plan is still the serving reference and still correct
    x = np.ones((1, matrix.n_cols), np.float32)
    assert np.allclose(np.asarray(ex.execute(x))[0],
                       matrix.spmv_dense_oracle(x[0]), atol=1e-4)
    # a correct plan still swaps
    good = repro.compile(matrix, repro.Target(batch_size=4),
                        graph=_DEFAULT_GRAPH)
    ex.swap_plan(good)
    assert ex.swap_count == 1


def test_ft_heartbeats_flag_stuck_steps(matrix, plan):
    ft = FaultToleranceManager()
    ex, eng = _engine(matrix, plan, ft=ft)
    rng = np.random.default_rng(0)
    # build a step-time baseline, then one stuck step via a slow execute
    for i in range(12):
        eng.enqueue(MatvecRequest(i, rng.standard_normal(matrix.n_cols)
                                  .astype(np.float32)))
        eng.step()
    orig = ex.execute

    def slow(xs):
        time.sleep(0.25)
        return orig(xs)

    ex.execute = slow
    eng.enqueue(MatvecRequest(99, rng.standard_normal(matrix.n_cols)
                              .astype(np.float32)))
    eng.step()
    assert eng.stuck_steps >= 1
    assert eng.health == "degraded"
    assert ft.stragglers()


def test_prefill_failure_marks_request_and_frees_slot():
    from repro.configs import get_config
    cfg = get_config("granite-3-2b").reduced()
    eng = ServingEngine(cfg, ServeConfig(max_batch=2, max_seq=64,
                                         max_new_tokens=4))
    orig = eng.executor.decode

    def boom(*a, **kw):
        raise RuntimeError("injected prefill failure")

    eng.executor.decode = boom
    req = Request(0, np.array([1, 2, 3]))
    with pytest.raises(RuntimeError, match="injected prefill"):
        eng.submit(req)
    # the slot rolled back AND the request closed out with the error
    assert req.failed and "injected prefill" in req.error
    assert req.t_done is not None and not eng.active
    assert sorted(eng.free) == [0, 1]
    eng.executor.decode = orig
    ok = Request(1, np.array([1, 2, 3]))
    assert eng.submit(ok)
    eng.run([])
    assert ok.done and not ok.failed


def test_serving_run_guards_configurable():
    from repro.configs import get_config
    cfg = get_config("granite-3-2b").reduced()
    eng = ServingEngine(cfg, ServeConfig(max_batch=1, max_seq=64,
                                         max_new_tokens=8, max_steps=2))
    with pytest.raises(RuntimeError, match="did not terminate within "
                                           "2 steps"):
        eng.run([Request(0, np.array([1, 2, 3]))])
    eng2 = ServingEngine(cfg, ServeConfig(max_batch=1, max_seq=64,
                                          max_new_tokens=8,
                                          max_wall_s=0.0))
    with pytest.raises(RuntimeError, match="did not terminate within"):
        eng2.run([Request(0, np.array([1, 2, 3]))])
