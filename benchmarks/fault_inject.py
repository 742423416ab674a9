"""Fault-injection harness: seeded failures across all three planes
-> BENCH_faults.json.

The robustness proof for the fault-tolerant compile & serve layer. Three
phases, each injecting the failures the layer claims to survive:

* **Store plane** — corrupt PlanStore entries (a truncated npz and a
  valid-zip/wrong-checksum tamper): ``verify()`` finds both, ``repair()``
  quarantines both, ``get`` on a corrupt key recompiles instead of
  serving garbage.
* **Search plane** — a ``fault_hook`` makes candidates crash, hang past
  the per-candidate deadline, and return wrong results mid-``compile()``:
  the search records every one as a failed EvalRecord in the taxonomy,
  finishes inside ``deadline_s``, and still returns an oracle-exact plan.
* **Serve plane** — under load: transient executor exceptions
  (retry-with-backoff recovers), a simulated mid-swap kill (half-written
  serving entry — the watch skips it, the old plan keeps serving), a
  wrong-result plan published to the store (admission spot-check rejects
  the swap), then a good plan (hot-swaps cleanly). Backpressure rejections
  and deadline timeouts get explicit error responses.

Fleet-grade fault domains (three more phases):

* **Sweep plane** — a corpus sweep subprocess is SIGKILLed mid-run;
  ``run_sweep(resume=True)`` completes the corpus from the fsync'd
  journal with zero duplicate records, re-sweeping only the entries
  that never journaled (at most the in-flight one plus the unswept
  tail).
* **Dist plane** — a 4-shard compile with one shard forced to crash,
  a hanging candidate on another (killed by the *cooperative* deadline
  on a pool thread), and a wrong-result candidate on a third: the
  compile still returns an oracle-exact sharded plan, the crashed shard
  on its baseline, ``failure_counts`` aggregated onto the plan.
* **Dyn plane** — the background re-search dies (twice) under serving
  load: the failure is observable (``stats()["last_error"]``), the
  watchdog restarts it with backoff, and the third attempt lands a
  hot-swap through the normal admission gate.

Gates: zero dropped requests, oracle-exact outputs for every completed
request, bounded recovery latency, >=1 rejected and >=1 successful swap.

  PYTHONPATH=src python benchmarks/fault_inject.py --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import repro
from repro.api import load_plan
from repro.core.search import fault_hook
from repro.ft.manager import FaultToleranceManager
from repro.serve import MatvecRequest, PlanExecutor, SpmvEngine
from repro.serve.sparse_linear import _DEFAULT_GRAPH

try:                      # runnable as module (-m benchmarks.fault_inject) ...
    from .common import scaled_families, smoke_families
except ImportError:       # ... or as a plain script from the repo root
    from common import scaled_families, smoke_families

WALL_GUARD_S = 300
ORACLE_RTOL = 1e-4
RECOVERY_BOUND_S = 10.0


def _tamper(path: Path) -> None:
    """Valid-zip/wrong-checksum corruption: rewrite the npz with one
    float array perturbed but the original (now stale) header kept, so
    only the content checksum can catch it."""
    z = np.load(path)
    arrays = {k: z[k] for k in z.files if k != "__plan__"}
    header = str(z["__plan__"])
    akey = next(k for k in sorted(arrays)
                if arrays[k].dtype == np.float32)
    arrays[akey] = arrays[akey] + 1.0
    with path.open("wb") as f:
        np.savez(f, __plan__=np.str_(header), **arrays)


def phase_store(m, target) -> dict:
    """Corrupt entries are found, quarantined, and never served."""
    with tempfile.TemporaryDirectory() as tmp:
        store = repro.PlanStore(tmp)
        budgets = [None, repro.SearchConfig(max_seconds=1), 2.0]
        for b in budgets:
            plan = repro.compile(m, target, graph=_DEFAULT_GRAPH)
            # keyed by budget (graph=None), so the three entries are
            # distinct files
            store.put(m, target, b, None, plan)
        keys = [store.key(m, target, b) for b in budgets]
        # corruption 1: truncation (a crashed non-atomic writer would
        # leave this; our atomic save can't, so it is injected directly)
        p0 = store._path(keys[0])
        p0.write_bytes(p0.read_bytes()[: p0.stat().st_size // 2])
        # corruption 2: silent bitrot — container intact, checksum stale
        _tamper(store._path(keys[1]))

        report = store.verify()
        corrupt_keys = {k for k, _ in report["corrupt"]}
        assert corrupt_keys == set(keys[:2]), (
            f"verify found {corrupt_keys}, expected {set(keys[:2])}")
        assert keys[2] in report["ok"]
        # a corrupt entry is a miss, not an error — get() recompiles
        assert store.get(m, target, budgets[0]) is None
        quarantined = store.repair()
        assert set(quarantined) == set(keys[:2])
        assert store.verify()["corrupt"] == []
        qdir = Path(tmp) / "quarantine"
        assert len(list(qdir.glob("*.plan.npz"))) == 2
        # the healthy entry still round-trips
        good = load_plan(store._path(keys[2]))
        x = np.ones(m.n_cols, np.float32)
        assert np.allclose(np.asarray(good(x)),
                           m.spmv_dense_oracle(x), atol=1e-3)
    return {"entries_corrupted": 2, "entries_quarantined": len(quarantined),
            "verify_clean_after_repair": True}


def phase_search(m, target, deadline_s: float) -> dict:
    """Crash/hang/wrong-result candidates during compile(): every fault
    becomes a failed EvalRecord, the search meets its deadline, and the
    returned plan is oracle-exact."""
    calls = {"n": 0}

    def hook(graph, y):
        calls["n"] += 1
        if calls["n"] == 2:
            time.sleep(deadline_s + 30)          # hang: deadline must kill
        if calls["n"] == 3:
            raise RuntimeError("injected candidate crash")
        if calls["n"] == 4:
            return y + 1.0                        # wrong result
        return None

    budget = repro.SearchConfig(max_seconds=deadline_s, max_structures=3,
                                coarse_samples=3, timing_repeats=1,
                                candidate_timeout_s=min(2.0, deadline_s / 4),
                                seed=0)
    t0 = time.perf_counter()
    with fault_hook(hook):
        plan = repro.compile(m, target, budget, deadline_s=deadline_s)
    wall = time.perf_counter() - t0

    counts = dict(plan.failure_counts or ())
    res = plan.search_result
    assert counts.get("timeout", 0) >= 1, f"hang not recorded: {counts}"
    assert counts.get("crash", 0) >= 1, f"crash not recorded: {counts}"
    assert counts.get("wrong_result", 0) >= 1, \
        f"wrong result not recorded: {counts}"
    n_failed = res.n_failed_candidates
    assert n_failed >= 3
    assert len(res.failed_records) == n_failed
    assert all(r.seconds == float("inf") for r in res.failed_records)
    # the hang may only be killed once its per-candidate deadline expires,
    # so allow one candidate-timeout of slack past the search deadline
    slack = (budget.candidate_timeout_s or 0) + 5.0
    assert wall < deadline_s + slack, \
        f"search wall {wall:.1f}s blew deadline {deadline_s}s"
    x = np.ones(m.n_cols, np.float32)
    err = float(np.abs(np.asarray(plan(x))
                       - m.spmv_dense_oracle(x)).max())
    scale = float(np.abs(m.spmv_dense_oracle(x)).max()) + 1e-9
    assert err / scale < 1e-3, f"compiled plan wrong under faults: {err}"
    return {"n_failed_candidates": n_failed, "failure_counts": counts,
            "fallback": res.fallback, "wall_s": wall,
            "deadline_s": deadline_s}


def phase_serve(m, target, n_requests: int) -> dict:
    """Executor exceptions, a mid-swap kill, a rejected swap, and a clean
    swap — all under load; zero drops and oracle-exact completions."""
    dense = m.to_dense()
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        store = repro.PlanStore(tmp)
        plan_a = repro.compile(m, target, graph=_DEFAULT_GRAPH)
        store.put(m, target, None, None, plan_a)
        serving_path = store._path(store.key(m, target))
        ex = PlanExecutor(plan_a, m, watch=store.watch(m, target))
        eng = SpmvEngine(ex, max_queue=max(n_requests // 2, 8),
                         max_retries=3, retry_backoff_s=0.01,
                         heal_after=2, ft=FaultToleranceManager())
        ex.warmup()

        # transient executor exceptions mid-request: calls 2 and 5 raise
        orig_execute, calls = ex.execute, {"n": 0}

        def flaky_execute(xs):
            calls["n"] += 1
            if calls["n"] in (2, 5):
                raise RuntimeError(f"injected executor fault "
                                   f"#{calls['n']}")
            return orig_execute(xs)

        ex.execute = flaky_execute

        xs = rng.standard_normal((n_requests, m.n_cols)).astype(np.float32)
        reqs = [MatvecRequest(i, xs[i]) for i in range(n_requests)]
        # two doomed requests prove timeout responses are explicit
        doomed = [MatvecRequest(10_000 + i,
                                rng.standard_normal(m.n_cols)
                                .astype(np.float32),
                                deadline_s=1e-4) for i in range(2)]

        for r in doomed:                          # before the burst, so
            eng.enqueue(r)                        # backpressure can't eat them
        rejected = [r for r in reqs if not eng.enqueue(r)]
        accepted = [r for r in reqs if r.status != "rejected"]
        time.sleep(0.01)                          # let the doomed expire

        plan_b = repro.compile(m, target, graph=_DEFAULT_GRAPH)
        bad_plan = repro.compile(m, target, graph=_DEFAULT_GRAPH)
        bad_plan.fmt = {k: (v + 1.0 if str(v.dtype) == "float32" else v)
                        for k, v in bad_plan.fmt.items()}
        events = {"killed": False, "bad": False, "good": False}
        steps = 0
        while eng.queue:
            eng.step()
            steps += 1
            if steps == 1 and not events["killed"]:
                # mid-swap kill: a writer dies halfway through a
                # non-atomic publish; the watch must skip the torn file
                raw = serving_path.read_bytes()
                serving_path.write_bytes(raw[: len(raw) // 2])
                events["killed"] = True
            elif steps == 2 and not events["bad"]:
                # wrong-result plan published: admission must reject it
                store.put(m, target, None, None, bad_plan)
                events["bad"] = True
            elif steps == 3 and not events["good"]:
                store.put(m, target, None, None, plan_b)
                events["good"] = True
            if steps > 10_000:
                raise RuntimeError("serve drain did not terminate")
        # any swap event still pending (tiny loads drain fast): replay
        # the remaining publishes with a trailing request each, so every
        # injection actually lands under serving
        for key, action in (("bad", lambda: store.put(m, target, None,
                                                      None, bad_plan)),
                            ("good", lambda: store.put(m, target, None,
                                                       None, plan_b))):
            if not events[key]:
                action()
                events[key] = True
            tail = MatvecRequest(20_000, xs[0])
            eng.enqueue(tail)
            accepted.append(tail)
            while eng.queue:
                eng.step()

        ex.execute = orig_execute

    ok = [r for r in accepted if r.status == "ok"]
    max_err = 0.0
    for r in ok:
        want = dense @ r.x
        scale = float(np.abs(want).max()) + 1e-9
        max_err = max(max_err, float(np.abs(r.y - want).max()) / scale)
    dropped = sum(r.status == "pending" for r in accepted + doomed)

    assert dropped == 0, f"{dropped} accepted requests dropped"
    assert max_err < ORACLE_RTOL, f"oracle mismatch {max_err:.2e}"
    assert all(r.status == "timeout" and r.error for r in doomed), \
        "expired requests lack explicit timeout responses"
    assert all(r.error and r.retry_after_s is not None for r in rejected), \
        "backpressure rejections lack retry-after responses"
    assert ex.rejected_swaps >= 1, "wrong-result swap was not rejected"
    assert eng.hot_swaps >= 1, "good plan never hot-swapped under load"
    assert eng.recovery_latencies, "injected executor faults never retried"
    recovery_max = max(eng.recovery_latencies)
    assert recovery_max < RECOVERY_BOUND_S, \
        f"recovery latency {recovery_max:.2f}s exceeds bound"
    assert eng.failed == 0, "transient faults were not recovered by retry"
    return {"accepted": len(accepted), "rejected": len(rejected),
            "timed_out": eng.timed_out, "completed_ok": len(ok),
            "requests_dropped": dropped, "oracle_max_rel_err": max_err,
            "recovery_latency_max_s": recovery_max,
            "rejected_swaps": ex.rejected_swaps,
            "hot_swaps": eng.hot_swaps, "health": eng.health}


# ------------------------- fleet fault domains ------------------------------

def _child_env() -> dict:
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return env


# the sweep child and the resuming parent must use the same budget, or
# the PlanStore keys diverge and the resume re-searches store hits
_SWEEP_BUDGET_KW = dict(max_seconds=5.0, max_structures=2, coarse_samples=1,
                        fine_eval_budget=0, timing_repeats=1,
                        use_cost_model=False, seed=0)

SWEEP_SCRIPT = r"""
import sys
import repro
from repro.core.search import SearchConfig
from repro.corpus.datasets import synthetic_corpus
from repro.corpus.sweep import run_sweep
budget = SearchConfig(max_seconds=5.0, max_structures=2, coarse_samples=1,
                      fine_eval_budget=0, timing_repeats=1,
                      use_cost_model=False, seed=0)
run_sweep(synthetic_corpus("smoke")[:4], repro.PlanStore(sys.argv[1]),
          budget=budget)
"""


def phase_sweep() -> dict:
    """Driver kill + resume: SIGKILL a sweep subprocess once it has
    journaled some (not all) entries; ``resume=True`` completes the
    corpus with zero duplicate records, re-sweeping only what never
    journaled."""
    from repro.corpus.datasets import synthetic_corpus
    from repro.corpus.sweep import RECORDS_FILENAME, load_records, run_sweep

    from repro.runtime import refuse_if_chip_held

    refuse_if_chip_held("the sweep phase")
    entries = synthetic_corpus("smoke")[:4]
    with tempfile.TemporaryDirectory() as tmp:
        journal = Path(tmp) / RECORDS_FILENAME
        proc = subprocess.Popen([sys.executable, "-c", SWEEP_SCRIPT, tmp],
                                env=_child_env(),
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        deadline = time.monotonic() + 240.0
        try:
            while time.monotonic() < deadline:
                if journal.is_file() and journal.read_text().count("\n") >= 2:
                    break
                if proc.poll() is not None:
                    raise RuntimeError(
                        "sweep child exited before it could be killed")
                time.sleep(0.05)
            else:
                raise RuntimeError("sweep child never journaled 2 entries")
        finally:
            proc.kill()                    # SIGKILL: no cleanup handlers run
            proc.wait()

        before = load_records(journal, warn=False)
        n_before = len(before)
        assert 1 <= n_before < len(entries), \
            f"kill landed outside the sweep window ({n_before} journaled)"

        budget = repro.SearchConfig(**_SWEEP_BUDGET_KW)
        resumed = run_sweep(entries, repro.PlanStore(tmp), budget=budget,
                            resume=True)
        after = load_records(journal)
        fps = [r.fingerprint for r in after]
        n_dupes = len(fps) - len(set(fps))
        assert len(after) == len(entries), \
            f"resume left {len(after)} records for {len(entries)} entries"
        assert n_dupes == 0, f"{n_dupes} duplicate journal records"
        assert len(resumed) == len(entries) - n_before, \
            (f"resume re-swept {len(resumed)} entries; expected only the "
             f"{len(entries) - n_before} unjournaled ones")
        assert all(r.error is None for r in after), \
            [r.error for r in after if r.error]
    return {"entries": len(entries), "journaled_before_kill": n_before,
            "resumed": len(resumed), "duplicate_records": n_dupes}


DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import warnings
import numpy as np
import jax
import repro
from repro.api import ShardedSpmvPlan
from repro.core.matrices import powerlaw_matrix
from repro.core.search import (SearchConfig, current_search_matrix,
                               fault_hook, sleep_checking_deadline)
from repro.dist.search import (ShardedSearchConfig, dist_search,
                               shard_fault_hook)
from repro.dist.spmv import partition_matrix

assert len(jax.devices()) == 4
mesh = jax.make_mesh((4,), ("data",))
m = powerlaw_matrix(320, 300, 6.0, 1.0, seed=2)
cfg = ShardedSearchConfig(
    search=SearchConfig(max_seconds=30, max_structures=2, coarse_samples=1,
                        fine_eval_budget=0, timing_repeats=1,
                        use_cost_model=False, candidate_timeout_s=2.0,
                        seed=7),
    min_nnz_for_search=1)
shards = partition_matrix(m, 4, mode=cfg.mode, balance=cfg.balance)
hang_nnz = shards[2].matrix.nnz
wrong_nnz = shards[3].matrix.nnz
state = {"hung": False, "wronged": False}


def crash_hook(shard):           # whole-shard fault domain: shard 1 dies
    if shard.index == 1:
        raise RuntimeError("injected shard crash")


def candidate_hook(graph, y):
    cur = current_search_matrix()
    if cur is None:
        return None
    if cur.nnz == hang_nnz and not state["hung"]:
        state["hung"] = True
        # a hang on a *pool thread*: only the cooperative deadline can
        # kill this (SIGALRM is main-thread-only)
        sleep_checking_deadline(120.0)
    if cur.nnz == wrong_nnz and not state["wronged"]:
        state["wronged"] = True
        return y + 1.0
    return None


with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    with shard_fault_hook(crash_hook), fault_hook(candidate_hook):
        res = dist_search(m, mesh, cfg)

plan = ShardedSpmvPlan.from_program(res.program, repro.Target(mesh=mesh),
                                    search_result=res)
x = np.random.default_rng(0).standard_normal(m.n_cols).astype(np.float32)
oracle = m.spmv_dense_oracle(x)
scale = float(np.abs(oracle).max()) + 1e-30
err = float(np.abs(np.asarray(plan(x)) - oracle).max() / scale)
print(json.dumps({
    "err": err,
    "failed_shards": res.failed_shards(),
    "failure_counts": res.failure_counts,
    "plan_failure_counts": list(plan.failure_counts or ()),
    "injected": state,
}))
"""


def phase_dist() -> dict:
    """Per-shard crash/hang/wrong-result under a real 4-fake-device mesh
    (subprocess): the compile degrades to the baseline on the crashed
    shard, the pooled hang is killed by the cooperative deadline, and the
    sharded plan stays oracle-exact with failure_counts aggregated. The
    child's mesh is four host devices, so it runs on the CPU and never
    needs the chip this process may hold."""
    env = dict(_child_env(), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", DIST_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=WALL_GUARD_S)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    counts = out["failure_counts"]
    assert out["err"] < 1e-3, \
        f"sharded plan wrong under shard faults: {out['err']:.2e}"
    assert out["failed_shards"] == [1], out["failed_shards"]
    assert counts.get("fallback", 0) >= 1, counts
    assert counts.get("timeout", 0) >= 1, \
        f"pooled hang not killed by the cooperative deadline: {counts}"
    assert counts.get("wrong_result", 0) >= 1, counts
    assert out["plan_failure_counts"], "failure_counts lost on the plan"
    return {"oracle_rel_err": out["err"],
            "failed_shards": out["failed_shards"],
            "failure_counts": counts}


def phase_dyn(n_requests: int) -> dict:
    """Background re-search dies twice under serving load: observable in
    stats()['last_error'], watchdog-restarted with backoff, third attempt
    lands and hot-swaps through the admission gate."""
    import repro.api as api_mod
    from repro.core.matrices import SparseMatrix, powerlaw_matrix
    from repro.dyn import DynamicSparsityManager, PatternDelta
    from repro.train.dynamic import capacity_graph

    m = powerlaw_matrix(96, 96, 12.0, 1.2, seed=3)
    with tempfile.TemporaryDirectory() as tmp:
        store = repro.PlanStore(tmp)
        plan = repro.compile(m, repro.Target(), graph=capacity_graph())
        store.put(m, plan.target, None, None, plan)
        watch = store.watch(m, plan.target)
        watch.poll()                       # arm: birth plan already seen
        ft = FaultToleranceManager()
        ex = PlanExecutor(plan, matrix=m, watch=watch)
        mgr = DynamicSparsityManager(
            m, plan, executor=ex, store=store, ft=ft,
            research_budget=repro.SearchConfig(max_seconds=2,
                                               max_structures=2),
            research_deadline_s=8.0, max_research_strikes=5,
            research_backoff_s=0.05)
        real_compile = api_mod.compile
        deaths = {"n": 0}

        def dying_compile(*a, **kw):
            if deaths["n"] < 2:
                deaths["n"] += 1
                raise RuntimeError(
                    f"injected background research death #{deaths['n']}")
            return real_compile(*a, **kw)

        api_mod.compile = dying_compile
        try:
            # drop ~35% of nnz: in-capacity (pure removal) but past the
            # DriftPolicy fold-change -> update + background re-search
            rng = np.random.default_rng(0)
            keep = np.ones(m.nnz, bool)
            keep[rng.choice(m.nnz, int(m.nnz * 0.35), replace=False)] = False
            m1 = SparseMatrix(m.n_rows, m.n_cols,
                              np.asarray(m.rows)[keep],
                              np.asarray(m.cols)[keep],
                              np.asarray(m.vals)[keep]).canonical()
            out = mgr.apply(PatternDelta.from_matrices(m, m1))
            assert out["action"] == "update+research", out

            rng2 = np.random.default_rng(1)
            xs = rng2.standard_normal((n_requests, m.n_cols)) \
                     .astype(np.float32)
            dense1 = m1.to_dense()
            detected = restarted = swapped = False
            served = 0
            max_err = 0.0
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                x = xs[served % n_requests]
                y = ex.execute(x[None, :])[0]     # serving load
                want = dense1 @ x
                scale = float(np.abs(want).max()) + 1e-9
                max_err = max(max_err,
                              float(np.abs(y - want).max()) / scale)
                served += 1
                # maybe_reload pumps the attached watchdog monitor
                swapped = ex.maybe_reload() or swapped
                st = mgr.stats()
                detected = detected or bool(st["last_error"])
                restarted = restarted or st["watchdog_restarts"] >= 1
                mgr.poll()
                if swapped and mgr.researches_landed >= 1:
                    break
                time.sleep(0.02)
        finally:
            api_mod.compile = real_compile
            mgr.quiesce(timeout=120.0)
        st = mgr.stats()

    assert detected, "background research death was never observable"
    assert restarted, "watchdog never restarted the dead research"
    assert deaths["n"] == 2, f"injector fired {deaths['n']} times"
    assert st["researches_failed"] >= 2
    assert st["researches_landed"] >= 1, "restarted research never landed"
    assert not st["research_dead"], "watchdog struck out prematurely"
    assert swapped and ex.swap_count >= 1, \
        "landed research never hot-swapped under load"
    assert max_err < ORACLE_RTOL, \
        f"serving went wrong during research churn: {max_err:.2e}"
    return {"requests_served": served, "oracle_max_rel_err": max_err,
            "research_deaths": deaths["n"],
            "watchdog_restarts": st["watchdog_restarts"],
            "researches_landed": st["researches_landed"],
            "hot_swaps": ex.swap_count,
            "last_error_seen": detected}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny matrix, short deadlines (the CI config)")
    ap.add_argument("--out", default=None, help="output json path")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    if args.smoke:
        m = smoke_families()["powerlaw"]
        deadline_s, n_requests = 30.0, 64
    else:
        m = scaled_families(1024)["powerlaw"]
        deadline_s, n_requests = 60.0, 256
    target = repro.Target(batch_size=8)

    # first: its child needs the device, which this process must not hold
    sweep_stats = phase_sweep()
    print(f"sweep:  {sweep_stats}", flush=True)
    store_stats = phase_store(m, target)
    print(f"store:  {store_stats}", flush=True)
    search_stats = phase_search(m, target, deadline_s)
    print(f"search: {search_stats}", flush=True)
    serve_stats = phase_serve(m, target, n_requests)
    print(f"serve:  {serve_stats}", flush=True)
    dist_stats = phase_dist()
    print(f"dist:   {dist_stats}", flush=True)
    dyn_stats = phase_dyn(n_requests)
    print(f"dyn:    {dyn_stats}", flush=True)

    wall = time.perf_counter() - t_start
    payload = {
        "matrix": {"n_rows": m.n_rows, "n_cols": m.n_cols, "nnz": m.nnz},
        "store": store_stats, "search": search_stats, "serve": serve_stats,
        "sweep": sweep_stats, "dist": dist_stats, "dyn": dyn_stats,
        # headline keys (summarize.py lifts these)
        "store_entries_quarantined": store_stats["entries_quarantined"],
        "n_failed_candidates": search_stats["n_failed_candidates"],
        "requests_dropped": serve_stats["requests_dropped"],
        "recovery_latency_max_s": serve_stats["recovery_latency_max_s"],
        "rejected_swaps": serve_stats["rejected_swaps"],
        "hot_swaps": serve_stats["hot_swaps"],
        "sweep_duplicate_records": sweep_stats["duplicate_records"],
        "sweep_resumed_entries": sweep_stats["resumed"],
        "dist_failed_shards": dist_stats["failed_shards"],
        "dist_oracle_rel_err": dist_stats["oracle_rel_err"],
        "dyn_watchdog_restarts": dyn_stats["watchdog_restarts"],
        "dyn_hot_swaps": dyn_stats["hot_swaps"],
        "wall_seconds": wall,
    }
    out = Path(args.out) if args.out else \
        Path(__file__).resolve().parent.parent / "BENCH_faults.json"
    out.write_text(json.dumps(payload, indent=1))
    print(f"all fault gates passed in {wall:.1f}s -> {out}")
    assert wall < WALL_GUARD_S, f"wall {wall:.0f}s exceeded {WALL_GUARD_S}s"
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
