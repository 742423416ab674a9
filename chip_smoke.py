"""Smoke test of the SpMV compiler's main path on a TPU: compile -> plan -> serve.

    python chip_smoke.py              # one chip: stencil + powerlaw, full size
    python chip_smoke.py --chips 4    # four chips: row/col sharded powerlaw

One process runs every phase; each phase passes or raises
``SmokeFailure`` and the script exits non-zero. The matrices are made from
seeds by the repo's own generators:

* ``stencil`` — ``banded_matrix(1_048_576, 13)``: a 27-point-stencil row
  length at the row count of HPCG's default local grid (104^3 ~ 1.12M
  rows); ~28.3M nnz. It drives the ELL family.
* ``powerlaw`` — ``powerlaw_matrix(1_048_576, 1_048_576, 16.0, 1.0)``:
  skewed rows. It drives the seg family.

One chip: for each matrix, ``repro.compile`` searches Pallas designs for
SpMV (B=1) and SpMM (B=8) under a small budget; the run fails if the
search fell back to the baseline program, if a candidate failed to lower
or compile, or if the plan runs anything but Mosaic-lowered Pallas
kernels. Each plan is checked against the float64 reference on a
device-resident x, the SpMV plans answer requests through
``PlanExecutor`` + ``SpmvEngine``, and each plan's per-call time is
printed (median of several calls ending in ``block_until_ready``).

``--chips 4`` runs only the sharded path: ``powerlaw`` compiled for a
4-device ``("data",)`` mesh in row and col partition, each compared with
the float64 reference and with the one-chip plan.

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
when set, else to ``<checkout>/.jax_cache``. The last line of standard
output is the JSON result ``{"ok": true, "device": {...}}``; it is printed
only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

N_ROWS = 1_048_576
BATCH = 8
# the search's own correctness tolerance, relative to max |y|
TOL = 1e-3
SERVE_WAVES = (1, 2, 3, 4, 6, 8, 8)      # 32 requests over buckets 1..8


class SmokeFailure(RuntimeError):
    """A phase saw the main path misbehave."""


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"{time.perf_counter() - _T0:8.1f}s {msg}", flush=True)


MATRICES = ("stencil", "powerlaw")


def make_matrix(name: str, n_rows: int = N_ROWS):
    from repro.core.matrices import banded_matrix, powerlaw_matrix
    if name == "stencil":
        return banded_matrix(n_rows, 13, seed=0)
    return powerlaw_matrix(n_rows, n_rows, 16.0, 1.0, seed=1)


def search_budget(max_seconds: float):
    """A small search: the seed formats plus one searched structure."""
    from repro.core.search import SearchConfig
    return SearchConfig(max_seconds=max_seconds, max_structures=1,
                        coarse_samples=1, fine_eval_budget=0,
                        timing_repeats=2, use_cost_model=False)


def _rhs(n_cols: int, batch: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    shape = (n_cols,) if batch <= 1 else (n_cols, batch)
    return rng.standard_normal(shape).astype(np.float32)


def _rel_err(y, ref: np.ndarray) -> float:
    y = np.asarray(y, np.float64)
    if y.shape != ref.shape:
        raise SmokeFailure(f"output shape {y.shape} != reference {ref.shape}")
    if not np.isfinite(y).all():
        raise SmokeFailure("output holds non-finite values")
    return float(np.abs(y - ref).max() / (np.abs(ref).max() + 1e-30))


def _reference(m, x: np.ndarray) -> np.ndarray:
    return m.spmv_dense_oracle(x) if x.ndim == 1 else m.spmm_dense_oracle(x)


# --------------------------------- phases -----------------------------------

def phase_device(chips: int = 1) -> dict:
    """The device as JAX reports it; fails unless it is a TPU."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] {dev['kind']} x{dev['count']} ({dev['platform']}), "
        f"jax {jax.__version__}")
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX's default device is "
                           f"{dev['platform']!r}")
    if dev["count"] < chips:
        raise SmokeFailure(f"{chips} chips asked for, {dev['count']} found")
    return dev


def _check_kernels(plan, batch: int, platform: str) -> None:
    """The plan runs Pallas kernels, lowered by Mosaic on a TPU."""
    import jax
    import jax.numpy as jnp
    if plan.target.backend != "pallas":
        raise SmokeFailure(f"plan backend is {plan.target.backend!r}")
    if platform != "tpu":
        return
    if plan.target.runs_interpreted:
        raise SmokeFailure("plan would run the Pallas interpreter on a TPU")
    shape = (plan.n_cols,) if batch <= 1 else (plan.n_cols, batch)
    text = (jax.jit(lambda p, x: p(x))
            .lower(plan, jax.ShapeDtypeStruct(shape, jnp.float32))
            .compile().as_text())
    if "tpu_custom_call" not in text:
        raise SmokeFailure("compiled plan holds no Mosaic kernel")


def phase_compile(name: str, m, batch: int, budget, deadline_s: float,
                  platform: str):
    """Search a Pallas plan; fail on fallback or a candidate that failed
    to lower or compile."""
    import repro
    t0 = time.perf_counter()
    plan = repro.compile(m, repro.Target(backend="pallas", batch_size=batch),
                         budget=budget, deadline_s=deadline_s)
    wall = time.perf_counter() - t0
    res = plan.search_result
    log(f"[compile] {name} B={batch}: {wall:.1f}s (deadline {deadline_s:g}s, "
        f"overrun {res.deadline_overrun_s:.1f}s), {res.n_evaluations} "
        f"candidates, failures {res.failure_counts}, "
        f"graph {plan.graph.label()}")
    log(plan.describe())
    if res.fallback:
        raise SmokeFailure(f"{name} B={batch}: every candidate failed and "
                           f"the search fell back ({res.failure_counts})")
    bad = {k: v for k, v in res.failure_counts.items()
           if k in ("lowering", "oom")}
    if bad:
        raise SmokeFailure(f"{name} B={batch}: candidates failed to lower "
                           f"or compile: {bad}")
    _check_kernels(plan, batch, platform)
    return plan


def phase_correctness(name: str, m, plan, batch: int, seed: int = 0) -> float:
    """The plan on a device-resident x against the float64 reference."""
    import jax
    x = _rhs(m.n_cols, batch, seed)
    y = plan(jax.device_put(x))
    err = _rel_err(y, _reference(m, x))
    log(f"[correct] {name} B={batch}: rel err {err:.3e} (limit {TOL:g})")
    if err > TOL:
        raise SmokeFailure(f"{name} B={batch}: rel err {err:.3e} > {TOL:g}")
    return err


def phase_serve(name: str, m, plan, waves=SERVE_WAVES, seed: int = 1) -> dict:
    """Requests through PlanExecutor + SpmvEngine, in waves sized to hit
    every decode bucket; all must end "ok" with no retry."""
    from repro.serve import MatvecRequest, PlanExecutor, SpmvEngine
    from repro.serve.executor import decode_buckets
    ex = PlanExecutor(plan, matrix=m,
                      buckets=decode_buckets(plan, max_bucket=max(waves)))
    ex.warmup()
    eng = SpmvEngine(ex)
    xs = np.random.default_rng(seed).standard_normal(
        (sum(waves), m.n_cols)).astype(np.float32)
    reqs, lo = [], 0
    for size in waves:
        wave = [MatvecRequest(lo + i, xs[lo + i]) for i in range(size)]
        eng.run(wave)
        reqs += wave
        lo += size
    statuses = {}
    for r in reqs:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    retries = len(eng.recovery_latencies)
    log(f"[serve] {name}: {len(reqs)} requests over buckets {ex.buckets}: "
        f"{statuses}, retries {retries}, failed {eng.failed}, "
        f"health {eng.health}")
    if statuses != {"ok": len(reqs)} or retries or eng.failed:
        raise SmokeFailure(f"{name}: served {statuses}, retries {retries}, "
                           f"failed {eng.failed}")
    err = 0.0
    for lo in range(0, len(reqs), BATCH):
        chunk = reqs[lo:lo + BATCH]
        ref = m.spmm_dense_oracle(np.stack([r.x for r in chunk], axis=1))
        err = max(err, _rel_err(np.stack([r.y for r in chunk], axis=1), ref))
    log(f"[serve] {name}: max rel err {err:.3e}")
    if err > TOL:
        raise SmokeFailure(f"{name}: served answers off by {err:.3e}")
    return {"requests": len(reqs), "rel_err": err}


def phase_timing(name: str, plan, batch: int, repeats: int = 10) -> float:
    """Median per-call time on a device-resident x."""
    import jax
    x = jax.device_put(_rhs(plan.n_cols, batch, 2))
    plan(x).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        plan(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    med = float(np.median(times))
    log(f"[time] {name} B={batch}: {med * 1e3:.4f} ms/call (median of "
        f"{repeats}), stored_bytes {plan.stored_bytes}")
    return med


def phase_dist(m, mesh, budget, reference_y, seed: int = 0) -> dict:
    """Row- and col-sharded plans over ``mesh``: no shard may fail or fall
    back, each device holds its own slice, and the output matches both
    the float64 reference and the one-chip plan's ``reference_y``."""
    import jax
    import repro
    x = _rhs(m.n_cols, 1, seed)
    ref = m.spmv_dense_oracle(x)
    devices = set(mesh.devices.flat)
    out = {}
    for mode in ("row", "col"):
        t0 = time.perf_counter()
        plan = repro.compile(m, repro.Target(backend="pallas", mesh=mesh,
                                             partition=mode), budget=budget)
        wall = time.perf_counter() - t0
        res = plan.search_result
        log(f"[dist] {mode}: {wall:.1f}s, shards "
            f"{[r.graph_label for r in res.reports]}, failures "
            f"{res.failure_counts}")
        log(plan.describe())
        fell_back = [r.shard.index for r in res.reports
                     if r.failed or (r.result is not None
                                     and r.result.fallback)]
        if fell_back or res.failure_counts.get("lowering"):
            raise SmokeFailure(f"{mode}: shards {fell_back} fell back "
                               f"({res.failure_counts})")
        for key, arr in plan.stacks.items():
            if arr.sharding.device_set != devices or \
                    len(devices) > 1 and arr.sharding.is_fully_replicated:
                raise SmokeFailure(f"{mode}: {key} is not split over the "
                                   f"mesh ({arr.sharding})")
        per_dev = {}
        for arr in plan.stacks.values():
            for s in arr.addressable_shards:
                per_dev[s.device.id] = per_dev.get(s.device.id, 0) + \
                    s.data.nbytes
        log(f"[dist] {mode}: format bytes per device {per_dev} "
            f"(plan reports {plan.per_device_format_bytes})")
        if len(per_dev) != len(devices) or min(per_dev.values()) <= 0:
            raise SmokeFailure(f"{mode}: format bytes per device {per_dev}")
        y = plan(jax.device_put(x))
        err = _rel_err(y, ref)
        vs_one = _rel_err(y, np.asarray(reference_y, np.float64))
        log(f"[dist] {mode}: rel err {err:.3e} vs float64, {vs_one:.3e} vs "
            "one-chip plan")
        if err > TOL or vs_one > TOL:
            raise SmokeFailure(f"{mode}: rel err {err:.3e} / {vs_one:.3e}")
        out[mode] = {"rel_err": err, "vs_one_chip": vs_one,
                     "per_device_bytes": per_dev}
    return out


# ---------------------------------- runs ------------------------------------

def run_one_chip(platform: str, n_rows: int = N_ROWS,
                 seconds: float = 120.0) -> None:
    budget = search_budget(seconds)
    for name in MATRICES:
        m = make_matrix(name, n_rows)
        log(f"[matrix] {name}: {m.n_rows}x{m.n_cols}, nnz {m.nnz}, "
            f"max row {int(m.row_lengths().max())}")
        plans = {b: phase_compile(name, m, b, budget, seconds, platform)
                 for b in (1, BATCH)}
        for b, plan in plans.items():
            phase_correctness(name, m, plan, b)
        phase_serve(name, m, plans[1])
        for b, plan in plans.items():
            phase_timing(name, plan, b)


def run_four_chips(platform: str, n_rows: int = N_ROWS,
                   seconds: float = 60.0) -> None:
    """The sharded plans against a one-chip plan of the heuristic design
    (no search: the comparison needs its output, not a tuned design)."""
    import jax
    import repro
    from repro.dist.spmv import default_shard_graph
    m = make_matrix("powerlaw", n_rows)
    one = repro.compile(m, repro.Target(backend="pallas"),
                        graph=default_shard_graph(m))
    _check_kernels(one, 1, platform)
    y_one = np.asarray(one(jax.device_put(_rhs(m.n_cols, 1, 0))))
    log(f"[one-chip] powerlaw: {one.graph.label()}")
    mesh = jax.make_mesh((4,), ("data",))
    phase_dist(m, mesh, search_budget(seconds), y_one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded path on a 4-chip host")
    args = ap.parse_args(argv)
    try:
        dev = phase_device(args.chips)
        from repro.runtime import enable_compilation_cache
        log(f"[cache] {enable_compilation_cache()}")
        t0 = time.perf_counter()
        if args.chips == 4:
            run_four_chips(dev["platform"])
        else:
            run_one_chip(dev["platform"])
        log(f"[done] {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
