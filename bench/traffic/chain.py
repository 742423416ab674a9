"""Traffic ``chain``: plan calls chained as an iterative solver runs them.

Each call's x is the previous call's y, rescaled on the device by its
largest magnitude (a power / PageRank / CG iteration's dependency). The
host dispatches asynchronously and stays at most ``PIPELINE_DEPTH`` calls
ahead of the device; the window closes at the first dispatch after
``--seconds`` and ends with one ``block_until_ready``. ``call_ms`` is the
window's wall-clock over the calls in it.

Correctness: the outputs of ``SAMPLES`` calls drawn from the seed among
the first ``SAMPLE_RANGE``, and of the window's last call, are kept on
the device. After the window they are compared, at the timed size, with
the benchmark's float64 CSR product of the very x each call was given.

Parameters (the cell's ``workloads/<name>.json``): ``batch`` (right-hand
sides), ``search`` (``repro.SearchConfig`` counts for the first run's
search) and ``limits``. The plan store is the cell's own, so no cell's
search is seeded by a plan that another cell stored.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench.lib import program
from bench.lib.csr import rel_err, spmv_f64

# How far the host may run ahead of the device. At 2 calls (~0.45 s of
# queued work on hpcg-104) one run in twelve read 8.7% slow, as a host
# stall longer than the queue idles the device; 8 calls (~1.8 s) left
# every run since within 0.003%.
PIPELINE_DEPTH = 8
SAMPLES = 3             # calls compared, drawn from the seed ...
SAMPLE_RANGE = 16       # ... among the window's first calls, plus its last
TRACE_SECONDS = 4       # the traced window's length: ~20 calls to reduce


def inputs(n_cols: int, batch: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    shape = (n_cols,) if batch <= 1 else (n_cols, batch)
    return rng.standard_normal(shape).astype(np.float32)


def sample_calls(seed: int, samples: int, sample_range: int) -> set[int]:
    rng = np.random.default_rng([seed, 1])
    return set(rng.choice(sample_range, size=min(samples, sample_range),
                          replace=False).tolist())


def reference(m, x: np.ndarray) -> np.ndarray:
    if x.ndim == 1:
        return spmv_f64(m, x)
    return np.stack([spmv_f64(m, x[:, j]) for j in range(x.shape[1])], 1)


def setup(ctx):
    """Plan (searched once per checkout, then loaded), the rescale step,
    and both warmed at the window's shapes."""
    import jax
    import jax.numpy as jnp
    p = ctx.params
    sm = program.sparse_matrix(ctx.matrix)
    plan = program.plan(sm, p["batch"], p["search"],
                        ctx.cache_dir / "plans" / ctx.cell)
    ctx.phase("plan")
    ctx.facts.update(stored_bytes=plan.stored_bytes, nnz=ctx.matrix.nnz,
                     n_rows=ctx.matrix.n_rows, n_cols=ctx.matrix.n_cols,
                     batch=p["batch"])
    call = ctx.plan_hook(plan, sm) if ctx.plan_hook else plan
    del sm

    @jax.jit
    def rescale(y):
        return y * (1.0 / (jnp.max(jnp.abs(y)) + 1e-30))

    x = jax.device_put(inputs(ctx.matrix.n_cols, p["batch"], ctx.seed))
    rescale(call(x)).block_until_ready()
    ctx.phase("warm")
    return call, rescale, x


def window(call, rescale, x, seconds: float, depth: int, keep: set[int]):
    """Chained calls for ``seconds``; returns (calls, wall seconds, kept
    (index, x, y) triples including the last call)."""
    import jax
    ann = jax.profiler.TraceAnnotation
    kept, pending = [], collections.deque()
    i = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        with ann("bench.dispatch"):
            y = call(x)
        with ann("bench.rescale"):
            x_next = rescale(y)
        if i in keep:
            kept.append((i, x, y))
        last = (i, x, y)
        pending.append(x_next)
        x = x_next
        i += 1
        if len(pending) > depth:
            with ann("bench.wait"):
                pending.popleft().block_until_ready()
        if time.perf_counter() >= end:
            break
    with ann("bench.block"):
        x.block_until_ready()
    wall = time.perf_counter() - t0
    if not kept or kept[-1][0] != last[0]:
        kept.append(last)
    return i, wall, kept


def run(ctx):
    p = ctx.params
    call, rescale, x = setup(ctx)
    ctx.mark_setup_done()
    keep = sample_calls(ctx.seed, SAMPLES, SAMPLE_RANGE)
    if ctx.tracer is not None:
        with ctx.tracer:
            calls, wall, kept = window(call, rescale, x,
                                       min(ctx.seconds, TRACE_SECONDS),
                                       PIPELINE_DEPTH, keep)
    else:
        calls, wall, kept = window(call, rescale, x, ctx.seconds,
                                   PIPELINE_DEPTH, keep)
    ctx.window_done(calls, wall)
    # to the host, then free the program before the reference runs
    kept = [(i, np.asarray(xi), np.asarray(yi)) for i, xi, yi in kept]
    del call, rescale, x
    errs = [rel_err(yi, reference(ctx.matrix, xi)) for _, xi, yi in kept]
    limit = p["limits"]["rel_err"]
    ctx.check("rel_err", max(errs), limit,
              failed=sum(not e <= limit for e in errs))
