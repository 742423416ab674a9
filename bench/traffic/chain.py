"""Traffic ``chain``: plan calls chained as an iterative solver runs them.

Each call's x is the previous call's y, rescaled on the device by its
largest magnitude (a power / PageRank / CG iteration's dependency).

The cell's ``calls_per_dispatch`` (K) sets how the host issues them:

* absent or 1: each call is its own dispatch, the plan's program and then
  the rescale's, two programs a call;
* K > 1: one program runs K chained calls in a ``lax.fori_loop``, the plan
  passed in as a pytree argument, as ``jax.scipy.sparse.linalg.cg`` runs
  its operator inside one loop. The host's dispatch then leaves the timed
  path, and a plan faster than a dispatch reads the chip's time.

The host stays at most ``PIPELINE_DEPTH`` programs ahead of the device;
the window closes at the first dispatch after ``--seconds`` and ends with
one ``block_until_ready``. ``call_ms`` is the window's wall-clock over the
calls in it, programs × K.

Correctness: the last call of ``SAMPLES`` programs drawn from the seed
among the first ``SAMPLE_RANGE``, and of the window's last program, are
kept on the device. After the window they are compared, at the timed
size, with the benchmark's float64 CSR product of the very x each call
was given.

Parameters (the cell's ``workloads/<name>.json``): ``batch`` (right-hand
sides), ``search`` (``repro.SearchConfig`` counts for the first run's
search), ``limits``, and optionally ``calls_per_dispatch``. The plan store
is the cell's own, so no cell's search is seeded by a plan that another
cell stored.
"""
from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

from bench.lib import program
from bench.lib.csr import rel_err, spmv_f64
from bench.lib.registry import BenchError

# How far the host may run ahead of the device, in programs. At 2 calls
# of one program each (~0.45 s of queued work on hpcg-104 when a call was
# 227 ms) one run in twelve read 8.7% slow, as a host stall longer than
# the queue idles the device; 8 (~1.8 s) left every run since within
# 0.003%. A cell whose call is far shorter sets K so that 8 programs
# still queue about that much work.
PIPELINE_DEPTH = 8
SAMPLES = 3             # programs compared, drawn from the seed ...
SAMPLE_RANGE = 16       # ... among the window's first programs, plus its last
TRACE_SECONDS = 4       # the traced window's length: ~20 programs to reduce


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


def calls_per_dispatch(params: dict) -> int:
    k = params.get("calls_per_dispatch", 1)
    if not isinstance(k, int) or isinstance(k, bool) or k < 1:
        raise BenchError(f"calls_per_dispatch must be a whole number >= 1, "
                         f"not {k!r}")
    return k


def rescale(y):
    import jax.numpy as jnp
    return y * (1.0 / (jnp.max(jnp.abs(y)) + 1e-30))


def stepwise(call):
    """K = 1: a dispatch is one call and its rescale, two programs. Maps
    x to (the next x, x, y)."""
    import jax
    ann = jax.profiler.TraceAnnotation
    rescale_jit = jax.jit(rescale)

    def dispatch(x):
        with ann("bench.dispatch"):
            y = call(x)
        with ann("bench.rescale"):
            x_next = rescale_jit(y)
        return x_next, x, y
    return dispatch


def chained(call, k: int):
    """K > 1: a dispatch is one program of K chained calls. Its state is
    the last call's y, and the next program starts by rescaling it (the
    window's first program rescales the seed's x). Maps y to (the last
    y, the last call's x, the last y)."""
    import jax
    ann = jax.profiler.TraceAnnotation

    @jax.jit
    def chain_step(plan, y):
        # the carry is the last call's (x, y), the pair the window checks;
        # a step reads only y
        def body(_, xy):
            x = rescale(xy[1])
            return x, plan(x).astype(y.dtype)
        return jax.lax.fori_loop(0, k, body, (y, y))

    def dispatch(y):
        with ann("bench.dispatch"):
            x, y = chain_step(call, y)
        return y, x, y
    return dispatch


def setup(ctx, k: int):
    """Plan (searched once per checkout, then loaded) and the dispatch,
    warmed at the window's shapes."""
    import jax
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
    dispatch = stepwise(call) if k == 1 else chained(call, k)
    x = jax.device_put(inputs(ctx.matrix.n_cols, p["batch"], ctx.seed))
    dispatch(x)[0].block_until_ready()
    ctx.phase("warm")
    return dispatch, x


def window(dispatch, state, seconds: float, depth: int, keep: set[int]):
    """Chained dispatches for ``seconds``; returns (programs, wall seconds,
    kept (index, x, y) triples including the last program's)."""
    import jax
    ann = jax.profiler.TraceAnnotation
    kept, pending = [], collections.deque()
    i = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        state, x, y = dispatch(state)
        if i in keep:
            kept.append((i, x, y))
        last = (i, x, y)
        pending.append(state)
        i += 1
        if len(pending) > depth:
            with ann("bench.wait"):
                pending.popleft().block_until_ready()
        if time.perf_counter() >= end:
            break
    with ann("bench.block"):
        state.block_until_ready()
    wall = time.perf_counter() - t0
    if not kept or kept[-1][0] != last[0]:
        kept.append(last)
    return i, wall, kept


def run(ctx):
    p = ctx.params
    k = calls_per_dispatch(p)
    dispatch, x = setup(ctx, k)
    ctx.mark_setup_done()
    keep = sample_calls(ctx.seed, SAMPLES, SAMPLE_RANGE)
    seconds = ctx.seconds
    if ctx.tracer is not None:
        seconds = min(seconds, TRACE_SECONDS)
    with ctx.tracer or contextlib.nullcontext():
        programs, wall, kept = window(dispatch, x, seconds, PIPELINE_DEPTH,
                                      keep)
    ctx.window_done(programs * k, wall)
    # to the host, then free the program before the reference runs
    kept = [(i, np.asarray(xi), np.asarray(yi)) for i, xi, yi in kept]
    del dispatch, x
    errs = [rel_err(yi, reference(ctx.matrix, xi)) for _, xi, yi in kept]
    limit = p["limits"]["rel_err"]
    ctx.check("rel_err", max(errs), limit,
              failed=sum(not e <= limit for e in errs))
