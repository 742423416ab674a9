"""In-process spans and counters: where the host's time goes, from inside.

* :class:`span` — ``with span("repro.store.get"): ...`` (or as a
  decorator). It enters a ``jax.profiler.TraceAnnotation``, so while a
  profiler runs the span lands in the trace's host plane on the same
  clock as the device ops, and it adds its duration to an in-memory
  table: per name ``count``, ``total_s``, ``self_s`` (the time its child
  spans on the same thread do not cover) and ``max_s``.
* :func:`count` — adds to a named counter in the same table.
* :func:`snapshot` / :func:`reset` — a copy of the table, and clearing it.
* :func:`device_call` — names device work: the decorated function runs as
  its own jitted call under a scope and/or a name, which every device op
  it makes carries in its name stack (the trace's ``tf_op``).

JAX's own compile events feed counters through one ``jax.monitoring``
listener, registered when this module is imported:

* ``jax.trace_s`` — seconds tracing Python to jaxprs (a jit traced inside
  another's trace is not counted twice);
* ``jax.lower_s`` — seconds lowering jaxprs to MLIR modules;
* ``jax.compile_or_load_s`` — seconds in XLA's compile step, which is
  either a compile or a load from the persistent compilation cache;
* ``jax.cache_hits`` — executables loaded from the persistent cache;
* ``jax.compiles`` / ``jax.compiles_by_fun`` — executables XLA compiled,
  in all and per jitted function.

A backend-compile event whose thread saw no persistent-cache hit inside
it is a compile. JAX's own ``cache_misses`` event is not used: it fires
only when an entry is written, so it misses every compile made with no
cache, or below the cache's size and time thresholds.

Nothing is written to disk: the profiler's trace and :func:`snapshot`
are the only outputs.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

__all__ = ["span", "count", "snapshot", "reset", "device_call"]

_lock = threading.Lock()
_spans: dict[str, list] = {}          # name -> [count, total, self, max] (ns)
_counters: dict[str, float] = {}
_compiles_by_fun: dict[str, int] = {}
_tls = threading.local()              # .stack of open spans, .cache_hit


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class span(contextlib.ContextDecorator):
    """A named, timed region of host code (see the module docstring).

    The instance holds no per-entry state (that lives on a per-thread
    stack), so one instance may decorate a function called from many
    threads."""

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        ann.__enter__()
        # [annotation, start ns, ns covered by child spans]
        _stack().append([ann, time.perf_counter_ns(), 0])
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        stack = _tls.stack
        ann, t0, child = stack.pop()
        ann.__exit__(*exc)
        dur = t1 - t0
        if stack:
            stack[-1][2] += dur
        with _lock:
            row = _spans.get(self.name)
            if row is None:
                _spans[self.name] = [1, dur, dur - child, dur]
            else:
                row[0] += 1
                row[1] += dur
                row[2] += dur - child
                row[3] = max(row[3], dur)
        return False


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def snapshot() -> dict:
    """``{"spans": {name: {count, total_s, self_s, max_s}},
    "counters": {name: value, "jax.compiles_by_fun": {fun: n}}}``."""
    with _lock:
        spans = {name: {"count": c, "total_s": t * 1e-9, "self_s": s * 1e-9,
                        "max_s": m * 1e-9}
                 for name, (c, t, s, m) in _spans.items()}
        counters = dict(_counters)
        counters.setdefault("jax.compiles", 0)
        counters["jax.compiles_by_fun"] = dict(_compiles_by_fun)
    return {"spans": spans, "counters": counters}


def reset() -> None:
    """Clear every span and counter (spans open now still record on exit)."""
    with _lock:
        _spans.clear()
        _counters.clear()
        _compiles_by_fun.clear()


def device_call(scope: str | None = None, name: str | None = None,
                **jit_kw):
    """Decorator: the function runs as its own jitted call, named
    ``jit(<name>)`` (default: its own name) under the ``scope`` named scope.

    Only a call keeps a name stack in its device ops' names whatever the
    location settings: a scope around ops lowered in line is dropped from
    them where locations keep just the innermost frame
    (``jax_include_full_tracebacks_in_locations=False``), and a
    ``pallas_call``'s ``name=`` names only the Mosaic function. XLA inlines
    the call, so the compiled ops are the same. ``jit_kw`` go to
    ``jax.jit`` (``static_argnums``, ...)."""
    def deco(fn):
        if name is not None:
            inner = fn

            def fn(*args):
                return inner(*args)
            fn.__name__ = fn.__qualname__ = name
        call = jax.jit(fn, **jit_kw)
        return jax.named_scope(scope)(call) if scope else call
    return deco


# --------------------------- JAX compile events ----------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_SECONDS = {_TRACE: "jax.trace_s",
            "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower_s",
            _BACKEND_COMPILE: "jax.compile_or_load_s"}


def _on_event(event: str, **kwargs) -> None:
    # fires inside the backend-compile step, on the thread that runs it
    if event == _CACHE_HIT:
        _tls.cache_hit = True


def _on_scalar(event: str, value: float, **kwargs) -> None:
    # a trace starts (its start time): nested traces end inside it
    if event == _TRACE:
        _tls.trace_depth = getattr(_tls, "trace_depth", 0) + 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    name = _SECONDS.get(event)
    if name is None:
        return
    if event == _TRACE:
        _tls.trace_depth = max(getattr(_tls, "trace_depth", 1) - 1, 0)
        if _tls.trace_depth:        # inside an outer trace that counts it
            return
    count(name, duration_secs)
    if event != _BACKEND_COMPILE:
        return
    if getattr(_tls, "cache_hit", False):
        _tls.cache_hit = False
        count("jax.cache_hits")
        return
    fun = str(kwargs.get("fun_name", "?"))
    with _lock:
        _counters["jax.compiles"] = _counters.get("jax.compiles", 0) + 1
        _compiles_by_fun[fun] = _compiles_by_fun.get(fun, 0) + 1


# once per process: this module body runs once per interpreter
jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_scalar_listener(_on_scalar)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
