"""jit_load_s: seconds JAX spent making the run's executables: tracing,
lowering, and XLA's compile step, which loads from the persistent
compilation cache on a hit (the program's counters ``jax.trace_s``,
``jax.lower_s`` and ``jax.compile_or_load_s``). None where the program
keeps no such counters."""
from bench.lib.telemetry import snapshot

PARTS = ("jax.trace_s", "jax.lower_s", "jax.compile_or_load_s")


def read(run):
    snap = snapshot()
    if snap is None or not any(p in snap["counters"] for p in PARTS):
        return None
    return sum(snap["counters"].get(p, 0.0) for p in PARTS)
