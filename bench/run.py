"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in BENCHMARK.json, its parameters
in ``bench/workloads/<cell>.json``, its configuration in
``bench/configs/<config>.json`` (whose generator is ``bench/gen/<g>.py``),
its traffic in ``bench/traffic/<traffic>.py`` and each metric's reader
in ``bench/metrics/<metric>.py``.

Set-up (counted in ``setup_s``, from process start to the window): the
matrix from the configuration's seed (kept under ``bench/.cache``), the
plan (searched on the first run in a checkout, then loaded from a
``PlanStore`` there), and a warm call at the window's shapes, served from
JAX's persistent compilation cache in ``bench/.cache/jax``. Then the
traffic's window runs for ``--seconds`` (``--trace 1``: a shorter traced
window), and the outputs are compared with the benchmark's own float64
reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, ``setup_phases_s`` (the seconds from the start at which
each step of set-up ended), and last ``checks``: each compared number
with its limit, which also close standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import device as devmod  # noqa: E402
from bench.lib import matrix as matmod  # noqa: E402
from bench.lib import trace as tracemod  # noqa: E402
from bench.lib.registry import BENCH_DIR, BenchError, Registry  # noqa: E402

CACHE_DIR = BENCH_DIR / ".cache"


class Tracer:
    """The profiler around the traced window, writing to a temporary
    directory that is removed once the trace is reduced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._window = None

    def __enter__(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._window = jax.profiler.TraceAnnotation(tracemod.WINDOW)
        self._window.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._window.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def reduce(self) -> tracemod.Summary:
        try:
            return tracemod.reduce(tracemod.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Run:
    """What one run knows; the traffic fills it and metric readers read it."""

    def __init__(self, cell, params, matrix, seed, seconds, trace, device,
                 plan_hook=None, cache_dir=CACHE_DIR, phases=None):
        self.cell, self.params, self.matrix = cell, params, matrix
        self.seed, self.seconds = seed, seconds
        self.device, self.cache_dir = device, cache_dir
        self.plan_hook = plan_hook
        self.tracer = Tracer() if trace else None
        self.trace: tracemod.Summary | None = None
        self.facts: dict = {}
        self.checks: dict = {}
        self.failed = 0
        self.setup_s = self.window_s = None
        self.calls = 0
        self.memory_peak_bytes = 0
        self.phases: dict = dict(phases or {})

    @property
    def peaks(self) -> dict:
        return devmod.peaks(self.device["kind"])

    def phase(self, name: str) -> None:
        """Marks the end of a step of set-up, in seconds from the start."""
        self.phases[name] = time.perf_counter() - T0

    def mark_setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T0

    def window_done(self, calls: int, wall: float) -> None:
        import jax
        self.calls, self.window_s = calls, wall
        self.memory_peak_bytes = devmod.memory_peak_bytes(jax.local_devices())

    def check(self, name: str, value: float, limit: float,
              failed: int = 0) -> None:
        self.checks[name] = {"value": float(value), "limit": float(limit)}
        self.failed += int(failed)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks.values())


def run_cell(registry: Registry, name: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True, plan_hook=None,
             cache_dir=CACHE_DIR) -> dict:
    """One run of cell ``name``; returns the result object."""
    cell = registry.cell(name)
    import jax
    phases = {"import": time.perf_counter() - T0}
    jax.config.update("jax_compilation_cache_dir", str(cache_dir / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # A Mosaic kernel's serialized body carries the source locations of its
    # ops, and with full tracebacks those name every caller frame: the plan
    # traced under the first run's search and under a later run's warm call
    # would then differ in its cache key, and the second run would compile
    # again. Innermost frames only make the key the same from any caller.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    if require_chip:
        dev = devmod.check_device(int(cell["chips"]))
        devmod.peaks(dev["kind"])
    else:                               # tests: whatever JAX runs on
        d = jax.devices()
        dev = {"platform": d[0].platform, "kind": d[0].device_kind,
               "count": len(d)}
    phases["device"] = time.perf_counter() - T0
    params = registry.data("workloads", name)
    m = matmod.load(registry, registry.data("configs", cell["config"]),
                    cache_dir / "matrices")
    phases["matrix"] = time.perf_counter() - T0
    run = Run(name, params, m, seed, seconds, trace, dev, plan_hook=plan_hook,
              cache_dir=cache_dir, phases=phases)
    registry.module("traffic", cell["traffic"]).run(run)
    if run.tracer is not None:
        run.trace = run.tracer.reduce()
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for entry in registry.metrics(name, section):
        value = registry.module("metrics", entry["name"]).read(run)
        if value is None:
            if section == "end_to_end":
                raise BenchError(f"end-to-end metric {entry['name']} has "
                                 "no value")
            continue
        metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    device = dict(dev, memory_peak_bytes=run.memory_peak_bytes)
    result = {"correct": run.correct, "attempted": run.calls,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        result["breakdown"] = run.trace.breakdown()
    result["setup_phases_s"] = run.phases
    result["checks"] = run.checks
    return result


def main(argv=None, **kw) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    registry = kw.pop("registry", None) or Registry.from_file()
    try:
        result = run_cell(registry, args.workload, args.seed, args.seconds,
                          bool(args.trace), **kw)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
