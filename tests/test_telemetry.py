"""In-process spans and counters (``repro.telemetry``), the device-side
names a plan's program carries (``spmv.gather`` / ``spmv.combine`` scopes,
named kernels, ``jit_spmv_plan``), and caller-independent lowering once
the compilation cache is enabled."""
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import repro
from repro import telemetry
from repro.core.graph import OperatorGraph
from repro.core.kernel_builder import build_kernel
from repro.core.matrices import powerlaw_matrix
from repro.core.operators import OpSpec
from repro.runtime import enable_compilation_cache


@pytest.fixture(autouse=True)
def _fresh_table():
    telemetry.reset()
    yield
    telemetry.reset()


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# ------------------------------- the table ---------------------------------

def test_nested_spans_count_total_and_self():
    for _ in range(2):
        with telemetry.span("outer", step=1):
            _spin(0.01)
            with telemetry.span("inner"):
                _spin(0.02)
    spans = telemetry.snapshot()["spans"]
    outer, inner = spans["outer"], spans["inner"]
    assert outer["count"] == inner["count"] == 2
    assert inner["total_s"] >= 0.04 and outer["total_s"] >= 0.06
    assert inner["self_s"] == pytest.approx(inner["total_s"])
    # the outer span's own time excludes what its child covered
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-6)
    assert outer["max_s"] <= outer["total_s"]
    assert outer["max_s"] >= outer["total_s"] / 2


def test_span_decorates_a_function():
    @telemetry.span("decorated")
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert f.__name__ == "f"
    assert telemetry.snapshot()["spans"]["decorated"]["count"] == 2


def test_counters_snapshot_and_reset():
    telemetry.count("a")
    telemetry.count("a", 2)
    telemetry.count("b", 0.5)
    snap = telemetry.snapshot()
    assert snap["counters"]["a"] == 3 and snap["counters"]["b"] == 0.5
    # a copy: changing it changes nothing inside
    snap["counters"]["a"] = 99
    assert telemetry.snapshot()["counters"]["a"] == 3
    telemetry.reset()
    snap = telemetry.snapshot()
    assert snap["spans"] == {}
    assert snap["counters"] == {"jax.compiles": 0, "jax.compiles_by_fun": {}}


def test_threads_keep_their_own_span_stacks():
    """A span on one thread is never the parent of a span on another."""
    start = threading.Barrier(2)

    def worker(name):
        start.wait()
        with telemetry.span(f"{name}.outer"):
            with telemetry.span(f"{name}.inner"):
                _spin(0.02)

    threads = [threading.Thread(target=worker, args=(n,)) for n in "ab"]
    with telemetry.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    spans = telemetry.snapshot()["spans"]
    for n in "ab":
        assert spans[f"{n}.outer"]["count"] == 1
        assert spans[f"{n}.outer"]["self_s"] < spans[f"{n}.inner"]["total_s"]
    # the main thread's span saw no children of its own
    assert spans["main"]["self_s"] == pytest.approx(spans["main"]["total_s"])


def test_span_lands_in_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("repro.test.traced"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {ev.name
             for plane in ProfileData.from_file(str(path)).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert "repro.test.traced" in names


def test_fresh_jit_counts_a_compile_by_function():
    before = telemetry.snapshot()["counters"]

    @jax.jit
    def fresh_telemetry_fn(x):
        return x * 3.0 + 0.123456789

    fresh_telemetry_fn(jnp.arange(7.0)).block_until_ready()
    after = telemetry.snapshot()["counters"]
    assert after["jax.compiles"] >= before["jax.compiles"] + 1
    assert after["jax.compiles_by_fun"]["jit(fresh_telemetry_fn)"] == 1
    for part in ("jax.trace_s", "jax.lower_s", "jax.compile_or_load_s"):
        assert after[part] > 0


def test_nested_jit_trace_counts_once():
    """A jit traced inside another's trace adds to ``jax.trace_s`` once,
    inside the outer trace's time."""
    @jax.jit
    def inner_traced(x):
        time.sleep(0.3)
        return x + 1.0

    @jax.jit
    def outer_traced(x):
        time.sleep(0.05)
        return inner_traced(x) * 2.0

    outer_traced(jnp.arange(5.0)).block_until_ready()
    trace_s = telemetry.snapshot()["counters"]["jax.trace_s"]
    assert 0.35 <= trace_s < 0.6


# ----------------------- names in the lowered program ----------------------

# XLA names a device op by the outermost name of its MLIR location, after
# the name of every call that reaches it
_NAME_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"[()]', re.M)
_FUNC = re.compile(r'^  func\.func \w+ @([^(]+)\([^\n]*\n(.*?)^  \}',
                   re.M | re.S)
_CALL = re.compile(r'call @([^(]+)\([^\n]*loc\((#loc\d+)\)$', re.M)
_OPS = {
    "gather": re.compile(r'"stablehlo\.gather"[^\n]*loc\((#loc\d+)\)$',
                         re.M),
    "dynamic_update_slice": re.compile(
        r'stablehlo\.dynamic_update_slice [^\n]*loc\((#loc\d+)\)$', re.M),
    "scatter": re.compile(
        r'"stablehlo\.scatter".*?^\s*\}\) :[^\n]*loc\((#loc\d+)\)$',
        re.M | re.S)}
_KERNEL = re.compile(r"^jit\((ell_sp(mv|mm)|seg_sp(mv|mm)(_fused)?_"
                     r"(seg_scan|onehot_mxu))\)$")


def _op_scopes(text: str) -> list:
    """(kind, name stack) of every gather, scatter and dynamic-update-slice
    in a lowered module, as the device names it: a private function's ops
    take the names of the calls that reach it."""
    locs = dict(_NAME_LOC.findall(text))
    bodies = dict(_FUNC.findall(text))
    out = []

    def walk(fn, stack):
        body = bodies[fn]
        for callee, loc in _CALL.findall(body):
            walk(callee, stack + locs.get(loc, "").split("/"))
        for kind, pat in _OPS.items():
            out.extend((kind, stack + locs.get(loc, "").split("/"))
                       for loc in pat.findall(body))

    walk("main", [])
    return out


ELL = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
    OpSpec.make("LANE_ROW_BLOCK"), OpSpec.make("LANE_TOTAL_RED"))
SEG = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("SORT"),
    OpSpec.make("LANE_NNZ_BLOCK", chunk=64, lanes=8),
    OpSpec.make("SEG_SCAN_RED"))


def _lowered(plan, b: int) -> str:
    fn = jax.jit(build_kernel(plan.spec, backend="pallas"))
    shape = (plan.n_cols,) if b == 1 else (plan.n_cols, b)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    return fn.lower(plan.fmt, x).as_text(debug_info=True)


@pytest.fixture(scope="module")
def plans():
    m = powerlaw_matrix(120, 120, 5.0, 1.2, seed=3)
    return {name: repro.compile(m, repro.Target(backend="pallas"), graph=g)
            for name, g in (("ell", ELL), ("seg", SEG))}


@pytest.fixture
def _restore_locations():
    names = ("jax_include_full_tracebacks_in_locations",
             "jax_traceback_in_locations_limit")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


@pytest.mark.parametrize("innermost", [False, True],
                         ids=["one_frame", "innermost_frame"])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("design", ["ell", "seg"])
def test_lowered_program_names_gather_combine_and_kernels(
        plans, design, b, innermost, _restore_locations):
    """Under either location setting that keeps compile-cache keys stable
    (``enable_compilation_cache``'s one frame, or innermost frames only),
    every gather is named ``spmv.gather``, every scatter and block write
    outside a kernel ``spmv.combine``, and the kernels by name."""
    jax.config.update("jax_include_full_tracebacks_in_locations",
                      not innermost)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    plan = plans[design]
    kinds = {s["kind"] for s in plan.spec["steps"]}
    assert kinds == {design}
    # an unfused step, so the combine is an XLA scatter
    assert not all(s.get("fused") for s in plan.spec["steps"])
    text = _lowered(plan, b)
    assert re.search(r"^module @jit_spmv_plan ", text, re.M)
    seen = {"gather": 0, "combine": 0, "kernel": 0}
    for kind, stack in _op_scopes(text):
        assert stack[0] == "jit(spmv_plan)", stack
        if any(_KERNEL.match(part) for part in stack):
            # the interpreted kernel's own block writes
            assert kind != "gather", stack
            seen["kernel"] += 1
        elif kind == "gather":
            assert "spmv.gather" in stack, stack
            seen["gather"] += 1
        else:
            assert "spmv.combine" in stack, stack
            seen["combine"] += 1
    assert all(seen.values()), seen


# -------------------- lowering independent of the caller --------------------

def _lowered_from_depth(depth: int, plan) -> str:
    if depth:
        return _lowered_from_depth(depth - 1, plan)
    return _lowered(plan, 1)


def test_compilation_cache_makes_lowering_caller_independent(
        plans, monkeypatch, tmp_path, _restore_locations):
    """With JAX's default of 10 frames a program's locations name its
    callers, so a persistent-cache key would differ by call site; once the
    cache is enabled the same plan lowers to the same text from any
    depth."""
    plan = plans["ell"]
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.config.update("jax_traceback_in_locations_limit", 10)
    assert _lowered_from_depth(0, plan) != _lowered_from_depth(3, plan)
    # point the cache at a throwaway directory (nothing is compiled here)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    enable_compilation_cache()
    assert _lowered_from_depth(0, plan) == _lowered_from_depth(3, plan)
