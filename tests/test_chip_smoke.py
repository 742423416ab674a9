"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The sizes are passed in here, not through an option of the script. The
checks that make the smoke fail — a search that fell back, a request that
failed, a shard that fell back — are exercised by injecting faults through
the repo's own seams (``fault_hook``, ``shard_fault_hook``).
"""
import json
import shutil
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
import repro  # noqa: E402
from repro.core.search import fault_hook  # noqa: E402
from repro.dist.search import shard_fault_hook  # noqa: E402
from repro.dist.spmv import default_shard_graph  # noqa: E402
from repro.runtime import enable_compilation_cache, resolve_interpret  # noqa: E402,E501

N = 256


@pytest.fixture(scope="module")
def tiny():
    return {name: cs.make_matrix(name, N) for name in cs.MATRICES}


@pytest.fixture(scope="module")
def heuristic_plan(tiny):
    m = tiny["powerlaw"]
    return repro.compile(m, repro.Target(backend="pallas"),
                         graph=default_shard_graph(m))


def test_one_chip_run_passes_tiny():
    """Every phase of the one-chip run, in order, at n=N."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cs.run_one_chip("cpu", n_rows=N, seconds=30.0)


def test_device_phase_refuses_a_cpu(capsys):
    with pytest.raises(cs.SmokeFailure, match="no TPU"):
        cs.phase_device()
    assert cs.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_fails_on_fallback(tiny):
    m = tiny["stencil"]
    with warnings.catch_warnings(), fault_hook(lambda graph, y: y + 1.0):
        warnings.simplefilter("ignore")
        with pytest.raises(cs.SmokeFailure, match="fell back"):
            cs.phase_compile("stencil", m, 1, cs.search_budget(20.0), 20.0,
                             "cpu")


def test_correctness_fails_on_a_wrong_plan(tiny, heuristic_plan):
    other = tiny["stencil"]
    with pytest.raises(cs.SmokeFailure, match="rel err"):
        cs.phase_correctness("powerlaw", other, heuristic_plan, 1)


def test_serve_fails_on_a_failed_request(tiny, heuristic_plan, monkeypatch):
    from repro.serve.executor import PlanExecutor

    def broken(self, xs):
        raise RuntimeError("injected executor fault")

    monkeypatch.setattr(PlanExecutor, "execute", broken)
    with pytest.raises(cs.SmokeFailure, match="failed"):
        cs.phase_serve("powerlaw", tiny["powerlaw"], heuristic_plan,
                       waves=(1, 2), seed=0)


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def _dist_budget():
    return cs.search_budget(10.0)


def test_dist_phase_passes_and_fails_on_shard_fallback(tiny):
    m = tiny["powerlaw"]
    x = cs._rhs(m.n_cols, 1, 0)
    ref = m.spmv_dense_oracle(x)
    out = cs.phase_dist(m, _mesh1(), _dist_budget(), ref)
    assert set(out) == {"row", "col"}
    assert all(r["rel_err"] < cs.TOL for r in out.values())

    def crash(shard):
        raise RuntimeError("injected shard crash")

    with warnings.catch_warnings(), shard_fault_hook(crash):
        warnings.simplefilter("ignore")
        with pytest.raises(cs.SmokeFailure, match="fell back"):
            cs.phase_dist(m, _mesh1(), _dist_budget(), ref)


def test_four_chip_run_passes_on_fake_devices():
    """The ``--chips 4`` path on four host devices (own process: the
    device count is fixed before jax starts)."""
    script = ("import chip_smoke as cs\n"
              f"cs.run_four_chips('cpu', n_rows={2 * N}, seconds=10.0)\n")
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    res = subprocess.run([sys.executable, "-W", "ignore", "-c", script],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.count("[dist]") >= 6, res.stdout[-2000:]


def test_script_alone_fails_without_a_result(tmp_path):
    """In a directory holding only the script, it exits non-zero and
    prints no result line."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    for line in res.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_interpret_derives_from_the_platform():
    assert resolve_interpret(None) is True          # the CPU
    assert resolve_interpret(False) is False
    assert repro.Target(backend="pallas").runs_interpreted


def test_compilation_cache_defers_to_the_environment(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper returns it and sets
    no path of its own (tests never turn the cache on)."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/cache")
    assert enable_compilation_cache() == "/nonexistent/cache"
    assert jax.config.jax_compilation_cache_dir == before
