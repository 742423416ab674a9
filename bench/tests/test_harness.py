"""The harness on the CPU at a tiny size: the no-chip guard, the peaks
table, resolution by name from files added beside the benchmark, calls
dispatched one by one or K to a program, and ``correct`` coming out false
for a broken timed path and for the lower-precision control."""
import json
import os
import subprocess
import sys

import pytest
from jax.tree_util import Partial

from bench import control
from bench.lib import device
from bench.lib.registry import BENCH_DIR, ROOT, BenchError, Registry
from bench.run import main, run_cell

TINY_CELL = "tiny-hpcg-chain"
# the tiny chain cell by calls a program: K = 1 is the cell's file without
# ``calls_per_dispatch``, K = 4 a cell that sets it
TINY_K = {1: TINY_CELL, 4: f"{TINY_CELL}-k4"}


def tiny_registry(tmp) -> Registry:
    """A throwaway configuration, cell and metric reader, written as files
    in ``tmp``; the registry searches ``tmp`` first, then bench/."""
    (tmp / "configs").mkdir()
    (tmp / "workloads").mkdir()
    (tmp / "metrics").mkdir()
    (tmp / "configs" / "tiny-hpcg.json").write_text(json.dumps({
        "name": "tiny-hpcg", "generator": "hpcg27",
        "params": {"nx": 8, "ny": 8, "nz": 8, "seed": 1}}))
    params = json.loads((BENCH_DIR / "workloads" / "hpcg-cg-b1.json")
                        .read_text())
    params.pop("calls_per_dispatch", None)
    (tmp / "workloads" / f"{TINY_CELL}.json").write_text(json.dumps(params))
    (tmp / "workloads" / f"{TINY_CELL}-b2.json").write_text(
        json.dumps(dict(params, batch=2)))
    (tmp / "workloads" / f"{TINY_K[4]}.json").write_text(
        json.dumps(dict(params, calls_per_dispatch=4)))
    (tmp / "metrics" / "tiny_calls.py").write_text(
        "def read(run):\n    return float(run.calls)\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in (TINY_CELL, f"{TINY_CELL}-b2", TINY_K[4]):
        spec["workloads"].append({"name": cell, "config": "tiny-hpcg",
                                  "traffic": "chain", "chips": 1,
                                  "why": "test"})
    spec["end_to_end"].append({"name": "tiny_calls", "unit": "count",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": [TINY_CELL]})
    for m in spec["per_layer"]:
        m["workloads"] += [TINY_CELL, TINY_K[4]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry.from_file(tmp / "BENCHMARK.json", dirs=(tmp, BENCH_DIR))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    return tiny_registry(tmp), tmp / "cache"


@pytest.fixture(params=sorted(TINY_K), ids=lambda k: f"K{k}")
def k(request):
    return request.param


def run_tiny(tiny, seed=3, trace=False, plan_hook=None, cell=TINY_CELL):
    registry, cache = tiny
    return run_cell(registry, cell, seed, 0.3, trace,
                    require_chip=False, plan_hook=plan_hook, cache_dir=cache)


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH_DIR / "run.py"),
                        "--workload", "hpcg-cg-b1", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_alone_without_program_fails(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ cannot run."""
    import shutil
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "hpcg-cg-b1", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, env=env, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_peaks_table():
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError, match="no peaks"):
        device.peaks("TPU v99")


def test_resolves_added_files_by_name(tiny):
    res = run_tiny(tiny)
    assert res["correct"] is True, res
    m = res["metrics"]
    assert set(m) == {"call_ms", "setup_s", "tiny_calls"}
    assert m["tiny_calls"]["value"] == res["attempted"] > 0
    assert m["call_ms"]["unit"] == "ms" and m["call_ms"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["rel_err"]["value"] < 1e-5
    # the second run loads the plan the first one stored
    assert run_tiny(tiny, seed=4)["correct"] is True


def test_batched_chain_is_a_data_file(tiny):
    """B right-hand sides need only ``batch`` in the cell's file."""
    res = run_tiny(tiny, cell=f"{TINY_CELL}-b2")
    assert res["correct"] is True, res
    assert res["checks"]["rel_err"]["value"] < 1e-5


def test_each_cell_has_its_own_plan_store(tiny):
    """No cell's search can be seeded by a plan another cell stored."""
    run_tiny(tiny)
    run_tiny(tiny, cell=f"{TINY_CELL}-b2")
    _, cache = tiny
    stores = sorted(p.name for p in (cache / "plans").iterdir())
    assert stores == [TINY_CELL, f"{TINY_CELL}-b2"]


def test_setup_phases_in_order(tiny):
    res = run_tiny(tiny, seed=5)
    ph = res["setup_phases_s"]
    assert list(ph) == ["import", "device", "matrix", "plan", "warm"]
    assert list(ph.values()) == sorted(ph.values())
    assert ph["warm"] <= res["metrics"]["setup_s"]["value"]


def test_hbm_roofline_reads_device_time(tiny):
    """The share's time per call is the trace's busy time, not the host's
    window, which a host-bound run would stretch."""
    from types import SimpleNamespace as NS
    registry, _ = tiny
    reader = registry.module("metrics", "hbm_roofline")
    facts = {"nnz": 1000, "n_rows": 100, "n_cols": 100, "batch": 1}
    floor_s = (4 * 1000 + 4 * 200) / 819e9
    run = NS(trace=NS(busy_s=10 * floor_s * 4), calls=10, window_s=1.0,
             facts=facts, peaks={"hbm_bytes_per_s": 819e9})
    assert reader.read(run) == pytest.approx(25.0)
    run.trace = None
    assert reader.read(run) is None


@pytest.mark.parametrize("value", [0, -1, 2.5, True, "4"])
def test_calls_per_dispatch_refuses_bad_values(tiny, value):
    chain = tiny[0].module("traffic", "chain")
    with pytest.raises(BenchError, match="calls_per_dispatch"):
        chain.calls_per_dispatch({"calls_per_dispatch": value})


def test_calls_per_dispatch(tiny, monkeypatch, k):
    """K absent runs the call-by-call loop; K > 1 runs K chained calls a
    program, and ``attempted`` counts programs x K."""
    registry, _ = tiny
    chain = registry.module("traffic", "chain")
    seen = {}

    def spy(name):
        fn = getattr(chain, name)

        def wrapped(*a, **kw):
            seen[name] = out = fn(*a, **kw)
            return out
        monkeypatch.setattr(chain, name, wrapped)
    for name in ("stepwise", "chained", "window"):
        spy(name)
    params = registry.data("workloads", TINY_K[k])
    assert ("calls_per_dispatch" in params) == (k > 1)
    res = run_tiny(tiny, cell=TINY_K[k])
    assert res["correct"] is True, res
    programs = seen.pop("window")[0]
    assert programs > 0 and res["attempted"] == programs * k
    assert set(seen) == {"stepwise" if k == 1 else "chained"}
    assert res["checks"]["rel_err"]["value"] < 1e-5


def test_traced_run_reports_per_layer(tiny, k):
    res = run_tiny(tiny, trace=True, cell=TINY_K[k])
    assert res["correct"] is True
    # no TPU plane on the CPU: the trace readers return nothing, the
    # plan's own count and the program's spans and counters still read
    assert set(res["metrics"]) == {"stored_bytes_ratio", "plan_load_s",
                                   "jit_load_s", "jit_compiles"}
    assert res["metrics"]["stored_bytes_ratio"]["value"] > 1
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_main_prints_checks_last(tiny, capsys):
    registry, cache = tiny
    rc = main(["--workload", TINY_CELL, "--seed", str(2 ** 40 + 7),
               "--seconds", "0.3", "--trace", "0"], registry=registry,
              require_chip=False, cache_dir=cache)
    out, err = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert err.strip().splitlines()[-1].startswith("check rel_err ")


# Faults put in the plan's place. Each is a pytree (``Partial``), as the
# plan is, so that a program of K chained calls takes it as an argument.
def _identity(x):
    return x


def _unchanged(plan, sm):
    return Partial(_identity)


def _half_rows_call(plan, x):
    return plan(x).at[plan.n_rows // 2:].set(0.0)


def _half_rows(plan, sm):
    return Partial(_half_rows_call, plan)


def _altered_call(plan, x):
    import jax.numpy as jnp
    y = plan(x)
    return y.at[7].add(1e-3 * jnp.max(jnp.abs(y)))


def _one_answer_altered(plan, sm):
    return Partial(_altered_call, plan)


@pytest.mark.parametrize("fault", [_unchanged, _half_rows,
                                   _one_answer_altered])
def test_broken_timed_path_is_not_correct(tiny, fault, k):
    res = run_tiny(tiny, plan_hook=fault, cell=TINY_K[k])
    assert res["correct"] is False
    assert res["failed"] > 0


def test_lower_precision_control_is_not_correct(tiny, k):
    res = run_tiny(tiny, plan_hook=control.lower_precision_hook(),
                   cell=TINY_K[k])
    assert res["correct"] is False
    assert res["checks"]["rel_err"]["value"] > 1e-4
