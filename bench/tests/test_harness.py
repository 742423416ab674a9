"""The harness on the CPU at a tiny size: the no-chip guard, the peaks
table, resolution by name from files added beside the benchmark, and
``correct`` coming out false for a broken timed path and for the
lower-precision control."""
import json
import os
import subprocess
import sys

import pytest

from bench import control
from bench.lib import device
from bench.lib.registry import BENCH_DIR, ROOT, BenchError, Registry
from bench.run import main, run_cell

TINY_CELL = "tiny-hpcg-chain"


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
    (tmp / "workloads" / f"{TINY_CELL}.json").write_text(json.dumps(params))
    (tmp / "workloads" / f"{TINY_CELL}-b2.json").write_text(
        json.dumps(dict(params, batch=2)))
    (tmp / "metrics" / "tiny_calls.py").write_text(
        "def read(run):\n    return float(run.calls)\n")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in (TINY_CELL, f"{TINY_CELL}-b2"):
        spec["workloads"].append({"name": cell, "config": "tiny-hpcg",
                                  "traffic": "chain", "chips": 1,
                                  "why": "test"})
    spec["end_to_end"].append({"name": "tiny_calls", "unit": "count",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": [TINY_CELL]})
    for m in spec["per_layer"]:
        m["workloads"].append(TINY_CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry.from_file(tmp / "BENCHMARK.json", dirs=(tmp, BENCH_DIR))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    return tiny_registry(tmp), tmp / "cache"


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


def test_traced_run_reports_per_layer(tiny):
    res = run_tiny(tiny, trace=True)
    assert res["correct"] is True
    # no TPU plane on the CPU: the trace readers return nothing, the
    # plan's own count still reads
    assert set(res["metrics"]) == {"stored_bytes_ratio"}
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


def _unchanged(plan, sm):
    return lambda x: x


def _half_rows(plan, sm):
    return lambda x: plan(x).at[plan.n_rows // 2:].set(0.0)


def _one_answer_altered(plan, sm):
    import jax.numpy as jnp
    return lambda x: (lambda y: y.at[7].add(1e-3 * jnp.max(jnp.abs(y))))(
        plan(x))


@pytest.mark.parametrize("fault", [_unchanged, _half_rows,
                                   _one_answer_altered])
def test_broken_timed_path_is_not_correct(tiny, fault):
    res = run_tiny(tiny, plan_hook=fault)
    assert res["correct"] is False
    assert res["failed"] > 0


def test_lower_precision_control_is_not_correct(tiny):
    res = run_tiny(tiny, plan_hook=control.lower_precision_hook())
    assert res["correct"] is False
    assert res["checks"]["rel_err"]["value"] > 1e-4
