"""The per-layer metrics the program measures from inside (its
``repro.telemetry`` spans and counters), on the CPU at the tests' tiny
size: reported by a traced run, and ``jit_compiles`` reading 0 once a run
finds every executable in the persistent compilation cache."""
import json
import os
import subprocess
import sys

from bench.lib.registry import ROOT
from bench.tests.test_harness import TINY_CELL, tiny_registry

PROGRAM_METRICS = {"plan_load_s", "jit_load_s", "jit_compiles"}

# one traced run of the tiny cell in a fresh process, so that the
# program's table and JAX's in-memory caches hold that run alone
RUN = """
import sys
from pathlib import Path
sys.path.insert(0, {root!r})
from bench.lib.registry import BENCH_DIR, Registry
from bench.run import main
tmp = Path({tmp!r})
registry = Registry.from_file(tmp / "BENCHMARK.json", dirs=(tmp, BENCH_DIR))
sys.exit(main(["--workload", {cell!r}, "--seed", "11", "--seconds", "0.3",
               "--trace", "1"], registry=registry, require_chip=False,
              cache_dir=tmp / "cache"))
"""


def _traced_run(tmp) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-c", RUN.format(root=str(ROOT), tmp=str(tmp),
                                          cell=TINY_CELL)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_program_metrics_reported_and_warm_run_compiles_nothing(tmp_path):
    tiny_registry(tmp_path)
    first = _traced_run(tmp_path)        # searches, stores, compiles
    assert first["correct"] is True
    m = first["metrics"]
    assert PROGRAM_METRICS <= set(m)
    assert m["plan_load_s"]["unit"] == "s" and m["plan_load_s"]["value"] > 0
    assert m["jit_load_s"]["value"] > 0
    assert m["jit_compiles"]["unit"] == "count"
    assert m["jit_compiles"]["value"] > 0
    # the second run loads the stored plan and every executable from the
    # persistent cache the first one filled
    second = _traced_run(tmp_path)
    assert second["correct"] is True
    m = second["metrics"]
    assert m["plan_load_s"]["value"] > 0
    assert m["jit_compiles"]["value"] == 0
