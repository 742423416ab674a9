"""Dry-run machinery on a small forced-device mesh (subprocess: the
512-device XLA flag must not leak into this test process)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.launch.dryrun import input_specs, lower_cell, collective_stats
from repro.models import n_blocks

cfg = get_config(sys.argv[1]).reduced()
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cell = ShapeCell("tiny_train", 32, 8, "train")
lowered = lower_cell(cfg, cell, mesh)
compiled = lowered.compile()
ca = dict(compiled.cost_analysis())
stats = collective_stats(compiled.as_text(), body_trip=n_blocks(cfg))
print(json.dumps({
    "flops": float(ca.get("flops", 0.0)),
    "collectives": stats,
    "arg_bytes": compiled.memory_analysis().argument_size_in_bytes,
}))
"""

DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.launch.dryrun import lower_cell

cfg = get_config(sys.argv[1]).reduced()
mesh = jax.make_mesh((4, 2), ("data", "model"))
cell = ShapeCell("tiny_decode", 64, 8, "decode")
compiled = lower_cell(cfg, cell, mesh).compile()
print(json.dumps({"ok": True,
                  "temp_bytes": compiled.memory_analysis().temp_size_in_bytes}))
"""


def _run(script, arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src")
    out = subprocess.run([sys.executable, "-c", script, arch],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["granite-3-2b", "jamba-v0.1-52b",
                                  "deepseek-moe-16b"])
def test_train_cell_lowers_on_multipod_mesh(arch):
    rec = _run(SCRIPT, arch)
    assert rec["flops"] > 0
    # SPMD partitioning must produce a real collective schedule
    assert rec["collectives"]["total_bytes"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["mamba2-1.3b", "qwen3-8b"])
def test_decode_cell_lowers(arch):
    rec = _run(DECODE_SCRIPT, arch)
    assert rec["ok"]


def test_collective_parser_units():
    from repro.launch.dryrun import collective_stats
    hlo = """
  %all-reduce.1 = f32[1024]{0} all-reduce(%x), replica_groups=[4,2]<=[8]
  %ag = bf16[2,512]{1,0} all-gather-start(%y), metadata={op_name="jit(f)/while/body/x"}
  %done = bf16[2,512]{1,0} all-gather-done(%ag)
  %other = f32[8]{0} add(%a, %b)
"""
    stats = collective_stats(hlo, body_trip=10)
    assert stats["all-reduce"]["bytes"] == 4096
    assert stats["all-gather"]["count"] == 1
    assert stats["all-gather"]["bytes"] == 2 * 512 * 2 * 10  # x body_trip
    assert stats["total_bytes"] == 4096 + 20480
