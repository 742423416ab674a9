"""Fused-combine megatile kernels vs. the kernel + jnp-scatter path.

Until this PR every generated Pallas kernel produced per-tile partials and
paid a second full pass over the output in plain ``jnp`` for the
SCATTER_RED combine. The fused variants absorb the combine into the
kernel's sequential grid iteration (revisited resident output block,
``tiles_per_step`` megatiles — the merge-path/CSR5 lineage) and this
benchmark measures the end-to-end SpMV win, combine included, plus the
mixed-precision storage axis (bf16 vals + int16 cols, fp32 accumulate).

Per family (the 4 regularity axes of the Figure 9 suite) it times, on the
Pallas backend (Mosaic on a TPU, the Pallas interpreter elsewhere):

* ``base``  — ``fuse_combine=False, tiles_per_step=1``: the historical
  kernel + jnp-scatter path;
* ``fused`` — no scatter pass (seg: resident y block; ELL: the row slab
  lands in y by one slice add) + megatile grid steps;
* ``bf16``  — the fused path with bf16/int16 storage (traffic halved).

Parity is checked against the dense float64 oracle before any timing
counts (fp32 tolerance for base/fused, bf16 tolerance for bf16).

Outputs ``BENCH_kernelfuse.json`` (schema: {scale, tiles_per_step,
families: {name: {base_s, fused_s, bf16_s, speedup, bf16_speedup,
storage_ratio, n_fused_steps, n_steps, nnz, max_rel_err_fused,
max_rel_err_bf16, parity_ok}}, n_speedup_ok, wall_seconds}) plus the
scaffold CSV lines.

``--smoke`` runs n=1024 matrices with a wall-clock guard (CI tier-1
adjacent): exit 1 on parity failure, exit 3 on guard breach.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

import jax.numpy as jnp

from repro.core.graph import run_graph
from repro.core.kernel_builder import build_program
from repro.dist.spmv import default_shard_graph

try:                      # runnable as module (-m benchmarks.kernel_fuse) ...
    from .common import SCALE, emit, scaled_families, time_fn
except ImportError:       # ... or as a plain script from the repo root
    from common import SCALE, emit, scaled_families, time_fn

SMOKE_WALL_SECONDS = 300.0   # --smoke guard: CI fails loudly on a hang
SPEEDUP_TARGET = 1.5


def fuse_families(smoke: bool) -> dict:
    # smoke uses n=1024: large enough that grid-step count (what the
    # megatile amortises) dominates the interpret-mode timing, small
    # enough for the CI wall guard
    if smoke:
        return scaled_families(1024)
    s = {"quick": 1, "full": 4}.get(SCALE, 1)
    return scaled_families(2048 * s)


def bench_one(name: str, m, tiles: int, repeats: int) -> dict:
    meta = run_graph(m, default_shard_graph(m))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(m.n_cols).astype(np.float32))
    oracle = m.spmv_dense_oracle(np.asarray(x))
    scale = float(np.abs(oracle).max()) + 1e-30

    base = build_program(meta, backend="pallas",
                         fuse_combine=False, tiles_per_step=1)
    fused = build_program(meta, backend="pallas",
                          fuse_combine=True, tiles_per_step=tiles)
    bf16 = build_program(meta, backend="pallas",
                         fuse_combine=True, tiles_per_step=tiles,
                         storage_dtype="bfloat16")

    err_fused = float(np.abs(np.asarray(fused(x)) - oracle).max()) / scale
    err_bf16 = float(np.abs(np.asarray(bf16(x)) - oracle).max()) / scale
    err_base = float(np.abs(np.asarray(base(x)) - oracle).max()) / scale
    parity_ok = bool(err_base <= 1e-5 and err_fused <= 1e-5
                     and err_bf16 <= 3e-2)

    # min-reduce: ratios of minima are far more stable than ratios of
    # medians on noisy shared runners, and the speedup is the headline
    base_s = time_fn(base, x, repeats=repeats, warmup=2, reduce="min")
    fused_s = time_fn(fused, x, repeats=repeats, warmup=2, reduce="min")
    bf16_s = time_fn(bf16, x, repeats=repeats, warmup=2, reduce="min")
    speedup = base_s / max(fused_s, 1e-12)
    n_steps = len(fused.spec["steps"])
    n_fused = sum(bool(s.get("fused")) for s in fused.spec["steps"])
    storage_ratio = bf16.stored_bytes / max(base.stored_bytes, 1)

    emit(f"kernelfuse_{name}_base", base_s * 1e6, "combine=jnp-scatter")
    emit(f"kernelfuse_{name}_fused", fused_s * 1e6,
         f"K={tiles} speedup={speedup:.2f}x fused_steps={n_fused}/{n_steps}")
    emit(f"kernelfuse_{name}_bf16", bf16_s * 1e6,
         f"storage_ratio={storage_ratio:.2f} err={err_bf16:.1e}")
    return {"base_s": base_s, "fused_s": fused_s, "bf16_s": bf16_s,
            "speedup": speedup,
            "bf16_speedup": base_s / max(bf16_s, 1e-12),
            "storage_ratio": storage_ratio,
            "n_fused_steps": n_fused, "n_steps": n_steps, "nnz": m.nnz,
            "max_rel_err_fused": err_fused, "max_rel_err_bf16": err_bf16,
            "parity_ok": parity_ok}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="n=1024 matrices + wall-clock guard (CI)")
    ap.add_argument("--tiles", type=int, default=8,
                    help="tiles_per_step of the fused path (default 8)")
    ap.add_argument("--out", default="BENCH_kernelfuse.json")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    repeats = 7
    families = {}
    for name, m in fuse_families(args.smoke).items():
        families[name] = bench_one(name, m, args.tiles, repeats)
    wall = time.perf_counter() - t0

    n_ok = sum(r["speedup"] >= SPEEDUP_TARGET for r in families.values())
    out = {"scale": "smoke" if args.smoke else SCALE,
           "tiles_per_step": args.tiles, "families": families,
           "n_speedup_ok": n_ok, "speedup_target": SPEEDUP_TARGET,
           "wall_seconds": wall}
    Path(args.out).write_text(json.dumps(out, indent=2))
    print(f"[kernel_fuse] K={args.tiles} {n_ok}/{len(families)} families "
          f">={SPEEDUP_TARGET}x, wall={wall:.1f}s -> {args.out}", flush=True)

    if not all(r["parity_ok"] for r in families.values()):
        print("[kernel_fuse] FAIL: fused/bf16 parity vs dense oracle",
              file=sys.stderr)
        return 1
    if args.smoke and wall > SMOKE_WALL_SECONDS:
        print(f"[kernel_fuse] FAIL: smoke wall {wall:.0f}s > "
              f"{SMOKE_WALL_SECONDS:.0f}s guard", file=sys.stderr)
        return 3
    if n_ok < 3:
        # the headline claim: >= 1.5x on at least 3 of the 4 families.
        # Smoke (CI, noisy shared runners) warns loudly but does not
        # fail the build; full-scale runs gate hard.
        print(f"[kernel_fuse] WARNING: only {n_ok}/4 families met the "
              f"{SPEEDUP_TARGET}x fused-combine target", file=sys.stderr)
        if not args.smoke:
            return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
