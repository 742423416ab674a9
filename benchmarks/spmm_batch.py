"""Fused multi-RHS SpMM vs. the legacy vmap-of-SpMV serving path.

The serving hot path used to batch decode by vmapping a 1-RHS program over
the B activation columns — re-streaming the format arrays B times. The
fused SpMM path hands the program one (n_cols, B) tile; this benchmark
measures the win at the decode batch size on the Pallas backend
(Mosaic on a TPU, the Pallas interpreter elsewhere; relative timings
reflect the B-fold reduction in grid steps / format streams).

Four matrix families (the regularity axes of the paper's Figure 9 suite):
``banded`` (stencil-regular), ``uniform`` (random-regular), ``powerlaw``
(scale-free irregular) and ``hyb`` (HYB-friendly bimodal). Each family is
checked for parity first: the fused (n_rows, B) output must match a
per-column loop of the same program to 1e-5 before its timing counts.

Outputs ``BENCH_spmm.json`` (schema: {scale, batch, families: {name:
{vmap_s, fused_s, speedup, max_rel_err, nnz, design}}, n_speedup_ok})
plus the scaffold's CSV lines.

``--smoke`` runs tiny matrices with a wall-clock guard (CI tier-1
adjacent): exit 1 on parity failure, exit 3 on guard breach.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.graph import run_graph
from repro.core.kernel_builder import build_program
from repro.dist.spmv import default_shard_graph

try:                      # runnable as module (-m benchmarks.spmm_batch) ...
    from .common import SCALE, emit, scaled_families, smoke_families, time_fn
except ImportError:       # ... or as a plain script from the repo root
    from common import SCALE, emit, scaled_families, smoke_families, time_fn

SMOKE_WALL_SECONDS = 300.0   # --smoke guard: CI fails loudly on a hang


def spmm_families(smoke: bool) -> dict:
    """The 4 benchmark matrix families at smoke / quick / full scale."""
    if smoke:
        return smoke_families()
    s = {"quick": 1, "full": 4}.get(SCALE, 1)
    return scaled_families(1024 * s)


def bench_one(name: str, m, batch: int, repeats: int) -> dict:
    graph = default_shard_graph(m)
    meta = run_graph(m, graph)
    prog = build_program(meta, backend="pallas")
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((m.n_cols, batch)).astype(np.float32))
    Xrows = jnp.asarray(np.asarray(X).T)          # legacy (B, n_cols) layout

    # --- parity: fused output vs a per-column loop of the same program ---
    fused = np.asarray(prog(X))
    percol = np.stack([np.asarray(prog(X[:, b])) for b in range(batch)],
                      axis=1)
    scale = float(np.abs(percol).max()) + 1e-30
    max_rel_err = float(np.abs(fused - percol).max()) / scale
    parity_ok = bool(max_rel_err <= 1e-5)

    # --- timings: min wall seconds over repeats of a blocking call ---
    def vmap_path(xb):
        return jax.vmap(lambda xi: prog(xi))(xb)

    vmap_s = time_fn(vmap_path, Xrows, repeats=repeats, warmup=1)
    fused_s = time_fn(prog, X, repeats=repeats, warmup=1)
    speedup = vmap_s / max(fused_s, 1e-12)
    design = graph.label()
    emit(f"spmm_{name}_vmap", vmap_s * 1e6, f"B={batch}")
    emit(f"spmm_{name}_fused", fused_s * 1e6,
         f"B={batch} speedup={speedup:.2f}x parity={parity_ok}")
    return {"vmap_s": vmap_s, "fused_s": fused_s, "speedup": speedup,
            "max_rel_err": max_rel_err, "parity_ok": parity_ok,
            "nnz": m.nnz, "design": design}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny matrices + wall-clock guard (CI)")
    ap.add_argument("--batch", type=int, default=8,
                    help="decode batch B (default 8)")
    ap.add_argument("--out", default="BENCH_spmm.json")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    repeats = 2 if args.smoke else 3
    families = {}
    for name, m in spmm_families(args.smoke).items():
        families[name] = bench_one(name, m, args.batch, repeats)
    wall = time.perf_counter() - t0

    n_ok = sum(r["speedup"] >= 2.0 for r in families.values())
    out = {"scale": "smoke" if args.smoke else SCALE, "batch": args.batch,
           "families": families, "n_speedup_ok": n_ok,
           "wall_seconds": wall}
    Path(args.out).write_text(json.dumps(out, indent=2))
    print(f"[spmm_batch] B={args.batch} {n_ok}/{len(families)} families "
          f">=2x, wall={wall:.1f}s -> {args.out}", flush=True)

    if not all(r["parity_ok"] for r in families.values()):
        print("[spmm_batch] FAIL: fused/per-column parity", file=sys.stderr)
        return 1
    if args.smoke and wall > SMOKE_WALL_SECONDS:
        print(f"[spmm_batch] FAIL: smoke wall {wall:.0f}s > "
              f"{SMOKE_WALL_SECONDS:.0f}s guard", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
