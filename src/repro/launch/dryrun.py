import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           ).strip()
"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, ``jax.jit(step).lower(...)
.compile()`` against the production mesh — 16x16 single-pod and 2x16x16
multi-pod — using ShapeDtypeStruct stand-ins (zero allocation). Records
``memory_analysis()`` (proves the per-device footprint), ``cost_analysis()``
(FLOPs/bytes for the roofline), and the collective schedule parsed from
the partitioned HLO, into ``results/dryrun/<arch>.<shape>.<mesh>.json``.

NOTE the XLA_FLAGS line above MUST run before any other import (jax locks
the device count at first init); tests/benchmarks never import this module.
(This also forces the docstring below the env setup and forbids
``from __future__ import annotations`` here.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun
"""
import argparse
import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import REGISTRY, get_config, cells_for
from repro.configs.base import ArchConfig, ShapeCell
from repro.dist.sharding import (batch_specs, cache_specs, dp_axes,
                                 param_specs)
from repro.models import (cache_spec, decode_step, init_params, n_blocks,
                          prefill)
from repro.train.optimizer import adamw_init
from repro.train.step import TrainConfig, make_train_step
from repro.launch.mesh import make_production_mesh

# -------------------------- input specs (deliverable) ----------------------


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def input_specs(cfg: ArchConfig, cell: ShapeCell, mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = cell.global_batch, cell.seq_len
    bs = batch_specs(cfg, mesh, global_batch=B)
    if cell.kind == "train":
        out = {"tokens": _sds((B, S), jnp.int32, mesh, bs["tokens"]),
               "labels": _sds((B, S), jnp.int32, mesh, bs["labels"])}
        if cfg.n_prefix:
            out["prefix_embeds"] = _sds((B, cfg.n_prefix, cfg.d_model),
                                        jnp.bfloat16, mesh,
                                        bs["prefix_embeds"])
        return out
    if cell.kind == "prefill":
        out = {"tokens": _sds((B, S), jnp.int32, mesh, bs["tokens"])}
        if cfg.n_prefix:
            out["prefix_embeds"] = _sds((B, cfg.n_prefix, cfg.d_model),
                                        jnp.bfloat16, mesh,
                                        bs["prefix_embeds"])
        return out
    # decode: one new token against an S-long cache
    caches_shape = jax.eval_shape(lambda: cache_spec(cfg, B, S))
    cspecs = cache_specs(cfg, mesh, caches_shape)
    caches = jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp),
        caches_shape, cspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return {
        "token": _sds((B, 1), jnp.int32, mesh, bs["tokens"]),
        "pos": jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(mesh, P())),
        "caches": caches,
    }


def _param_structs(cfg: ArchConfig, mesh):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, mesh, shapes)
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)), specs


# --------------------------- HLO collective parse --------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(tok_dtype: str, dims: str) -> int:
    if tok_dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[tok_dtype]


def collective_stats(hlo_text: str, body_trip: int = 1) -> dict:
    """Sum result-shape bytes of every collective op in the partitioned HLO.

    CPU-backend HLO dumps carry shapes on results only, so we account the
    result tensor (== operand size for all-reduce; == wire volume proxy for
    all-gather; reduce-scatter under-counts by the group factor — noted in
    EXPERIMENTS.md). Collectives whose op_name metadata places them inside
    a scan body (``/while/body``) execute ``body_trip`` times but appear
    once in the text — we multiply. Deeper nesting (depth >= 2: SSD chunk
    scan / blockwise attention) is recorded separately as a caveat count.
    """
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    depth2_bytes = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.search(r"=\s*((?:\([^)]*\)|\S+))\s+([a-z\-]+)\(", s)
        if not m:
            continue
        result, op = m.group(1), m.group(2)
        base = op.removesuffix("-start").removesuffix("-done")
        if base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        b = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(result))
        depth = s.count("while/body")
        mult = body_trip if depth >= 1 else 1
        if depth >= 2:
            depth2_bytes += b
        stats[base]["count"] += 1
        stats[base]["bytes"] += b * mult
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    stats["depth2_raw_bytes"] = depth2_bytes
    return stats


# ------------------------------- dry run ----------------------------------

def lower_cell(cfg: ArchConfig, cell: ShapeCell, mesh,
               train_cfg: "TrainConfig | None" = None,
               optimized: bool = False):
    """Build + lower the step function for one cell. Returns `lowered`.

    optimized=True applies the §Perf improvements (activation sharding
    constraints anchoring the scan carry + logits; see EXPERIMENTS.md).
    """
    act_dp = dp_axes(mesh) if optimized else None
    tc = train_cfg or TrainConfig(
        block_kv=2048 if cell.seq_len > 8192 else None,
        act_dp=act_dp)
    params, pspecs = _param_structs(cfg, mesh)
    ins = input_specs(cfg, cell, mesh)

    if cell.kind == "train":
        step = make_train_step(cfg, tc)
        opt_shapes = jax.eval_shape(adamw_init, params)
        opt = jax.tree.map(
            lambda s, sp: _sds(s.shape, s.dtype, mesh,
                               sp if s.ndim else P()),
            opt_shapes,
            {"m": pspecs, "v": pspecs, "count": P()},
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        state = {"params": params, "opt": opt}
        fn = jax.jit(step, donate_argnums=(0,))
        with mesh:
            return fn.lower(state, ins)
    if cell.kind == "prefill":
        def fn(params, tokens, prefix_embeds=None):
            return prefill(cfg, params, tokens, prefix_embeds,
                           block_kv=tc.block_kv, act_dp=act_dp)
        args = [params, ins["tokens"]]
        if cfg.n_prefix:
            args.append(ins["prefix_embeds"])
        with mesh:
            return jax.jit(fn).lower(*args)
    # decode
    def fn(params, token, pos, caches):
        return decode_step(cfg, params, token, pos, caches, act_dp=act_dp)
    with mesh:
        return jax.jit(fn, donate_argnums=(3,)).lower(
            params, ins["token"], ins["pos"], ins["caches"])


def run_cell(cfg: ArchConfig, cell: ShapeCell, multi_pod: bool,
             out_dir: Path, optimized: bool = False) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{cfg.name}.{cell.name}.{mesh_name}"
    if optimized:
        tag += ".opt"
    out_path = out_dir / f"{tag}.json"
    if out_path.exists():
        return json.loads(out_path.read_text())
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": cfg.name, "shape": cell.name, "mesh": mesh_name,
           "kind": cell.kind, "chips": int(np.prod(tuple(mesh.shape.values())))}
    rec["variant"] = "opt" if optimized else "base"
    try:
        lowered = lower_cell(cfg, cell, mesh, optimized=optimized)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ca = dict(compiled.cost_analysis())
        ma = compiled.memory_analysis()
        rec.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
            "memory": {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
                "output_bytes": getattr(ma, "output_size_in_bytes", 0),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
                "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
                "code_bytes": getattr(ma, "generated_code_size_in_bytes", 0),
            },
            "collectives": collective_stats(compiled.as_text(),
                                            body_trip=n_blocks(cfg)),
        })
    except Exception as e:  # a failure here is a bug in the system
        rec.update({"ok": False, "error": f"{type(e).__name__}: {e}"})
    rec["wall_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    args = ap.parse_args()

    archs = list(REGISTRY) if args.arch == "all" else args.arch.split(",")
    out_dir = Path(args.out)
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        for cell in cells_for(cfg):
            if args.shape != "all" and cell.name not in args.shape.split(","):
                continue
            meshes = {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]
            for mp in meshes:
                rec = run_cell(cfg, cell, mp, out_dir,
                               optimized=args.variant == "opt")
                status = "OK " if rec.get("ok") else "FAIL"
                n_ok += rec.get("ok", False)
                n_fail += not rec.get("ok", False)
                print(f"[{status}] {arch:24s} {cell.name:12s} "
                      f"{'multi' if mp else 'single':6s} "
                      f"flops={rec.get('flops', 0):.3e} "
                      f"coll={rec.get('collectives', {}).get('total_bytes', 0):.3e} "
                      f"wall={rec.get('wall_s')}s"
                      + ("" if rec.get("ok") else f"  {rec.get('error', '')[:120]}"),
                      flush=True)
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
