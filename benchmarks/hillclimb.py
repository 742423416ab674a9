"""§Perf hillclimbing (deliverable g): hypothesis -> change -> re-lower ->
measure, on the three selected cells.

Cells (selection rationale in EXPERIMENTS.md §Perf):
  A llama3-405b.train_4k       — flagship dense cell, largest absolute cost
  B granite-moe-3b-a800m.train_4k — worst roofline fraction / most
                                    collective-bound in the baseline table
  C deepseek-moe-16b.train_4k  — most representative of the paper's
                                  technique (MoE dispatch IS a sparse
                                  format problem: one-hot-MXU vs
                                  sort+segment, the paper's §IV reduce duel)

Iterations per cell:
  it0 baseline          (recorded dry-run, variant=base)
  it1 +act constraints  (variant=opt)
  it2 +grad reduce-scatter anchoring        [all cells]
  it2c sorted (AlphaSparse-style) dispatch  [cell C]
  it2b expert padding 40->48 for EP         [cell B]

Each iteration re-lowers + compiles on the production 16x16 mesh and
records flops / collective bytes / memory to results/hillclimb/*.json.

Run: REPRO_DRYRUN_DEVICES=512 PYTHONPATH=src python -m benchmarks.hillclimb
(must be a fresh process: forces 512 host devices).
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import dataclasses  # noqa: E402
import json  # noqa: E402
from pathlib import Path  # noqa: E402


def _record(tag, compiled, cfg, out_dir):
    from repro.launch.dryrun import collective_stats
    from repro.models import n_blocks
    ca = dict(compiled.cost_analysis())
    ma = compiled.memory_analysis()
    rec = {
        "tag": tag,
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "collectives": collective_stats(compiled.as_text(),
                                        body_trip=n_blocks(cfg)),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
    c = rec["collectives"]["total_bytes"]
    print(f"[{tag}] flops={rec['flops']:.3e} coll={c:.3e} "
          f"temp={rec['temp_bytes']:.3e}", flush=True)
    return rec


def _lower_train(cfg, cell, mesh, *, act: bool, grad_rs: bool,
                 bf16_gather: bool = False, seq_shard: bool = False):
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import dp_axes
    from repro.launch.dryrun import _param_structs, input_specs, _sds
    from repro.train.optimizer import adamw_init
    from repro.train.step import TrainConfig, make_train_step

    tc = TrainConfig(block_kv=2048 if cell.seq_len > 8192 else None,
                     act_dp=dp_axes(mesh) if act else None,
                     cast_params_bf16=bf16_gather, seq_shard=seq_shard)
    params, pspecs = _param_structs(cfg, mesh)
    ins = input_specs(cfg, cell, mesh)
    step = make_train_step(cfg, tc, grad_specs=pspecs if grad_rs else None)
    opt_shapes = jax.eval_shape(adamw_init, params)
    opt = jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp if s.ndim else P()),
        opt_shapes, {"m": pspecs, "v": pspecs, "count": P()},
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    state = {"params": params, "opt": opt}
    with mesh:
        return jax.jit(step, donate_argnums=(0,)).lower(state, ins)


def main():
    from repro.configs import get_config
    from repro.configs.base import SHAPE_CELLS
    from repro.launch.mesh import make_production_mesh

    out_dir = Path("results/hillclimb")
    mesh = make_production_mesh()
    train = SHAPE_CELLS[0]
    only = os.environ.get("REPRO_HILLCLIMB_ONLY", "").split(",")
    only = [o for o in only if o]

    def want(tag):
        done = (out_dir / f"{tag}.json").exists()
        return (not done) and (not only or any(o in tag for o in only))

    # ---- Cell A: llama3-405b train_4k ----
    cfg = get_config("llama3-405b")
    if want("A.llama.it2_grad_rs"):
        c = _lower_train(cfg, train, mesh, act=True, grad_rs=True).compile()
        _record("A.llama.it2_grad_rs", c, cfg, out_dir)
    if want("A.llama.it4_seq_parallel"):
        # iteration 4: sequence parallelism — residual stream's seq axis
        # sharded over model between layers; TP activation psums become
        # reduce-scatter/all-gather pairs (2.4x on granite; see §Perf)
        c = _lower_train(cfg, train, mesh, act=True, grad_rs=False,
                         seq_shard=True).compile()
        _record("A.llama.it4_seq_parallel", c, cfg, out_dir)
    if want("A.llama.it3_bf16_gather"):
        # hypothesis: remaining all-reduce/gather volume ~= 3 passes x
        # N x 4B == fp32 weight gathering; casting to bf16 BEFORE the FSDP
        # gather halves it (fp32 masters stay sharded in the optimizer)
        c = _lower_train(cfg, train, mesh, act=True, grad_rs=False,
                         bf16_gather=True).compile()
        _record("A.llama.it3_bf16_gather", c, cfg, out_dir)

    # ---- Cell B: granite-moe train_4k ----
    cfg = get_config("granite-moe-3b-a800m")
    if want("B.gmoe.it2_grad_rs"):
        c = _lower_train(cfg, train, mesh, act=True, grad_rs=True).compile()
        _record("B.gmoe.it2_grad_rs", c, cfg, out_dir)
    if want("B.gmoe.it3_pad_experts"):
        # hypothesis: 40 experts don't divide the 16-way model axis, so
        # expert compute replicates; padding to 48 (dead experts) enables
        # expert parallelism. FLOPs rise 48/40 = 1.2x but collectives drop.
        padded = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=48))
        c = _lower_train(padded, train, mesh, act=True,
                         grad_rs=True).compile()
        _record("B.gmoe.it3_pad_experts", c, padded, out_dir)

    # ---- Cell C: deepseek-moe train_4k ----
    cfg = get_config("deepseek-moe-16b")
    if want("C.dsmoe.it2_grad_rs"):
        c = _lower_train(cfg, train, mesh, act=True, grad_rs=True).compile()
        _record("C.dsmoe.it2_grad_rs", c, cfg, out_dir)
    if want("C.dsmoe.it3_sorted_dispatch"):
        # the paper's move: routing as a sparse-format problem — replace the
        # GShard one-hot dispatch einsum (ONEHOT_MXU-style) with
        # sort + capacity-buffer scatter (SORT/BIN + SEG-style)
        sorted_cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl="sorted"))
        c = _lower_train(sorted_cfg, train, mesh, act=True,
                         grad_rs=True).compile()
        _record("C.dsmoe.it3_sorted_dispatch", c, sorted_cfg, out_dir)
    if want("C.dsmoe.it4_sorted_bf16"):
        both = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl="sorted"))
        c = _lower_train(both, train, mesh, act=True, grad_rs=False,
                         bf16_gather=True).compile()
        _record("C.dsmoe.it4_sorted_bf16", c, both, out_dir)

    # ---- Cell B continued: combine padding with bf16 gather ----
    cfg = get_config("granite-moe-3b-a800m")
    if want("B.gmoe.it4_pad_bf16"):
        padded = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_experts=48))
        c = _lower_train(padded, train, mesh, act=True, grad_rs=False,
                         bf16_gather=True).compile()
        _record("B.gmoe.it4_pad_bf16", c, padded, out_dir)


if __name__ == "__main__":
    main()
