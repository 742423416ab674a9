"""Format & Kernel Generator (paper §V): project an executed Operator Graph
(i.e. a MetadataSet) onto a concrete format (arrays) + kernel (callable).

The paper splices CUDA source fragments into a skeleton. Pallas is already a
metaprogramming layer, so our "kernel fragments" are compile-time Python
closures selected by the implementing-stage operators (DESIGN.md D2), and the
"Adapter" fragments become layout conversions between tile partials and the
output vector.

Two backends share one plan:
  * ``jax``    — pure-jnp program (the oracle; also what we time on CPU).
  * ``pallas`` — the TPU kernels in ``repro.kernels`` (Mosaic on a TPU, the
    Pallas interpreter elsewhere — ``repro.runtime.resolve_interpret``).

Since the compile-API redesign the generator is two explicit stages:

1. ``plan_format(meta)`` packs the format arrays (``fmt``: name -> array)
   and emits a JSON-able *kernel spec* — the complete static description of
   the generated program (step kinds, column models, combine plans,
   geometry). Nothing the kernel needs lives in Python closures anymore.
2. ``build_kernel(spec, backend)`` interprets the spec into the
   runnable ``fn(fmt, x)``.

That split is what makes ``repro.SpmvPlan`` a portable artifact: the spec
plus the ``fmt`` arrays round-trip through an npz file and rebuild the exact
same program on load (``repro.api``), and the distributed layer can re-pack
``fmt`` into stacked shard_map operands (``repro.dist.spmv``).

Generated programs are multi-RHS aware: calling a program with a 2-D x of
shape (n_cols, B) dispatches to the fused SpMM kernel variants (format
arrays stream once for all B right-hand sides) and returns (n_rows, B);
a 1-D x takes the classic SpMV path. The dispatch happens at trace time
(``x.ndim`` is static), so both ranks jit-compile independently.

Model-Driven Format Compression (``compress.py``) runs here: fitted arrays
are elided from the stored format and recomputed in-kernel, and an affine
rowmap is elided too (the rows are ``b0 + arange``).

Fused combine (pallas backend): when a step's output rows are provably
contiguous — affine slope-1 rowmap for ELL, per-tile ascending row runs
for the seg family — the step is marked ``fused`` and the post-hoc
``jnp`` scatter pass disappears: an ELL step's row slab lands in y by one
slice add, and the seg kernel accumulates into a resident y block.
``tiles_per_step`` format tiles are processed per grid step (megatile).

Mixed-precision storage: ``storage_dtype="bfloat16"`` stores vals as bf16
(and explicit cols arrays as int16 when ``n_cols`` fits), recorded per
step under ``"store"``; kernels upcast in-register and accumulate fp32.
Both knobs come from the MetadataSet (SET_RESOURCES — searchable) or the
explicit ``plan_format``/``build_program`` overrides (Target-driven).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.runtime import resolve_interpret

from . import compress
from .deprecation import warn_once
from .metadata import (Block, DiagLayout, EllTileLayout, MetadataSet,
                       SegTileLayout)

__all__ = ["SpmvProgram", "build_program", "build_spmv", "plan_format",
           "build_kernel", "register_layout_planner", "spec_slots",
           "SPEC_VERSION"]

SPEC_VERSION = 2

# explicit cols arrays narrow to int16 when every column index fits
_INT16_MAX_COLS = 32767


@dataclasses.dataclass
class SpmvProgram:
    """A generated SpMV/SpMM program: format arrays + kernel spec + report.

    ``__call__`` dispatches on ``x.ndim``: a (n_cols,) vector runs the
    1-RHS SpMV kernels, a (n_cols, B) tile runs the fused multi-RHS SpMM
    variants (one format stream for all B columns) and yields (n_rows, B).

    ``fmt`` (the packed format arrays) and ``spec`` (the JSON-able kernel
    description) fully determine the program — ``fn`` is just
    ``build_kernel(spec, ...)`` jitted, and carries no baked-in constants.
    """

    # explicit batching protocol (see serve.sparse_linear): callers check
    # this instead of duck-typing on program internals
    supports_batch = True

    n_rows: int
    n_cols: int
    nnz: int
    fmt: dict                     # name -> jnp array (the stored format)
    fn: Callable                  # fn(fmt, x) -> y  (jitted)
    descriptor: dict              # structural report (kernels, combines, fits)
    spec: dict = None             # JSON-able kernel spec (see plan_format)
    backend: str = "jax"

    def __call__(self, x):
        return self.fn(self.fmt, x)

    @property
    def stored_bytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self.fmt.values())

    @property
    def padded_nnz(self) -> int:
        return self.descriptor["padded_nnz"]

    def flops(self) -> int:
        return 2 * self.nnz  # useful flops; padding waste is padded_nnz-based


def _col_model_expr(kind: str, params, n: int, shape):
    """Recompute an elided int array inside the kernel (jnp, no exceptions)."""
    i = jnp.arange(int(n), dtype=jnp.int32)
    if kind == "linear":
        a, b = params
        v = a * i + b
    elif kind == "step":
        a, b, k = params
        v = a * (i // k) + b
    else:
        a, b, c, p = params
        v = a * (i % p) + c * (i // p) + b
    return v.reshape(tuple(shape))


def materialize_cols(colspec: dict, fmt: dict) -> np.ndarray:
    """Host-side column-index array for a spec step (array or fitted model).

    Used by the distributed operand-packing path, which must materialize
    model-elided arrays to pass them as shard_map operands.
    """
    if colspec["mode"] == "array":
        return np.asarray(fmt[colspec["key"]])
    return np.asarray(_col_model_expr(colspec["model"], colspec["params"],
                                      colspec["n"], colspec["shape"]))


def _plan_ell_block(bi: int, block: Block, fmt: dict,
                    steps: list, reports: list, do_compress: bool):
    """Plan one ELL-layout block: one spec step per width bucket."""
    layout: EllTileLayout = block.layout
    for ki, bucket in enumerate(layout.buckets):
        key = f"b{bi}k{ki}"
        fmt[f"{key}_vals"] = jnp.asarray(bucket.vals)
        rep = {"kernel": "ell", "width": bucket.width,
               "tiles": bucket.n_tiles, "tile_rows": bucket.tile_rows}

        # --- model-driven compression: cols ---
        col_model = compress.fit_array(bucket.cols) if do_compress else None
        if col_model is not None and col_model.n_exceptions == 0:
            rep["cols"] = f"elided({col_model.kind})"
            colspec = {"mode": "model", "model": col_model.kind,
                       "params": [int(p) for p in col_model.params],
                       "n": int(np.prod(bucket.cols.shape)),
                       "shape": [int(s) for s in bucket.cols.shape]}
        else:
            fmt[f"{key}_cols"] = jnp.asarray(bucket.cols)
            colspec = {"mode": "array", "key": f"{key}_cols"}

        # --- model-driven compression: rowmap -> combine upgrade ---
        affine = compress.affine_rowmap(bucket.rowmap) if do_compress else None
        if affine is not None and affine[0] == 1:
            # tile i owns rows [b0 + i*R, b0 + (i+1)*R): GRID_ACC and
            # scatter build the same program (see _run_ell_step)
            _, b0 = affine
            nv = int((bucket.rowmap.ravel() >= 0).sum())
            rep["combine"] = "scatter(affine)"
            rep["rowmap"] = "elided(linear)"
            combspec = {"mode": "affine", "b0": int(b0), "nv": nv}
        else:
            if block.reduce.combine == "grid_acc":
                rep["combine"] = "scatter(grid_acc-fallback: rowmap not affine)"
            else:
                rep["combine"] = "scatter"
            fmt[f"{key}_rowmap"] = jnp.asarray(bucket.rowmap)
            combspec = {"mode": "rowmap", "key": f"{key}_rowmap"}

        steps.append({"kind": "ell", "key": key, "cols": colspec,
                      "combine": combspec, "report": rep})
        reports.append(rep)


def _plan_seg_block(bi: int, block: Block, fmt: dict, steps: list,
                    reports: list, do_compress: bool):
    layout: SegTileLayout = block.layout
    key = f"b{bi}s"
    fmt[f"{key}_vals"] = jnp.asarray(layout.vals)
    rep = {"kernel": block.reduce.kind, "tiles": layout.n_tiles,
           "seg_rows": layout.seg_rows, "combine": "scatter"}
    rows_sorted = False
    if block.reduce.kind == "gmem_atom":
        # GMEM_ATOM_RED stores the global row stream directly (Merge/COO
        # style): no rowmap/descriptor arrays, no in-kernel row decode.
        T = layout.vals.shape[0]
        rows_global = np.take_along_axis(
            layout.rowmap, layout.local_row.reshape(T, -1), axis=1)
        fmt[f"{key}_rows"] = jnp.asarray(rows_global.astype(np.int32))
        # without converting-stage reordering the row stream stays sorted,
        # enabling the fast sorted-segment reduction
        rows_sorted = bool(np.all(np.diff(rows_global.ravel()) >= 0))
        rep["rows_sorted"] = rows_sorted
        # pallas fallback (no TPU atomics) still needs the descriptor path
        fmt[f"{key}_rowmap"] = jnp.asarray(layout.rowmap)
        fmt[f"{key}_local"] = jnp.asarray(layout.local_row)
        fmt[f"{key}_end"] = jnp.asarray(layout.seg_end)
    else:
        fmt[f"{key}_rowmap"] = jnp.asarray(layout.rowmap)
        if block.reduce.kind == "onehot_mxu":
            fmt[f"{key}_local"] = jnp.asarray(layout.local_row)
        else:  # seg_scan consumes the CSR5-style segment descriptor
            fmt[f"{key}_end"] = jnp.asarray(layout.seg_end)
    col_model = compress.fit_array(layout.cols) if do_compress else None
    if col_model is not None and col_model.n_exceptions == 0:
        rep["cols"] = f"elided({col_model.kind})"
        colspec = {"mode": "model", "model": col_model.kind,
                   "params": [int(p) for p in col_model.params],
                   "n": int(np.prod(layout.cols.shape)),
                   "shape": [int(s) for s in layout.cols.shape]}
    else:
        fmt[f"{key}_cols"] = jnp.asarray(layout.cols)
        colspec = {"mode": "array", "key": f"{key}_cols"}
    steps.append({"kind": "seg", "key": key, "reduce": block.reduce.kind,
                  "seg_rows": int(layout.seg_rows),
                  "rows_sorted": rows_sorted, "cols": colspec,
                  "report": rep})
    reports.append(rep)


def _plan_dia_block(bi: int, block: Block, fmt: dict, steps: list,
                    reports: list, do_compress: bool):
    """Plan one diagonal-layout block: one ``dia`` step, one array."""
    from repro.kernels.dia_spmv import geometry  # lazy: core stays light
    layout: DiagLayout = block.layout
    key = f"b{bi}d"
    fmt[f"{key}_vals"] = jnp.asarray(layout.vals)
    n_diag = len(layout.offsets)
    slots = n_diag * layout.n_rows
    pad_left, x_rows = geometry(layout.n_rows, layout.offsets)
    rep = {"kernel": "dia", "diagonals": n_diag,
           "fill": round(block.nnz / max(slots, 1), 4), "combine": "direct"}
    steps.append({"kind": "dia", "key": key, "offsets": list(layout.offsets),
                  "n_rows": layout.n_rows, "pad_left": pad_left,
                  "x_rows": x_rows, "slots": slots, "report": rep})
    reports.append(rep)


# Layout -> spec-step planner dispatch. Keyed on the layout *type* so an
# out-of-tree operator that packs its own layout class can register a
# planner (and a matching spec-step interpreter) without editing core:
# ``register_layout_planner(MyLayout)(my_planner)``. The planner signature
# matches ``_plan_ell_block``: (bi, block, fmt, steps, reports, compress).
_LAYOUT_PLANNERS: dict[type, Callable] = {}


def register_layout_planner(layout_cls: type, *, replace: bool = False):
    """Register a format planner for a custom layout type (see
    ``repro.design``: the open half of the Format & Kernel Generator)."""
    def deco(fn: Callable) -> Callable:
        if layout_cls in _LAYOUT_PLANNERS and not replace:
            raise ValueError(f"planner for {layout_cls.__name__} already "
                             "registered; pass replace=True to override")
        _LAYOUT_PLANNERS[layout_cls] = fn
        return fn
    return deco


register_layout_planner(EllTileLayout)(_plan_ell_block)
register_layout_planner(SegTileLayout)(_plan_seg_block)
register_layout_planner(DiagLayout)(_plan_dia_block)


def _contiguous_rowmap(rm: np.ndarray) -> bool:
    """True when every tile's used slots are a prefix ascending by 1 from
    slot 0 (rowmap[t, m] = rowmap[t, 0] + m) — the precondition for the
    fused seg combine (dense accumulate at r0 instead of a scatter)."""
    used = rm >= 0
    if not used.any():
        return True
    prefix_ok = bool(np.all(used[:, 1:] <= used[:, :-1]))
    idx = np.arange(rm.shape[1])
    r0 = np.where(used[:, 0], rm[:, 0], 0)
    vals_ok = bool(np.all(np.where(used, rm == r0[:, None] + idx[None, :],
                                   True)))
    return prefix_ok and vals_ok


def _finalize_steps(fmt: dict, steps: list, n_cols: int, storage_dtype: str,
                    fuse_combine: bool) -> None:
    """Post-planner pass: mark fused-combine steps and narrow storage.

    Runs centrally (not in the per-layout planners) so registered custom
    planners keep their signature; unknown step kinds are left untouched.
    """
    for step in steps:
        key = step["key"]
        if step["kind"] in ("ell", "seg"):
            step["slots"] = int(np.prod(fmt[f"{key}_vals"].shape))
        if step["kind"] == "ell":
            # affine slope-1 rowmap: tile i owns rows [b0+i*R, b0+(i+1)*R)
            # -> the row slab lands in y by one slice add, no scatter
            fused = bool(fuse_combine
                         and step["combine"]["mode"] == "affine")
            step["fused"] = fused
            if fused:
                step["report"]["combine"] = "fused(slice-add)"
        elif step["kind"] == "seg":
            rm = np.asarray(fmt[f"{key}_rowmap"])
            if fuse_combine and rm.size and _contiguous_rowmap(rm):
                r0 = np.where(rm[:, 0] >= 0, rm[:, 0], 0).astype(np.int32)
                fmt[f"{key}_r0"] = jnp.asarray(r0)
                step["fused"] = True
                # static slab size for the fused kernel's resident y block
                step["fused_rows"] = int(r0.max()) + int(step["seg_rows"])
                step["report"]["combine"] = "fused(carry)"
            else:
                step["fused"] = False
        elif step["kind"] != "dia":
            continue
        if storage_dtype == "bfloat16":
            store = {"vals": "bfloat16"}
            fmt[f"{key}_vals"] = jnp.asarray(fmt[f"{key}_vals"],
                                             jnp.bfloat16)
            cspec = step.get("cols")   # a dia step stores no columns
            if (cspec and cspec["mode"] == "array"
                    and n_cols <= _INT16_MAX_COLS):
                fmt[cspec["key"]] = jnp.asarray(fmt[cspec["key"]], jnp.int16)
                store["cols"] = "int16"
            step["store"] = store
            step["report"]["store"] = "+".join(
                f"{k}:{v}" for k, v in sorted(store.items()))


def plan_format(meta: MetadataSet, do_compress: bool = True, *,
                storage_dtype: str = None, tiles_per_step: int = None,
                fuse_combine: bool = True) -> tuple[dict, dict]:
    """Stage 1: pack format arrays and emit the JSON-able kernel spec.

    ``storage_dtype`` / ``tiles_per_step`` default to the MetadataSet's
    SET_RESOURCES decisions; pass them explicitly to override (the
    ``Target.dtype`` plumbing in ``repro.compile``). ``fuse_combine=False``
    disables the in-kernel combine (benchmark baseline: the historical
    kernel + jnp-scatter path).
    """
    for b in meta.blocks:
        if b.layout is None or b.reduce is None:
            raise ValueError("metadata not fully designed: run mapping and "
                             "implementing operators first")
    sd = storage_dtype or getattr(meta, "storage_dtype", "float32")
    if sd not in ("float32", "bfloat16"):
        raise ValueError(f"unsupported storage_dtype {sd!r} "
                         "(float32 | bfloat16)")
    kts = int(tiles_per_step or getattr(meta, "tiles_per_step", 1) or 1)
    fmt: dict = {}
    steps: list = []
    reports: list = []
    for bi, block in enumerate(meta.blocks):
        planner = _LAYOUT_PLANNERS.get(type(block.layout))
        if planner is None:
            raise ValueError(
                f"no format planner registered for layout type "
                f"{type(block.layout).__name__}; register one with "
                "repro.core.kernel_builder.register_layout_planner")
        planner(bi, block, fmt, steps, reports, do_compress)
    _finalize_steps(fmt, steps, int(meta.n_cols), sd, fuse_combine)
    spec = {"version": SPEC_VERSION,
            "n_rows": int(meta.n_rows), "n_cols": int(meta.n_cols),
            "nnz": int(meta.nnz), "padded_nnz": int(meta.padded_nnz()),
            "tiles_per_step": max(kts, 1), "storage_dtype": sd,
            "history": list(meta.history), "steps": steps}
    return fmt, spec


# Recomputed column indices feed only the gather.
_col_model = telemetry.device_call(
    scope="spmv.gather", static_argnums=(0, 1, 2, 3))(_col_model_expr)


def _step_cols(step: dict, fmt: dict):
    """A step's column indices: the stored array, or the fitted model's
    recomputation."""
    cspec = step["cols"]
    if cspec["mode"] == "array":
        return fmt[cspec["key"]]
    return _col_model(cspec["model"], tuple(cspec["params"]), cspec["n"],
                      tuple(cspec["shape"]))


# The combines: each a call under the ``spmv.combine`` scope, so its
# device ops say so (telemetry.device_call).

@telemetry.device_call(scope="spmv.combine")
def _scatter_rows(y, rowmap, partial):
    """y[rowmap] += partial; slots with rowmap < 0 hold no row."""
    rm = rowmap.reshape(-1)
    safe = jnp.where(rm >= 0, rm, y.shape[0])
    return y.at[safe].add(partial.reshape((-1,) + y.shape[1:]), mode="drop")


@telemetry.device_call(scope="spmv.combine", static_argnums=(2, 3, 4))
def _place_rows(y, partial, b0: int, nv: int, fused: bool):
    """y[b0:b0 + nv] += the first nv rows of partial: a dense slice add
    when the step is fused, else a scatter at b0 + arange(nv)."""
    flat = partial.reshape((-1,) + y.shape[1:])[:nv]
    if fused:
        return y.at[b0:b0 + nv].add(flat)
    return y.at[b0 + jnp.arange(nv, dtype=jnp.int32)].add(flat)


@telemetry.device_call(scope="spmv.combine")
def _add_slab(y, slab):
    return y + slab


@telemetry.device_call(scope="spmv.combine", static_argnums=(3,))
def _segment_add(y, prod, rows, rows_sorted: bool):
    """y[rows] += prod, as one segmented reduction."""
    return y + jax.ops.segment_sum(
        prod.reshape((-1,) + y.shape[1:]), rows, num_segments=y.shape[0],
        indices_are_sorted=rows_sorted)


def _run_ell_step(step: dict, fmt: dict, x, y, n_rows: int,
                  backend: str, interpret: bool, tiles_per_step: int = 1):
    rhs = x.shape[1:]
    vals = fmt[f"{step['key']}_vals"]
    cols = _step_cols(step, fmt)
    comb = step["combine"]
    if backend == "pallas":
        from repro.kernels import ops as kops  # lazy: keeps core importable
        op = kops.ell_spmm if rhs else kops.ell_spmv
        partial = op(vals, cols, x, tiles_per_step=tiles_per_step,
                     interpret=interpret)
    else:
        from repro.kernels import ref as kref
        op = kref.ell_spmm_ref if rhs else kref.ell_spmv_ref
        partial = op(vals, cols, x)
    if comb["mode"] == "rowmap":
        return _scatter_rows(y, fmt[comb["key"]], partial)
    return _place_rows(y, partial, comb["b0"], comb["nv"],
                       bool(step.get("fused")))


def _run_seg_step(step: dict, fmt: dict, x, y, n_rows: int,
                  backend: str, interpret: bool, tiles_per_step: int = 1):
    rhs = x.shape[1:]
    key = step["key"]
    kind = step["reduce"]
    vals = fmt[f"{key}_vals"]
    cols = _step_cols(step, fmt)
    if kind == "gmem_atom" and backend != "pallas":
        # GMEM_ATOM_RED: one global reduction of the product stream; rows
        # stored directly in the format (padded entries carry val=0 and a
        # valid row -> no masking).
        from repro.kernels import ref as kref
        v = vals.astype(jnp.float32)
        prod = (v[..., None] if rhs else v) * kref.gather(x, cols)
        return _segment_add(y, prod, fmt[f"{key}_rows"].reshape(-1),
                            bool(step.get("rows_sorted", False)))
    local = fmt.get(f"{key}_local")
    seg_end = fmt.get(f"{key}_end")
    seg_rows = step["seg_rows"]
    if backend == "pallas":
        from repro.kernels import ops as kops
        pk = "seg_scan" if kind == "gmem_atom" else kind
        if step.get("fused") and f"{key}_r0" in fmt:
            # fused carry-last-segment kernel: straddled rows finish
            # in-kernel on the resident y block — no scatter pass
            op = kops.seg_spmm_fused if rhs else kops.seg_spmv_fused
            slab = op(vals, cols, local, seg_end, fmt[f"{key}_r0"], x,
                      seg_rows, n_rows=n_rows,
                      n_out=step.get("fused_rows", n_rows), mode=pk,
                      tiles_per_step=tiles_per_step, interpret=interpret)
            return _add_slab(y, slab)
        op = kops.seg_spmm if rhs else kops.seg_spmv
        partial = op(vals, cols, local, seg_end, x,
                     seg_rows, mode=pk, interpret=interpret)
    else:
        from repro.kernels import ref as kref
        op = kref.seg_spmm_ref if rhs else kref.seg_spmv_ref
        partial = op(vals, cols, local, seg_end, x, seg_rows, mode=kind)
    return _scatter_rows(y, fmt[f"{key}_rowmap"], partial)


@telemetry.device_call(scope="spmv.gather", static_argnums=(1, 2, 3))
def _pad_x(x, pad_left: int, x_rows: int, rows2d: bool):
    """x zero-padded to x_rows * 128 entries, x[c] at pad_left + c (the
    tail is cut where no diagonal reads it): the diagonal step's one copy
    of x, as (x_rows, 128) rows for the kernel when ``rows2d``."""
    n = x.shape[0]
    hi = x_rows * 128 - pad_left - n
    pad = [(pad_left, hi, 0)] + [(0, 0, 0)] * (x.ndim - 1)
    xp = jax.lax.pad(x.astype(jnp.float32), jnp.float32(0), pad)
    return xp.reshape(x_rows, 128) if rows2d else xp


def _run_dia_step(step: dict, fmt: dict, x, y, backend: str,
                  interpret: bool):
    """A diagonal step: x read as shifted windows, y written in row order.
    Multi-RHS x runs the reference formulation."""
    vals = fmt[f"{step['key']}_vals"]
    geom = dict(offsets=tuple(step["offsets"]), pad_left=step["pad_left"],
                n_rows=step["n_rows"])
    kernel = backend == "pallas" and x.ndim == 1
    xp = _pad_x(x, step["pad_left"], step["x_rows"], kernel)
    if kernel:
        from repro.kernels import ops as kops
        return y + kops.dia_spmv(vals, xp, interpret=interpret, **geom)
    from repro.kernels import ref as kref
    return y + kref.dia_spmv_ref(vals, xp, **geom)


def run_spec_step(step: dict, fmt: dict, x, y, n_rows: int,
                  backend: str, interpret: bool, tiles_per_step: int = 1):
    """Accumulate one spec step's contribution into y (shared with dist)."""
    if step["kind"] == "dia":
        return _run_dia_step(step, fmt, x, y, backend, interpret)
    if step["kind"] == "ell":
        return _run_ell_step(step, fmt, x, y, n_rows, backend, interpret,
                             tiles_per_step)
    return _run_seg_step(step, fmt, x, y, n_rows, backend, interpret,
                         tiles_per_step)


def spec_slots(spec: dict) -> tuple[int, int]:
    """(gather-free, gathered) slots of a plan's steps: the slots of its
    ``dia`` steps, which read x as windows, and of its ``ell`` / ``seg``
    steps, each of which gathers one x entry (spec ``slots``; 0 in plans
    saved before it was recorded)."""
    free = sum(s.get("slots", 0) for s in spec["steps"] if s["kind"] == "dia")
    gathered = sum(s.get("slots", 0) for s in spec["steps"]
                   if s["kind"] in ("ell", "seg"))
    return free, gathered


def build_kernel(spec: dict, backend: str = "jax") -> Callable:
    """Stage 2: interpret a kernel spec into the runnable ``fn(fmt, x)``.

    Pallas kernels lower through Mosaic on a TPU and run in the Pallas
    interpreter elsewhere (``repro.runtime.resolve_interpret``)."""
    interpret = resolve_interpret()
    n_rows = spec["n_rows"]
    steps = spec["steps"]
    tiles_per_step = int(spec.get("tiles_per_step", 1))

    # the name is the jitted program's: XLA calls it jit_spmv_plan
    def spmv_plan(fmt, x):
        # trace-time dispatch: 1-D x -> SpMV kernels, (n_cols, B) -> fused
        # SpMM variants. ``rhs`` is () or (B,), appended to output shapes.
        rhs = x.shape[1:]
        y = jnp.zeros((n_rows,) + rhs, dtype=jnp.float32)
        for step in steps:
            y = run_spec_step(step, fmt, x, y, n_rows, backend, interpret,
                              tiles_per_step)
        return y

    return spmv_plan


def build_program(meta: MetadataSet, backend: str = "jax",
                  do_compress: bool = True, jit: bool = True, storage_dtype: str = None,
                  tiles_per_step: int = None,
                  fuse_combine: bool = True) -> SpmvProgram:
    """Generate the SpMV program for a designed MetadataSet.

    ``storage_dtype`` / ``tiles_per_step`` override the MetadataSet's
    SET_RESOURCES knobs (see :func:`plan_format`); ``fuse_combine=False``
    forces the historical kernel + jnp-scatter combine (benchmark
    baseline). Only the pallas backend implements the in-kernel combine,
    so jax-backend programs are planned unfused — their reports and cost
    features then describe the combine they actually execute."""
    fmt, spec = plan_format(meta, do_compress=do_compress,
                            storage_dtype=storage_dtype,
                            tiles_per_step=tiles_per_step,
                            fuse_combine=(fuse_combine
                                          and backend == "pallas"))
    descriptor = {"backend": backend,
                  "blocks": [s["report"] for s in spec["steps"]],
                  "padded_nnz": spec["padded_nnz"],
                  "history": meta.history}
    run = build_kernel(spec, backend=backend)
    fn = jax.jit(run) if jit else run
    return SpmvProgram(n_rows=meta.n_rows, n_cols=meta.n_cols, nnz=meta.nnz,
                       fmt=fmt, fn=fn, descriptor=descriptor, spec=spec,
                       backend=backend)


def build_spmv(meta: MetadataSet, backend: str = "jax", *,
               do_compress: bool = True, jit: bool = True) -> SpmvProgram:
    """Deprecated alias of :func:`build_program` (old four-entrypoint API).

    Prefer ``repro.compile(matrix, target)`` for the full matrix-in /
    plan-out path, or :func:`build_program` when you already hold a
    designed ``MetadataSet``.
    """
    warn_once("build_spmv",
              "repro.core.build_spmv is deprecated; use repro.compile("
              "matrix, target) or repro.core.build_program(meta)")
    return build_program(meta, backend=backend, do_compress=do_compress,
                         jit=jit)
