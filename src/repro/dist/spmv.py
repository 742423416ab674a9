"""Sharded SpMV: partition a SparseMatrix over the ``data`` mesh axis and
execute one machine-designed program per shard under ``shard_map``.

AlphaSparse designs a format *per matrix*; here the device mesh is one more
level of the hardware hierarchy, so the unit of design becomes the *shard*:
each partition may end up with a different machine-designed format (an
irregular shard picks a SEG design while a regular shard picks ELL — see
``dist.search``).

Execution model (since the compile-API redesign): per-shard formats are
**stacked per kernel family and passed as shard_map operands**, not closed
over as jitted constants. Every shard's format is canonicalized into at
most a handful of family groups — ``ell`` (all width buckets padded to a
common (R, W)) and one ``seg`` group per (reduce kind, S, L) — then padded
to the family's max tile count and stacked with a leading shard axis that
is sharded over the mesh. Each device therefore *stores* only its own
1/n_shards slice of every family stack (closing the ROADMAP "dist format
memory dedup" item), and the body needs no ``lax.switch``: a device just
runs every family kernel on its slice, where tiles belonging to other
families are empty padding (val=0, rowmap=-1) that contributes nothing.
The body itself is ``core.kernel_builder.build_kernel`` on a synthetic
spec, so ``backend="pallas"`` runs the real Pallas kernels inside
shard_map (Mosaic on a TPU, the interpreter elsewhere).

Two partition modes:

* ``row``  — shard i owns a contiguous row band (boundaries balanced by
  rows or by nnz). x is replicated; each device emits its padded band of y
  and the bands are concatenated. No cross-device reduction.
* ``col``  — the distributed analogue of the paper's COL_DIV operator:
  shard i owns a uniform column slice and computes a full-length *partial*
  y from its x slice; partials are combined with ``lax.psum`` inside the
  shard_map body (the COL_DIV partial-sum combine step).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.graph import OperatorGraph, run_graph
from repro.core.kernel_builder import (SPEC_VERSION, SpmvProgram,
                                       build_kernel, build_program,
                                       materialize_cols)
from repro.core.matrices import SparseMatrix
from repro.core.operators import OpSpec

__all__ = ["RowShard", "partition_matrix", "ShardedSpmvProgram",
           "build_sharded_spmv", "shard_map_spmv", "default_shard_graph",
           "pack_operand_format"]


def _axis_size(mesh, axis_name: str) -> int:
    sizes = dict(mesh.shape)
    if axis_name not in sizes:
        raise ValueError(f"mesh has no {axis_name!r} axis (axes: "
                         f"{tuple(sizes)}); build one with "
                         "launch.mesh.make_data_mesh")
    return int(sizes[axis_name])


@dataclasses.dataclass(frozen=True)
class RowShard:
    """One partition: a local-index-space sub-matrix plus its global slice.

    ``row`` mode: rows [start, stop) of the global matrix, all columns.
    ``col`` mode: cols [start, stop) of the global matrix, all rows.
    """

    index: int
    start: int
    stop: int
    matrix: SparseMatrix
    mode: str = "row"

    @property
    def size(self) -> int:
        return self.stop - self.start

    @property
    def is_empty(self) -> bool:
        return self.matrix.nnz == 0


def _row_boundaries(m: SparseMatrix, n_shards: int, balance: str) -> np.ndarray:
    if balance == "rows":
        return np.linspace(0, m.n_rows, n_shards + 1).astype(np.int64)
    # nnz-balanced: split the cumulative row-nnz curve into equal arcs, so a
    # power-law matrix doesn't starve most devices while one holds the tail.
    cum = np.concatenate([[0], np.cumsum(m.row_lengths())])
    targets = np.linspace(0, m.nnz, n_shards + 1)
    bounds = np.searchsorted(cum, targets, side="left")
    bounds[0], bounds[-1] = 0, m.n_rows
    return np.maximum.accumulate(bounds).astype(np.int64)


def partition_matrix(m: SparseMatrix, n_shards: int, mode: str = "row",
                     balance: str = "nnz") -> list[RowShard]:
    """Split ``m`` into ``n_shards`` contiguous shards in local index space.

    Shards may be empty (0 nnz, possibly 0 rows) when ``n_shards`` exceeds
    the number of populated bands; callers get a ``None`` program for those.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    shards = []
    if mode == "row":
        bounds = _row_boundaries(m, n_shards, balance)
        for i in range(n_shards):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            keep = (m.rows >= lo) & (m.rows < hi)
            sub = SparseMatrix(hi - lo, m.n_cols,
                               (m.rows[keep] - lo).astype(np.int32),
                               m.cols[keep].astype(np.int32),
                               m.vals[keep].astype(np.float32))
            shards.append(RowShard(i, lo, hi, sub, mode="row"))
    elif mode == "col":
        # uniform slice width: the sharded x layout must be an even split.
        # Trailing shards can be degenerate (n_shards*width > n_cols):
        # clamp both bounds to n_cols so shard bounds still tile [0, n_cols)
        width = -(-m.n_cols // n_shards)
        for i in range(n_shards):
            lo = min(i * width, m.n_cols)
            hi = min((i + 1) * width, m.n_cols)
            keep = (m.cols >= lo) & (m.cols < hi)
            sub = SparseMatrix(m.n_rows, hi - lo,
                               m.rows[keep].astype(np.int32),
                               (m.cols[keep] - lo).astype(np.int32),
                               m.vals[keep].astype(np.float32))
            shards.append(RowShard(i, lo, hi, sub, mode="col"))
    else:
        raise ValueError(f"unknown partition mode {mode!r}")
    return shards


ELL_GRAPH = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("TILE_ROW_BLOCK", rows=16),
    OpSpec.make("LANE_ROW_BLOCK"), OpSpec.make("LANE_TOTAL_RED"))
SEG_GRAPH = OperatorGraph.chain(
    OpSpec.make("COMPRESS"), OpSpec.make("LANE_NNZ_BLOCK", chunk=128, lanes=8),
    OpSpec.make("SEG_SCAN_RED"))


def default_shard_graph(m: SparseMatrix) -> OperatorGraph:
    """Search-free per-shard design: the paper's regularity split (§VI-B) —
    regular shards take a tiled-ELL design, irregular ones a SEG design."""
    return SEG_GRAPH if m.is_irregular() else ELL_GRAPH


def baseline_shard_program(m: SparseMatrix, backend: str = "jax"):
    """Build one shard's trusted baseline program: the search-free
    heuristic design, no machine-designed risk, no fault hook.

    The single definition of "the baseline" for the dist plane — used
    both for shards too small to search (``min_nnz_for_search``) and as
    the degraded-but-correct substitute when a shard's search fails
    (``dist_search``'s per-shard fault domain). Returns
    ``(graph, program)``."""
    from repro.core.graph import run_graph
    from repro.core.kernel_builder import build_program
    g = default_shard_graph(m)
    meta = run_graph(m, g)
    return g, build_program(meta, backend=backend, jit=False)


# ------------------- operand packing (per-family stacking) ------------------

def _pad_to(a: np.ndarray, shape: tuple, fill) -> np.ndarray:
    """Pad ``a`` up to ``shape`` (same rank) with a constant fill value."""
    if tuple(a.shape) == tuple(shape):
        return a
    out = np.full(shape, fill, dtype=a.dtype)
    out[tuple(slice(0, s) for s in a.shape)] = a
    return out


_FILL = {"vals": 0.0, "cols": 0, "rowmap": -1, "local": 0, "end": 0,
         "rows": 0}

# canonical ELL chunk geometry for operand stacking: every bucket is
# re-tiled to (R0, W0) so heterogeneous bucket widths across shards never
# force a pad-to-global-max blowup (wide rows split into several chunks of
# the same output row — exact under the scatter-*add* combine)
_ELL_R0, _ELL_W0 = 8, 8


def _canon_ell(vals: np.ndarray, cols: np.ndarray,
               rowmap: np.ndarray) -> dict:
    """Re-tile one ELL bucket (T, R, W) to canonical (T', R0, W0) chunks."""
    T, R, W = vals.shape
    Rp = -(-R // _ELL_R0) * _ELL_R0
    Wp = -(-W // _ELL_W0) * _ELL_W0
    vals = _pad_to(vals, (T, Rp, Wp), 0.0)
    cols = _pad_to(cols, (T, Rp, Wp), 0)
    rowmap = _pad_to(rowmap, (T, Rp), -1)
    kw, kr = Wp // _ELL_W0, Rp // _ELL_R0
    # split the width axis: chunk (t, j) holds columns [j*W0, (j+1)*W0) of
    # tile t's rows; every chunk scatters into the same output rows
    vals = vals.reshape(T, Rp, kw, _ELL_W0).transpose(0, 2, 1, 3)
    cols = cols.reshape(T, Rp, kw, _ELL_W0).transpose(0, 2, 1, 3)
    rowmap = np.repeat(rowmap, kw, axis=0)
    # split the row axis: a pure reshape (rows stay whole per chunk)
    # (dtypes are preserved: bf16-stored vals / int16 cols keep their
    # narrowed width through the stacking, shrinking per-device bytes)
    vals = vals.reshape(T * kw * kr, _ELL_R0, _ELL_W0)
    cols = cols.reshape(T * kw * kr, _ELL_R0, _ELL_W0)
    rowmap = rowmap.reshape(T * kw * kr, _ELL_R0)
    return {"vals": np.ascontiguousarray(vals),
            "cols": np.ascontiguousarray(cols),
            "rowmap": np.ascontiguousarray(rowmap)}


def _shard_family_parts(program: Optional[SpmvProgram]) -> dict:
    """Canonicalize one shard program's (spec, fmt) into family parts.

    Returns {family_key: [part, ...]} where a part is {name: np.ndarray}.
    Family keys: ("ell",) for every width bucket (re-tiled to canonical
    (R0, W0) chunks), and ("seg", reduce, S, L) for nnz-split blocks (the
    flat (S, L) stream cannot be padded without shifting segment
    descriptors, so it is part of the family identity; tile count and
    seg_rows are paddable).
    """
    out: dict = {}
    if program is None:
        return out
    fmt = {k: np.asarray(v) for k, v in program.fmt.items()}
    for step in program.spec["steps"]:
        if step["kind"] not in ("ell", "seg"):
            raise ValueError(f"sharded plans pack ell and seg steps, not "
                             f"{step['kind']!r}")
        key = step["key"]
        vals = fmt[f"{key}_vals"]          # narrowed dtype preserved
        cols = materialize_cols(step["cols"], fmt)
        if cols.dtype != np.int16:          # model-elided cols come back
            cols = cols.astype(np.int32)    # int64; int16 storage stays
        if step["kind"] == "ell":
            comb = step["combine"]
            if comb["mode"] == "rowmap":
                rowmap = fmt[f"{key}_rowmap"].astype(np.int32)
            else:
                # affine combine (a == 1): reconstruct the equivalent
                # explicit rowmap — scatter-adding to b0 + arange(nv) is
                # exactly what the direct/affine write did.
                T, R = vals.shape[0], vals.shape[1]
                flat = np.full(T * R, -1, np.int32)
                flat[: comb["nv"]] = comb["b0"] + np.arange(comb["nv"],
                                                            dtype=np.int32)
                rowmap = flat.reshape(T, R)
            out.setdefault(("ell",), []).append(
                _canon_ell(vals, cols, rowmap))
        else:
            S, L = int(vals.shape[1]), int(vals.shape[2])
            fam = ("seg", step["reduce"], S, L)
            part = {"vals": vals, "cols": cols,
                    "rowmap": fmt[f"{key}_rowmap"].astype(np.int32)}
            for name in ("local", "end", "rows"):
                if f"{key}_{name}" in fmt:
                    part[name] = fmt[f"{key}_{name}"].astype(np.int32)
            out.setdefault(fam, []).append(part)
    return out


def _family_dtype(name: str, parts: list[dict]) -> np.dtype:
    """One dtype per stacked family array: keep the narrowed storage when
    every shard agrees, otherwise widen to the fp32/int32 baseline."""
    dts = {np.dtype(p[name].dtype) for p in parts}
    if len(dts) == 1:
        return next(iter(dts))
    return np.dtype(np.float32) if name == "vals" else np.dtype(np.int32)


def _concat_shard_family(parts: list[dict], names: list[str],
                         rw: Optional[tuple], seg_rows: int,
                         dtypes: dict) -> dict:
    """Pad each part to the family geometry and concatenate along tiles."""
    pieces = {n: [] for n in names}
    for part in parts:
        T = part["vals"].shape[0]
        for n in names:
            a = part[n].astype(dtypes[n], copy=False)
            if rw is not None:                      # ell: (T, R, W) family
                shape = ((T,) + rw if n != "rowmap" else (T, rw[0]))
            elif n in ("rowmap", "end"):            # seg descriptor rows
                shape = (T, seg_rows)
            else:                                   # seg flat (S, L) stream
                shape = a.shape
            pieces[n].append(_pad_to(a, shape, _FILL[n]))
    return {n: np.concatenate(pieces[n], axis=0) for n in names}


def pack_operand_format(programs: Sequence[Optional[SpmvProgram]]
                        ) -> tuple[list, dict]:
    """Stack per-shard formats into per-family shard_map operands.

    Returns ``(steps, stacks)``: a synthetic kernel spec step list (one
    step per family, rowmap-scatter combine, ``n_rows = n_out``) and the
    stacked arrays {name: (n_shards, ...)}. Shards missing a family get
    all-padding tiles (val=0, rowmap=-1) that contribute nothing, which is
    what removes the need for a ``lax.switch`` over per-shard branches.
    """
    per_shard = [_shard_family_parts(p) for p in programs]
    families = sorted({k for sh in per_shard for k in sh})
    steps, stacks = [], {}
    for gi, fam in enumerate(families):
        gkey = f"g{gi}"
        all_parts = [part for sh in per_shard for part in sh.get(fam, [])]
        if fam[0] == "ell":
            names = ["vals", "cols", "rowmap"]
            rw = (max(p["vals"].shape[1] for p in all_parts),
                  max(p["vals"].shape[2] for p in all_parts))
            seg_rows = 0
            step = {"kind": "ell", "key": gkey,
                    "cols": {"mode": "array", "key": f"{gkey}_cols"},
                    "combine": {"mode": "rowmap", "key": f"{gkey}_rowmap"},
                    "report": {"kernel": "ell", "family": "ell",
                               "tile_rows": rw[0], "width": rw[1]}}
        else:
            _, reduce_kind, S, L = fam
            names = sorted({n for p in all_parts for n in p})
            rw = None
            seg_rows = max(p["rowmap"].shape[1] for p in all_parts)
            # stacking appends padding tiles: the gmem row stream is no
            # longer globally sorted, so never claim the sorted fast path
            step = {"kind": "seg", "key": gkey, "reduce": reduce_kind,
                    "seg_rows": int(seg_rows), "rows_sorted": False,
                    "cols": {"mode": "array", "key": f"{gkey}_cols"},
                    "report": {"kernel": reduce_kind, "family": "seg",
                               "chunk": (S, L), "seg_rows": int(seg_rows)}}
        dtypes = {n: _family_dtype(n, all_parts) for n in names}
        shard_arrays = [
            _concat_shard_family(sh.get(fam, []), names, rw, seg_rows,
                                 dtypes)
            if sh.get(fam) else None
            for sh in per_shard]
        t_max = max(a["vals"].shape[0] for a in shard_arrays if a is not None)
        for n in names:
            tails = {tuple(a[n].shape[1:])
                     for a in shard_arrays if a is not None}
            tail = max(tails)   # singleton by construction of the family
            full = []
            for a in shard_arrays:
                if a is None:
                    full.append(np.full((t_max,) + tail, _FILL[n],
                                        dtype=dtypes[n]))
                else:
                    full.append(_pad_to(a[n], (t_max,) + tail, _FILL[n]))
            stacks[f"{gkey}_{n}"] = np.stack(full)
        steps.append(step)
    return steps, stacks




# ------------------------------ the program --------------------------------

@dataclasses.dataclass
class ShardedSpmvProgram:
    """A compiled sharded SpMV/SpMM: y = A @ x across the mesh ``data`` axis.

    Multi-RHS: a 2-D x is an (n_cols, B) tile (same convention as
    ``SpmvProgram``) and runs the per-shard *fused SpMM* kernels inside the
    same shard_map — row mode concatenates (size, B) bands, col mode psums
    (n_rows, B) partials exactly like the 1-RHS combine.

    ``stacks`` (per-family stacked format arrays, leading dim sharded over
    the mesh axis) and ``steps`` (the synthetic kernel spec the shard_map
    body interprets) fully determine the executable — the same plan
    protocol as ``SpmvProgram``, which is what ``repro.api`` serializes.
    """

    # explicit batching protocol shared with SpmvProgram (see
    # serve.sparse_linear): 2-D x means (n_cols, B), not a vmapped batch
    supports_batch = True

    n_rows: int
    n_cols: int
    mode: str
    shards: list[RowShard]
    programs: list[Optional[SpmvProgram]]
    mesh: object
    axis_name: str
    steps: list = dataclasses.field(default_factory=list)
    stacks: dict = dataclasses.field(default_factory=dict)
    band_rows: int = 0               # row mode: padded per-device band size
    backend: str = "jax"
    _fn: Callable = dataclasses.field(repr=False, default=None)

    @property
    def nnz(self) -> int:
        return sum(s.matrix.nnz for s in self.shards)

    @property
    def stored_bytes(self) -> int:
        return sum(p.stored_bytes for p in self.programs if p is not None)

    @property
    def replicated_format_bytes(self) -> int:
        """Per-device format bytes under the old closure design: every
        device held every shard's format as baked-in jit constants."""
        return self.stored_bytes

    @property
    def per_device_format_bytes(self) -> int:
        """Per-device format bytes under operand passing: the device's
        1/n_shards slice of every family stack."""
        n = max(len(self.shards), 1)
        return sum(v.nbytes // n for v in self.stacks.values())

    def descriptor(self) -> list[dict]:
        out = []
        for s, p in zip(self.shards, self.programs):
            out.append({"shard": s.index, "start": s.start, "stop": s.stop,
                        "nnz": s.matrix.nnz,
                        "design": None if p is None
                        else p.descriptor["blocks"]})
        return out

    def __call__(self, x) -> jax.Array:
        """x: (n_cols,) -> (n_rows,), or (n_cols, B) -> (n_rows, B)."""
        return self._fn(self.stacks, jnp.asarray(x, jnp.float32))


def make_stacked_fn(steps: list, mode: str, n_out: int, mesh,
                    axis_name: str, sizes: Sequence[int], n_cols: int,
                    backend: str = "jax") -> Callable:
    """Jitted ``fn(stacks, x) -> y`` over the stacked-operand body.

    The body is a generated kernel (``build_kernel``) over the device's
    slice of each family stack; format arrays arrive as sharded operands,
    so nothing is baked into the executable as per-device constants.
    ``sizes`` are the shards' true extents: col mode pads x to the
    uniform slice width before sharding it, row mode slices each device's
    padded band back to its size — both inside the jitted function, so
    the result is one array whatever the mesh's axis types.
    """
    sizes = tuple(int(v) for v in sizes)
    n_shards = max(len(sizes), 1)
    run = build_kernel({"version": SPEC_VERSION, "n_rows": n_out,
                        "steps": steps}, backend=backend)

    def body(stacks, x):
        fmt = {k: v[0] for k, v in stacks.items()}
        y = run(fmt, x)
        if mode == "col":
            # the COL_DIV combine step: sum per-slice partial products —
            # identical for (n_rows,) and (n_rows, B) partials
            return jax.lax.psum(y, axis_name)
        # row mode: every device gets all padded bands, so the un-padding
        # below slices an unsharded array (Explicit mesh axes forbid
        # slicing a sharded dim to a size it does not divide)
        return jax.lax.all_gather(y, axis_name)

    def specs_for(stacks):
        return {k: P(axis_name) for k in stacks}

    x_spec = P(axis_name) if mode == "col" else P(None)
    out_spec = P(None)

    def fn(stacks, x):
        if mode == "col":
            pad = -(-n_cols // n_shards) * n_shards - n_cols
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        mapped = jax.shard_map(body, mesh=mesh,
                               in_specs=(specs_for(stacks), x_spec),
                               out_specs=out_spec, check_vma=False)
        out = mapped(stacks, x)
        if mode == "col":
            return out
        # (n_shards, band[, B]) padded row bands -> (n_rows[, B])
        pieces = [out[i, :size] for i, size in enumerate(sizes)]
        return (jnp.concatenate(pieces) if pieces
                else out[:, :0].reshape((-1,) + x.shape[1:]))

    return jax.jit(fn)


def build_sharded_spmv(shards: Sequence[RowShard],
                       programs: Sequence[Optional[SpmvProgram]],
                       mesh, axis_name: str = "data",
                       backend: str = "jax") -> ShardedSpmvProgram:
    """Compile per-shard programs into one SPMD stacked-operand program.

    ``backend`` selects the kernels the shard_map body runs.
    """
    shards = list(shards)
    programs = list(programs)
    n_shards = _axis_size(mesh, axis_name)
    if len(shards) != n_shards:
        raise ValueError(f"{len(shards)} shards for a {n_shards}-way "
                         f"'{axis_name}' mesh axis")
    mode = shards[0].mode if shards else "row"
    if mode == "row":
        n_rows = shards[-1].stop if shards else 0
        n_cols = shards[0].matrix.n_cols if shards else 0
        R = max((s.size for s in shards), default=0)
        n_out = R
    else:
        n_rows = shards[0].matrix.n_rows if shards else 0
        n_cols = shards[-1].stop if shards else 0
        R = 0
        n_out = n_rows
    steps, host_stacks = pack_operand_format(programs)
    sharding = NamedSharding(mesh, P(axis_name))
    stacks = {k: jax.device_put(v, sharding) for k, v in host_stacks.items()}
    fn = make_stacked_fn(steps, mode, n_out, mesh, axis_name,
                         [s.size for s in shards], n_cols,
                         backend=backend)
    return ShardedSpmvProgram(n_rows=n_rows, n_cols=n_cols, mode=mode,
                              shards=shards, programs=programs, mesh=mesh,
                              axis_name=axis_name, steps=steps,
                              stacks=stacks, band_rows=R, backend=backend,
                              _fn=fn)


def shard_map_spmv(m: SparseMatrix, mesh, axis_name: str = "data",
                   mode: str = "row", balance: str = "nnz",
                   graph_for: Callable[[SparseMatrix], OperatorGraph]
                   = default_shard_graph,
                   backend: str = "jax",
                   storage_dtype: str = "float32") -> ShardedSpmvProgram:
    """Search-free sharded SpMV: partition + per-shard heuristic design.

    ``dist.search.dist_search`` is the searched variant (one AlphaSparse
    search per shard); this one is the cheap path for serving and tests.
    ``storage_dtype="bfloat16"`` narrows every per-shard format (bf16
    vals, int16 cols where n_cols fits) — the family stacks preserve the
    narrowed dtypes, so per-device bytes shrink accordingly.
    """
    n_shards = _axis_size(mesh, axis_name)
    shards = partition_matrix(m, n_shards, mode=mode, balance=balance)
    sd = None if storage_dtype == "float32" else storage_dtype
    programs = []
    for s in shards:
        if s.is_empty:
            programs.append(None)
        else:
            meta = run_graph(s.matrix, graph_for(s.matrix))
            # jit=False: only the packed fmt + spec feed the stacked body
            programs.append(build_program(meta, backend=backend, jit=False,
                                          storage_dtype=sd))
    return build_sharded_spmv(shards, programs, mesh, axis_name,
                              backend=backend)
