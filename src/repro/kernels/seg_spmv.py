"""Pallas TPU kernels: nnz-split segmented SpMV/SpMM (merge-based / CSR5 family).

Format: vals/cols are (T, S, L) — T tiles, each a flat stream of C = S*L
nonzeros laid out as S sublane rows of L lanes. ``seg_end`` (T, M) is the
CSR5-style descriptor (exclusive end of each of the tile's M row
segments); ``local_row`` (T, S, L) is each nonzero's segment slot.

As in the ELL family, ``x[cols]`` is gathered by XLA ahead of the
``pallas_call`` (Mosaic gathers only within 2-D tiles) and streamed in as
one more blocked operand. The kernel owns the product and the segmented
reduction. Both reductions run on the MXU and land lane-dense as a (B, M)
row per tile:

* ``seg_scan``  (SEG_SCAN_RED) — segment m sums the stream positions
  [end[m-1], end[m]); for each sublane row s the kernel builds the (L, M)
  interval mask from the descriptor and contracts the row's products
  against it. This is the TPU form of the CSR5 scan + gather + diff: the
  format stores only the M segment ends per tile.
* ``onehot_mxu`` (ONEHOT_MXU_RED) — products x one-hot(local_row): for
  each sublane row an (M, L) one-hot is contracted on the MXU. It stores a
  slot per nonzero but costs no descriptor arithmetic.

The S axis is a second ("arbitrary") grid axis in blocks of up to 16
sublane rows, so VMEM per step is independent of the tile size; partials
accumulate across it. Every contraction runs at ``Precision.HIGHEST``.

Fused-combine variants (``*_fused``): when the format generator proves
each tile's rows are a contiguous ascending run (rowmap[t, m] = r0[t] + m),
y becomes one resident output block, laid out lane-dense as
(B, rows/128, 128), and each tile *accumulates* its M partials at rows
r0[t] .. r0[t]+M-1. The kernel places them with one selection matmul into
an 8-row-aligned window of y, so a row straddling a tile boundary
receives one add per tile on the same resident block (the carry-last-
segment scheme) and no scatter pass remains. ``r0`` arrives by scalar
prefetch. When the resident y would not fit VMEM, the fused entry points
compute partials and place them with one XLA scatter instead.

Mixed precision: vals may arrive bfloat16 and cols int16; kernels upcast
in-register and accumulate in float32 — partials/outputs are always fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry

from .ell_spmv import HIGHEST, _ceil_to, gather_rows, vmem_limit

__all__ = ["seg_spmv_pallas", "seg_spmm_pallas",
           "seg_spmv_fused_pallas", "seg_spmm_fused_pallas"]

_LANES = 128
_SUBLANES = 8
# sublane rows of one tile per grid step (the S-axis block)
_MAX_SUB = 16
# resident fused y (all right-hand sides, double-buffered) may take at most
# this much VMEM; larger outputs take the partials + XLA scatter path
_RESIDENT_Y_BUDGET = 48 * 1024 * 1024
# default tiles per grid step for the unfused partial kernels
_TILES_PER_STEP = 8


def _f32(a):
    return a.astype(jnp.float32)


def _sub_block(S: int) -> int:
    """Sublane rows per grid step: whole tile when small, else a 16-row
    block (a native bf16 tile height) that divides S."""
    if S <= _MAX_SUB or S % _MAX_SUB:
        return S
    return _MAX_SUB


def _tile_partial(vals_ref, xg_ref, aux_ref, k, s_base, *, mode: str,
                  Sb: int, L: int, M: int):
    """(B, M) fp32 segment partials of tile k's sublane rows
    [s_base, s_base + Sb)."""
    v = _f32(vals_ref[k])                                 # (Sb, L)
    nb = xg_ref.shape[2]
    acc = jnp.zeros((nb, M), jnp.float32)
    if mode == "onehot_mxu":
        loc = aux_ref[k]                                  # (Sb, L)
        slot = jax.lax.broadcasted_iota(jnp.int32, (M, L), 0)
    else:
        bounds = aux_ref[k]                               # (2, M)
        start, end = bounds[0:1, :], bounds[1:2, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, (L, M), 0)
    for s in range(Sb):
        a = v[s:s + 1, :] * _f32(xg_ref[k, s])           # (B, L)
        if mode == "onehot_mxu":
            oh = (slot == loc[s:s + 1, :]).astype(jnp.float32)     # (M, L)
            acc = acc + jax.lax.dot_general(
                a, oh, (((1,), (1,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)
        else:
            pos = (s_base + s) * L + lane                           # (L, M)
            inside = ((pos >= start) & (pos < end)).astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                a, inside, (((1,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)
    return acc


def _partials_kernel(vals_ref, xg_ref, aux_ref, out_ref, *, mode, Sb, L, M,
                     K):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def tile(k, carry):
        out_ref[k] += _tile_partial(vals_ref, xg_ref, aux_ref, k, j * Sb,
                                    mode=mode, Sb=Sb, L=L, M=M)
        return carry

    jax.lax.fori_loop(0, K, tile, 0)


def _fused_kernel(r0_ref, vals_ref, xg_ref, aux_ref, y_ref, *, mode, Sb, L,
                  M, K, T, NW):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    nb = y_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (NW, M), 1)
    win_row = jax.lax.broadcasted_iota(jnp.int32, (NW, M), 0)
    m_idx = jax.lax.broadcasted_iota(jnp.int32, (M, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (M, _LANES), 1)

    def tile(k, carry):
        t = i * K + k
        part = _tile_partial(vals_ref, xg_ref, aux_ref, k, j * Sb,
                             mode=mode, Sb=Sb, L=L, M=M)
        # the last grid step may run past T: those tiles add nothing
        part = jnp.where(t < T, part, 0.0)
        r0 = r0_ref[jnp.minimum(t, T - 1)]
        # y row r lives at (r // 128, r % 128); the window starts at an
        # 8-aligned sublane row so the read-modify-write is tile-aligned
        q0 = pl.multiple_of((r0 >> 10) << 3, _SUBLANES)
        off = r0 - q0 * _LANES
        in_row = ((col + off) >> 7) == win_row                     # (NW, M)
        to_lane = (((m_idx + off) & (_LANES - 1)) == lane
                   ).astype(jnp.float32)                           # (M, 128)
        for b in range(nb):
            placed = jax.lax.dot_general(
                jnp.where(in_row, part[b:b + 1, :], 0.0), to_lane,
                (((1,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)                # (NW, 128)
            y_ref[b, pl.ds(q0, NW), :] += placed
        return carry

    jax.lax.fori_loop(0, K, tile, 0)


def _operands(vals, cols, local_row, seg_end, x, mode: str):
    """Gathered x as (T, S, B, L) and the reduction's aux operand."""
    T, S, L = vals.shape
    xg = jnp.moveaxis(gather_rows(x, cols), 0, 2)          # (T, S, B, L)
    if mode == "onehot_mxu":
        aux = local_row.astype(jnp.int32)
    elif mode == "seg_scan":
        end = seg_end.astype(jnp.int32)
        start = jnp.pad(end[:, :-1], ((0, 0), (1, 0)))
        aux = jnp.stack([start, end], axis=1)              # (T, 2, M)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return xg, aux


def _aux_spec(aux, K, Sb, mode):
    if mode == "onehot_mxu":
        return pl.BlockSpec((K, Sb, aux.shape[2]), lambda i, j, *_: (i, j, 0))
    return pl.BlockSpec((K, 2, aux.shape[2]), lambda i, j, *_: (i, 0, 0))


def _seg_partials(vals, cols, local_row, seg_end, x, seg_rows: int,
                  mode: str, interpret: bool):
    """(T, B, M) fp32 partials."""
    T, S, L = vals.shape
    M = int(seg_rows)
    xg, aux = _operands(vals, cols, local_row, seg_end, x, mode)
    nb = xg.shape[2]
    K = min(_TILES_PER_STEP, T)
    Sb = _sub_block(S)
    blk = 2 * K * Sb * L * (vals.dtype.itemsize + 4 * _SUBLANES) \
        + 2 * K * _SUBLANES * M * 4
    name = f"seg_{'spmv' if x.ndim == 1 else 'spmm'}_{mode}"
    kernel = pl.pallas_call(
        functools.partial(_partials_kernel, mode=mode, Sb=Sb, L=L, M=M, K=K),
        grid=(pl.cdiv(T, K), S // Sb),
        in_specs=[pl.BlockSpec((K, Sb, L), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((K, Sb, nb, L), lambda i, j: (i, j, 0, 0)),
                  _aux_spec(aux, K, Sb, mode)],
        out_specs=pl.BlockSpec((K, nb, M), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, nb, M), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blk)),
        interpret=interpret,
        name=name,
    )
    return telemetry.device_call(name=name)(kernel)(vals, xg, aux)


@telemetry.device_call(scope="spmv.combine", static_argnums=(2,))
def _place_partials(part, r0, ny: int):
    """(T, B, M) partials of tiles whose rows start at r0 -> (ny, B) y."""
    _, nb, M = part.shape
    rows = r0[:, None] + jnp.arange(M, dtype=jnp.int32)[None, :]
    return jnp.zeros((ny, nb), jnp.float32).at[rows.reshape(-1)].add(
        jnp.swapaxes(part, 1, 2).reshape(-1, nb), mode="drop")


def _seg_fused(vals, cols, local_row, seg_end, r0, x, seg_rows: int,
               n_rows: int, n_out: int, mode: str, tiles_per_step: int,
               interpret: bool):
    """The finished y: (n_rows,) for 1-D x, (n_rows, B) for (n, B)."""
    T, S, L = vals.shape
    M = int(seg_rows)
    nb = 1 if x.ndim == 1 else x.shape[1]
    ny = max(int(n_rows), int(n_out))
    # window rows: an 8-aligned start leaves up to 1023 lanes of offset
    NW = _ceil_to(-(-(_SUBLANES * _LANES - 1 + M) // _LANES), _SUBLANES)
    Yr = _ceil_to(-(-ny // _LANES), _SUBLANES) + NW
    y_bytes = 2 * nb * Yr * _LANES * 4
    r0 = r0.astype(jnp.int32)
    if y_bytes > _RESIDENT_Y_BUDGET:
        part = _seg_partials(vals, cols, local_row, seg_end, x, M, mode,
                             interpret)                      # (T, B, M)
        y = _place_partials(part, r0, ny)[:n_rows]
        return y[:, 0] if x.ndim == 1 else y
    xg, aux = _operands(vals, cols, local_row, seg_end, x, mode)
    K = max(min(int(tiles_per_step), T), 1)
    Sb = _sub_block(S)
    blk = 2 * K * Sb * L * (vals.dtype.itemsize + 4 * _SUBLANES) + y_bytes
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(T, K), S // Sb),
        in_specs=[pl.BlockSpec((K, Sb, L), lambda i, j, _: (i, j, 0)),
                  pl.BlockSpec((K, Sb, nb, L), lambda i, j, _: (i, j, 0, 0)),
                  _aux_spec(aux, K, Sb, mode)],
        out_specs=pl.BlockSpec((nb, Yr, _LANES), lambda i, j, _: (0, 0, 0)),
    )
    name = f"seg_{'spmv' if x.ndim == 1 else 'spmm'}_fused_{mode}"
    kernel = pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode, Sb=Sb, L=L, M=M, K=K,
                          T=T, NW=NW),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, Yr, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blk)),
        interpret=interpret,
        name=name,
    )
    y = telemetry.device_call(name=name)(kernel)(r0, vals, xg, aux)
    y = y.reshape(nb, -1)[:, :n_rows]
    return y[0] if x.ndim == 1 else y.T


@functools.partial(jax.jit, static_argnames=("seg_rows", "mode", "interpret"))
def seg_spmv_pallas(vals: jax.Array, cols: jax.Array, local_row: jax.Array,
                    seg_end: jax.Array, x: jax.Array, seg_rows: int,
                    mode: str = "seg_scan", *, interpret: bool) -> jax.Array:
    """vals/cols/local_row: (T, S, L); seg_end: (T, M) -> fp32 (T, M)."""
    return _seg_partials(vals, cols, local_row, seg_end, x, seg_rows, mode,
                         interpret)[:, 0, :]


@functools.partial(jax.jit, static_argnames=("seg_rows", "mode", "interpret"))
def seg_spmm_pallas(vals: jax.Array, cols: jax.Array, local_row: jax.Array,
                    seg_end: jax.Array, x: jax.Array, seg_rows: int,
                    mode: str = "seg_scan", *, interpret: bool) -> jax.Array:
    """vals/cols/local_row: (T, S, L); x: (n_cols, B) -> fp32 (T, M, B)."""
    return jnp.swapaxes(_seg_partials(vals, cols, local_row, seg_end, x,
                                      seg_rows, mode, interpret), 1, 2)


@functools.partial(jax.jit, static_argnames=("seg_rows", "n_rows", "n_out",
                                             "mode", "tiles_per_step",
                                             "interpret"))
def seg_spmv_fused_pallas(vals: jax.Array, cols: jax.Array,
                          local_row: jax.Array, seg_end: jax.Array,
                          r0: jax.Array, x: jax.Array, seg_rows: int,
                          n_rows: int, *, n_out: int,
                          mode: str = "seg_scan", tiles_per_step: int = 1,
                          interpret: bool) -> jax.Array:
    """Fused-combine seg SpMV -> the finished (n_rows,) y.

    ``r0``: (T,) first global row of each tile (0 for all-padding tiles);
    ``n_out``: REQUIRED static slab size >= max(r0) + seg_rows (the
    format generator records it in the kernel spec as ``fused_rows``).
    """
    return _seg_fused(vals, cols, local_row, seg_end, r0, x, seg_rows,
                      n_rows, n_out, mode, tiles_per_step, interpret)


@functools.partial(jax.jit, static_argnames=("seg_rows", "n_rows", "n_out",
                                             "mode", "tiles_per_step",
                                             "interpret"))
def seg_spmm_fused_pallas(vals: jax.Array, cols: jax.Array,
                          local_row: jax.Array, seg_end: jax.Array,
                          r0: jax.Array, x: jax.Array, seg_rows: int,
                          n_rows: int, *, n_out: int,
                          mode: str = "seg_scan", tiles_per_step: int = 1,
                          interpret: bool) -> jax.Array:
    """Fused-combine seg SpMM: x (n_cols, B) -> the finished (n_rows, B)."""
    return _seg_fused(vals, cols, local_row, seg_end, r0, x, seg_rows,
                      n_rows, n_out, mode, tiles_per_step, interpret)
