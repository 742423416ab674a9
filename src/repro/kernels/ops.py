"""Public wrappers for the Pallas SpMV kernels.

The kernel builder (``core/kernel_builder.py`` with ``backend='pallas'``)
calls these; tests sweep them against ``ref.py``. ``interpret`` defaults to
the platform (``repro.runtime.resolve_interpret``): Mosaic on a TPU, the
Pallas interpreter everywhere else.

All kernels accept mixed-precision storage (bfloat16 vals, int16 cols),
upcast in-register and return float32 partials/outputs. The ELL kernels
return the row sums in tile order, which the kernel builder places in y;
the seg ``*_fused`` variants own the cross-tile combine (a resident y
block) and return the finished y directly, and so does the diagonal
kernel, which reads x as shifted windows instead of a gather.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.runtime import resolve_interpret

from .dia_spmv import dia_spmv_pallas
from .ell_spmv import ell_spmv_pallas, ell_spmm_pallas
from .seg_spmv import (seg_spmv_pallas, seg_spmm_pallas,
                       seg_spmv_fused_pallas, seg_spmm_fused_pallas)

__all__ = ["ell_spmv", "seg_spmv", "seg_spmv_fused", "ell_spmm", "seg_spmm",
           "seg_spmm_fused", "dia_spmv"]


def ell_spmv(vals, cols, x, *, tiles_per_step: int = 1,
             interpret: Optional[bool] = None) -> jax.Array:
    """(T, R, W) padded tiles -> (T, R) fp32 row sums."""
    return ell_spmv_pallas(vals, cols, x, tiles_per_step=tiles_per_step,
                           interpret=resolve_interpret(interpret))


def seg_spmv(vals, cols, local_row, seg_end, x, seg_rows: int,
             mode: str = "seg_scan", *,
             interpret: Optional[bool] = None) -> jax.Array:
    """(T, S, L) nnz-split tiles -> (T, seg_rows) fp32 segment partials."""
    return seg_spmv_pallas(vals, cols, local_row, seg_end, x, seg_rows,
                           mode=mode, interpret=resolve_interpret(interpret))


def seg_spmv_fused(vals, cols, local_row, seg_end, r0, x, seg_rows: int,
                   *, n_rows: int, n_out: int,
                   mode: str = "seg_scan", tiles_per_step: int = 1,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Fused-combine (carry-last-segment) seg SpMV -> finished fp32 y."""
    return seg_spmv_fused_pallas(vals, cols, local_row, seg_end, r0, x,
                                 seg_rows, n_rows, n_out=n_out, mode=mode,
                                 tiles_per_step=tiles_per_step,
                                 interpret=resolve_interpret(interpret))


def ell_spmm(vals, cols, x, *, tiles_per_step: int = 1,
             interpret: Optional[bool] = None) -> jax.Array:
    """Fused multi-RHS: (T, R, W) tiles, x (n_cols, B) -> (T, R, B) fp32."""
    return ell_spmm_pallas(vals, cols, x, tiles_per_step=tiles_per_step,
                           interpret=resolve_interpret(interpret))


def seg_spmm(vals, cols, local_row, seg_end, x, seg_rows: int,
             mode: str = "seg_scan", *,
             interpret: Optional[bool] = None) -> jax.Array:
    """Fused multi-RHS: (T, S, L) tiles, x (n_cols, B) -> (T, M, B) fp32."""
    return seg_spmm_pallas(vals, cols, local_row, seg_end, x, seg_rows,
                           mode=mode, interpret=resolve_interpret(interpret))


def seg_spmm_fused(vals, cols, local_row, seg_end, r0, x, seg_rows: int,
                   *, n_rows: int, n_out: int,
                   mode: str = "seg_scan", tiles_per_step: int = 1,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Fused-combine seg SpMM -> the finished (n_rows, B) fp32 y."""
    return seg_spmm_fused_pallas(vals, cols, local_row, seg_end, r0, x,
                                 seg_rows, n_rows, n_out=n_out, mode=mode,
                                 tiles_per_step=tiles_per_step,
                                 interpret=resolve_interpret(interpret))


def dia_spmv(vals, xw, *, offsets: tuple, pad_left: int, n_rows: int,
             interpret: Optional[bool] = None) -> jax.Array:
    """Diagonal SpMV: (D, NB, 128) vals, the zero-padded x as (M, 128)
    -> the finished fp32 y (n_rows,)."""
    return dia_spmv_pallas(vals, xw, offsets=tuple(offsets),
                           pad_left=pad_left, n_rows=n_rows,
                           interpret=resolve_interpret(interpret))
