"""Pallas TPU kernel: gather-free diagonal (DIA) SpMV.

Format: ``vals`` is (D, NB, 128): diagonal d's value of row r sits at
``vals[d].ravel()[r]`` and multiplies x[r + offsets[d]]; the offsets are
static. Over a band of consecutive rows each diagonal reads a contiguous
window of x shifted by a constant, so the kernel needs no column index, no
gather, and writes y in row order (no row map, no combine).

x arrives zero-padded on both sides (``pad_left`` leading zeros, a
multiple of 128 and at least the largest negative offset) and reshaped to
(M, 128). It is whole-resident in VMEM, loaded once a call. The grid walks
row tiles of ``128 * TB`` rows: the ``vals`` block is (D, TB, 128) and the
output block (TB, 128), lane-dense, rows in order. Inside a tile, chunks
of ``CH`` rows of 128 accumulate on the VPU in fp32: for diagonal d, with
t = pad_left + offset, q, s = divmod(t, 128) are static, and the window of
chunk row j is lanes s.. of x row r0 + j + q followed by lanes ..s of the
next row — two loads of the resident x, one ``pltpu.roll`` by s each, and
a lane select. No MXU. A load may start at any row (VMEM is addressed by
128-lane row), so the windows' unaligned starts cost no shuffles.

Mixed precision: vals may be stored bfloat16; they are upcast in-register.
The output is float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry

from .ell_spmv import vmem_limit

__all__ = ["dia_spmv_pallas", "geometry", "x_fits_vmem", "X_VMEM_BUDGET"]

_LANES = 128
_SUBLANES = 8
# rows of 128 per grid step (at most), and per accumulated chunk inside
# it: on a v5e, 256 / 16 ran HPCG's 27 diagonals at 0.195 ms against
# 0.211–0.236 ms for 64–256 / 8 (see PERF.md)
_TB = 256
_CH = 16
# a grid step's vals block stays within this many bytes
_VALS_BLOCK = 4 * 1024 * 1024
# bytes the resident zero-padded x may take in VMEM
X_VMEM_BUDGET = 16 * 1024 * 1024


def geometry(n_rows: int, offsets) -> tuple[int, int]:
    """(pad_left, x_rows): x's leading zeros, and the rows of 128 of the
    zero-padded x: every window the grid's tiles read, and no more (x
    past the last diagonal's reach is cut)."""
    lo, hi = min(offsets), max(offsets)
    pad_left = -(-max(-lo, 0) // _LANES) * _LANES
    nb = -(-max(n_rows, 1) // _LANES)
    tb = _tile_rows(nb, len(offsets))
    # the last tile's windows end at row tiles * tb + q_max; 8 more rows
    # cover it in whole sublanes
    x_rows = -(-nb // tb) * tb + (pad_left + hi) // _LANES + _SUBLANES
    return pad_left, -(-x_rows // _SUBLANES) * _SUBLANES


def _tile_rows(nb: int, n_diag: int) -> int:
    """Rows of 128 per grid step: ``_TB``, fewer where many diagonals would
    pass the (fp32) vals block's budget, or all ``nb`` rows when fewer."""
    fit = _VALS_BLOCK // (n_diag * 4 * _LANES) // _CH * _CH
    return min(nb, max(_CH, min(_TB, fit)))


def x_fits_vmem(n_rows: int, offsets) -> bool:
    """True when the zero-padded x fits the kernel's VMEM budget. The
    kernel takes one right-hand side; a plan runs several through the
    XLA reference, which holds nothing in VMEM."""
    _, x_rows = geometry(n_rows, offsets)
    return x_rows * _LANES * 4 <= X_VMEM_BUDGET


def _dia_kernel(vals_ref, x_ref, out_ref, *, offsets, pad_left: int,
                tb: int, ch: int):
    base = pl.program_id(0) * tb

    def rows(c0, n):
        """Accumulate chunk rows [c0, c0 + n) of this tile."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, _LANES), 1)
        r0 = base + c0
        acc = jnp.zeros((n, _LANES), jnp.float32)
        for d, off in enumerate(offsets):
            q, s = divmod(pad_left + off, _LANES)
            w = x_ref[pl.ds(r0 + q, n), :]
            if s:
                nxt = x_ref[pl.ds(r0 + q + 1, n), :]
                w = jnp.where(lane < _LANES - s,
                              pltpu.roll(w, _LANES - s, 1),
                              pltpu.roll(nxt, _LANES - s, 1))
            acc += vals_ref[d, pl.ds(c0, n), :].astype(jnp.float32) * w
        out_ref[pl.ds(c0, n), :] = acc

    n_full, tail = divmod(tb, ch)

    def chunk(c, carry):
        rows(pl.multiple_of(c * ch, ch), ch)
        return carry

    if n_full > 1:
        jax.lax.fori_loop(0, n_full, chunk, 0)
    else:               # a static start: a short tile's rows in one chunk
        rows(0, ch)
    if tail:
        rows(n_full * ch, tail)


@functools.partial(jax.jit, static_argnames=("offsets", "pad_left",
                                             "n_rows", "interpret"))
def dia_spmv_pallas(vals: jax.Array, xw: jax.Array, *, offsets: tuple,
                    pad_left: int, n_rows: int, interpret: bool) -> jax.Array:
    """vals (D, NB, 128); xw the zero-padded x as (M, 128), x[c] at flat
    index pad_left + c -> fp32 y (n_rows,)."""
    D, nb, _ = vals.shape
    tb = _tile_rows(nb, D)
    ch = min(_CH, tb)
    blk = (2 * (D * vals.dtype.itemsize + 4) * tb * _LANES
           + xw.size * xw.dtype.itemsize)
    kernel = pl.pallas_call(
        functools.partial(_dia_kernel, offsets=tuple(offsets),
                          pad_left=pad_left, tb=tb, ch=ch),
        grid=(pl.cdiv(nb, tb),),
        in_specs=[pl.BlockSpec((D, tb, _LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tb, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(blk)),
        interpret=interpret,
        name="dia_spmv",
    )
    out = telemetry.device_call(name="dia_spmv")(kernel)(vals, xw)
    return out.reshape(-1)[:n_rows]
