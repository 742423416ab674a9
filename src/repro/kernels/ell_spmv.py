"""Pallas TPU kernels: row-per-lane padded-tile SpMV/SpMM (ELL / SELL family).

Format: vals/cols are (T, R, W) — T tiles of R rows, each row padded to W
slots (val=0, col=0 in the padding).

Mosaic gathers only within 2-D tiles, so ``x[cols]`` is gathered by XLA
ahead of the ``pallas_call`` and streamed into the kernel as one more
blocked operand beside ``vals`` (x itself needs no VMEM block). The kernel
owns the multiply-reduce: each grid step takes a band of ``8 * G`` rows
(G = lcm(R, 128) * megatile lanes), multiplies vals by the gathered x and
reduces every row over its W slots on the MXU (ones @ prod^T), which lands
the row sums lane-dense as an (8, G) output block. Rows stay in tile order,
so the output flattened IS the (T*R,) row slab; the kernel builder places
it in y (a slice add when the rowmap is affine, else a scatter).

Wide rows: the W axis is a second ("arbitrary") grid axis in chunks of
128-lane multiples sized to a VMEM budget; the output block accumulates
across it, and the lanes past W in the last chunk are masked.

Mixed precision: vals may be stored bfloat16 and cols int16; the kernel
upcasts in-register and accumulates in float32 — outputs are always
float32. Every MXU contraction runs at ``Precision.HIGHEST`` so the
reduction keeps fp32 accuracy.

Multi-RHS (SpMM): x is (n_cols, B); the gathered operand is (B, T*R, W)
and the kernel loops over B inside each grid step, so the vals block is
read from HBM once for all B right-hand sides.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry

__all__ = ["ell_spmv_pallas", "ell_spmm_pallas", "vmem_limit", "HIGHEST"]

HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_SUBLANES = 8
# VMEM the double-buffered input blocks of one grid step may take; the
# scoped limit handed to Mosaic is derived from the blocks actually chosen
_BLOCK_BUDGET = 8 * 1024 * 1024


def _f32(a):
    """Upcast a (possibly bf16-stored) operand to the fp32 compute type."""
    return a.astype(jnp.float32)


def _ceil_to(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def vmem_limit(block_bytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose blocks take ``block_bytes``:
    room for them plus compiler temporaries, never below the 32 MiB
    default nor above what one v5e/v6e core has."""
    return int(min(max(2 * block_bytes + (16 << 20), 32 << 20), 100 << 20))


@telemetry.device_call(scope="spmv.gather")
def gather_rows(x, cols):
    """XLA gather ahead of the kernel: x[cols] as (B, *cols.shape).

    A 1-D x gives B = 1; an (n_cols, B) x gives its B columns leading."""
    idx = cols.astype(jnp.int32)
    if x.ndim == 1:
        return jnp.take(x, idx, axis=0)[None]
    return jnp.take(x.T, idx, axis=1)


def _geometry(R: int, W: int, nb: int, k: int, itemsize: int):
    """Lanes per output row G, W chunk Wc, and the step's block bytes."""
    base = R * _LANES // math.gcd(R, _LANES)          # lcm(R, 128)
    wc = W if W <= _LANES else _LANES
    lane_w = _ceil_to(wc, _LANES)

    def step_bytes(g, w):
        return 2 * _SUBLANES * g * _ceil_to(w, _LANES) * (itemsize + 4 * nb)

    k = max(int(k), 1)
    while k > 1 and step_bytes(base * k, lane_w) > _BLOCK_BUDGET:
        k //= 2
    G = base * k
    if W > _LANES:
        # widest 128-multiple chunk that keeps the step in budget
        per_lane = step_bytes(G, _LANES) // _LANES
        wc = max(_LANES, min(_ceil_to(W, _LANES),
                             (_BLOCK_BUDGET // max(per_lane, 1))
                             // _LANES * _LANES))
        if wc >= W:
            wc = W
    return G, wc, step_bytes(G, wc)


def _ell_rows_kernel(vals_ref, xg_ref, out_ref, *, G: int, W: int, Wc: int,
                     nb: int):
    """One band of 8*G rows x one W chunk: out[b, g, :] += row sums."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    vals = _f32(vals_ref[...])                            # (8G, Wc)
    mask = None
    if W % Wc:
        # last chunk runs past W: those lanes hold no format data
        lane = j * Wc + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
        mask = lane < W
    ones = jnp.ones((_SUBLANES, vals.shape[1]), jnp.float32)

    def one_rhs(b, carry):
        prod = vals * _f32(xg_ref[b])
        if mask is not None:
            prod = jnp.where(mask, prod, 0.0)
        # (8, Wc) x (8G, Wc)^T: every result row holds all 8G row sums,
        # lane-dense; row band g is lanes [g*G, (g+1)*G)
        tot = jax.lax.dot_general(ones, prod, (((1,), (1,)), ((), ())),
                                  precision=HIGHEST,
                                  preferred_element_type=jnp.float32)
        for g in range(_SUBLANES):
            out_ref[b, g:g + 1, :] += tot[0:1, g * G:(g + 1) * G]
        return carry

    jax.lax.fori_loop(0, nb, one_rhs, 0)


def _ell_rows(vals, cols, x, *, tiles_per_step: int, interpret: bool):
    """Row slab of an ELL bucket: (T*R,) for 1-D x, (T*R, B) for (n, B)."""
    T, R, W = vals.shape
    N = T * R
    xg = gather_rows(x, cols).reshape(-1, N, W)           # (B, N, W)
    nb = xg.shape[0]
    G, Wc, blk = _geometry(R, W, nb, tiles_per_step, vals.dtype.itemsize)
    rows = _SUBLANES * G
    n_steps = pl.cdiv(N, rows)
    name = "ell_spmv" if x.ndim == 1 else "ell_spmm"
    kernel = pl.pallas_call(
        functools.partial(_ell_rows_kernel, G=G, W=W, Wc=Wc, nb=nb),
        grid=(n_steps, pl.cdiv(W, Wc)),
        in_specs=[pl.BlockSpec((rows, Wc), lambda i, j: (i, j)),
                  pl.BlockSpec((nb, rows, Wc), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((nb, _SUBLANES, G), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, n_steps * _SUBLANES, G),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(blk)),
        interpret=interpret,
        name=name,
    )
    out = telemetry.device_call(name=name)(kernel)(vals.reshape(N, W), xg)
    slab = out.reshape(nb, -1)[:, :N]
    return slab[0] if x.ndim == 1 else slab.T


@functools.partial(jax.jit, static_argnames=("tiles_per_step", "interpret"))
def ell_spmv_pallas(vals: jax.Array, cols: jax.Array, x: jax.Array, *,
                    tiles_per_step: int = 1, interpret: bool) -> jax.Array:
    """vals, cols: (T, R, W); x: (n_cols,) -> fp32 row sums (T, R).

    Each grid step covers ``tiles_per_step`` times the minimal row band
    (the megatile), capped by the VMEM budget."""
    T, R, _ = vals.shape
    return _ell_rows(vals, cols, x, tiles_per_step=tiles_per_step,
                     interpret=interpret).reshape(T, R)


@functools.partial(jax.jit, static_argnames=("tiles_per_step", "interpret"))
def ell_spmm_pallas(vals: jax.Array, cols: jax.Array, x: jax.Array, *,
                    tiles_per_step: int = 1, interpret: bool) -> jax.Array:
    """vals, cols: (T, R, W); x: (n_cols, B) -> fp32 row sums (T, R, B)."""
    T, R, _ = vals.shape
    return _ell_rows(vals, cols, x, tiles_per_step=tiles_per_step,
                     interpret=interpret).reshape(T, R, x.shape[1])
