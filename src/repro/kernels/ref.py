"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests sweep against
(``tests/test_kernels.py``) and double as the CPU fast path used by the
kernel builder's ``backend='jax'``. Like the kernels, they upcast
mixed-precision storage (bfloat16 vals, int16 cols) and accumulate in
float32, so a bf16-stored format compared against its fp32 twin differs
only by the storage rounding, never by accumulation error.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import telemetry

__all__ = ["gather", "ell_spmv_ref", "seg_spmv_ref", "ell_spmm_ref",
           "seg_spmm_ref", "dia_spmv_ref"]


def _f32(a):
    return a.astype(jnp.float32)


@telemetry.device_call(scope="spmv.gather")
def gather(x, cols):
    """x[cols] as fp32, shaped like cols (then B for an (n_cols, B) x)."""
    return _f32(x[cols.astype(jnp.int32)])


def ell_spmv_ref(vals: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    """Row-per-lane padded-tile SpMV partials.

    vals, cols: (T, R, W); x: (n_cols,) -> fp32 partials (T, R).
    Padded entries must carry val=0 (their gathered x value is ignored).
    """
    return jnp.einsum("trw,trw->tr", _f32(vals), gather(x, cols))


def seg_spmv_ref(vals, cols, local_row, seg_end, x, seg_rows: int,
                 mode: str = "seg_scan") -> jax.Array:
    """NNZ-split tile SpMV partials.

    vals/cols/local_row: (T, S, L); seg_end: (T, M) exclusive in-tile end
    positions; returns per-tile fp32 row partials (T, M).

    mode='onehot_mxu': products x one-hot(local_row) matmul (MXU path).
    mode='seg_scan'  : in-tile cumulative sum gathered at segment ends
                       (CSR5-style descriptor path).
    Both are mathematically identical; tests assert they agree.
    """
    T = vals.shape[0]
    prod = (_f32(vals) * gather(x, cols)).reshape(T, -1)
    if mode == "onehot_mxu":
        onehot = jax.nn.one_hot(local_row.reshape(T, -1).astype(jnp.int32),
                                seg_rows, dtype=jnp.float32)
        return jnp.einsum("tc,tcm->tm", prod, onehot)
    cs = jnp.cumsum(prod, axis=1)
    # g[t, m] = inclusive cumsum at the last element of segment m
    end = seg_end.astype(jnp.int32)
    g = jnp.where(end > 0,
                  jnp.take_along_axis(cs, jnp.maximum(end - 1, 0), axis=1),
                  0.0)
    g_prev = jnp.concatenate([jnp.zeros((T, 1), g.dtype), g[:, :-1]], axis=1)
    return g - g_prev


# ----------------------------- multi-RHS (SpMM) -----------------------------

def ell_spmm_ref(vals: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    """Fused multi-RHS partials: vals, cols (T, R, W); x (n_cols, B)
    -> fp32 (T, R, B). Column b of x is the b-th right-hand side."""
    return jnp.einsum("trw,trwb->trb", _f32(vals), gather(x, cols))


def seg_spmm_ref(vals, cols, local_row, seg_end, x, seg_rows: int,
                 mode: str = "seg_scan") -> jax.Array:
    """Fused multi-RHS seg partials: vals/cols/local_row (T, S, L);
    x (n_cols, B) -> fp32 (T, M, B). Same two reduction modes as 1-RHS."""
    T = vals.shape[0]
    B = x.shape[1]
    prod = (_f32(vals)[..., None] * gather(x, cols)).reshape(T, -1, B)
    if mode == "onehot_mxu":
        onehot = jax.nn.one_hot(local_row.reshape(T, -1).astype(jnp.int32),
                                seg_rows, dtype=jnp.float32)
        return jnp.einsum("tcb,tcm->tmb", prod, onehot)
    cs = jnp.cumsum(prod, axis=1)
    end = seg_end.astype(jnp.int32)
    g = jnp.where((end > 0)[..., None],
                  jnp.take_along_axis(cs, jnp.maximum(end - 1, 0)[..., None],
                                      axis=1), 0.0)
    g_prev = jnp.concatenate([jnp.zeros((T, 1, B), g.dtype), g[:, :-1]],
                             axis=1)
    return g - g_prev


# --------------------------------- DIA ---------------------------------------

def dia_spmv_ref(vals, xp, offsets, pad_left: int, n_rows: int) -> jax.Array:
    """Diagonal SpMV as XLA static slices of the zero-padded x.

    vals (D, NB, 128), diagonal d's value of row r at vals[d].ravel()[r];
    xp the zero-padded x, (L,) or (L, B), with x[c] at xp[pad_left + c]
    -> fp32 y (n_rows,) or (n_rows, B): the sum over d of vals[d] times
    the window xp[pad_left + offsets[d]:][:n_rows], in the kernel's order.
    """
    v = _f32(vals).reshape(len(offsets), -1)[:, :n_rows]
    if xp.ndim > 1:
        v = v[..., None]
    y = jnp.zeros((n_rows,) + xp.shape[1:], jnp.float32)
    for d, off in enumerate(offsets):
        start = pad_left + off
        y = y + v[d] * _f32(jax.lax.slice_in_dim(xp, start, start + n_rows))
    return y
