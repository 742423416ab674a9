"""Every Pallas entry point in ``repro.kernels.ops`` lowers through Mosaic.

Each kernel is compiled ahead of time for one chip of a described TPU
v5e (no chip needed: the TPU compiler is part of the installation) at the
widths ``chip_smoke.py`` runs: n_cols = 1,048,576, and the (R, W) / (S, L,
M) geometry ``plan_format`` emits for its two matrices — the 27-point
stencil row (R=8, W=27), a power-law matrix's widest row (W past one
128-lane chunk and not a multiple of it), and its 2048- and 512-nnz seg
tiles. The dist path's canonical (8, 8) ELL chunks and (16, 8) seg chunks
are compiled too, and the diagonal kernel at HPCG's 27 offsets (plane
stride 10,816) and at a 7-offset band.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

N_COLS = 1_048_576
B = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


ELL_GEOMS = {
    "stencil_r8": (131_072, 8, 27),
    "wide_row": (1, 8, 662_886),
}


@pytest.mark.parametrize("geom", sorted(ELL_GEOMS))
@pytest.mark.parametrize("tiles_per_step", [1, 4, 8])
@pytest.mark.parametrize("entry", ["ell_spmv", "ell_spmm"])
def test_ell_entry_points_lower(one_chip, entry, tiles_per_step, geom):
    T, R, W = ELL_GEOMS[geom]
    x = (N_COLS, B) if entry == "ell_spmm" else (N_COLS,)
    op = functools.partial(getattr(ops, entry), tiles_per_step=tiles_per_step,
                           interpret=False)
    _compile(one_chip, op,
             ((T, R, W), jnp.float32), ((T, R, W), jnp.int32),
             (x, jnp.float32))


SEG_GEOMS = {
    "powerlaw_c2048": (1407, 16, 128, 1696),
    "stencil_c512": (55_296, 4, 128, 24),
}


def _seg_args(T, S, L, M, x, fused):
    tile, idx = ((T, S, L), jnp.float32), ((T, S, L), jnp.int32)
    r0 = (((T,), jnp.int32),) if fused else ()
    return (tile, idx, idx, ((T, M), jnp.int32)) + r0 + ((x, jnp.float32),)


@pytest.mark.parametrize("geom", sorted(SEG_GEOMS))
@pytest.mark.parametrize("mode", ["seg_scan", "onehot_mxu"])
@pytest.mark.parametrize("entry", ["seg_spmv", "seg_spmm", "seg_spmv_fused",
                                   "seg_spmm_fused"])
def test_seg_entry_points_lower(one_chip, entry, mode, geom):
    T, S, L, M = SEG_GEOMS[geom]
    fused = entry.endswith("fused")
    x = (N_COLS, B) if "spmm" in entry else (N_COLS,)
    kw = dict(n_rows=N_COLS, n_out=N_COLS + M, tiles_per_step=4) \
        if fused else {}
    op = functools.partial(getattr(ops, entry), seg_rows=M, mode=mode,
                           interpret=False, **kw)
    _compile(one_chip, op, *_seg_args(T, S, L, M, x, fused))


@pytest.mark.parametrize("entry", ["ell_spmv", "ell_spmm", "seg_spmv",
                                   "seg_spmm"])
def test_dist_chunks_lower(one_chip, entry):
    """The kernels a sharded plan's shard_map body runs, at the stacked
    families' canonical chunk geometry."""
    x = (N_COLS, B) if "spmm" in entry else (N_COLS,)
    if entry.startswith("ell"):
        T, R, W = 65_536, 8, 8
        _compile(one_chip,
                 functools.partial(getattr(ops, entry), interpret=False),
                 ((T, R, W), jnp.float32), ((T, R, W), jnp.int32),
                 (x, jnp.float32))
    else:
        T, S, L, M = 16_384, 16, 8, 32
        _compile(one_chip,
                 functools.partial(getattr(ops, entry), seg_rows=M,
                                   mode="seg_scan", interpret=False),
                 *_seg_args(T, S, L, M, x, False))


def test_bf16_storage_lowers(one_chip):
    """bf16-stored vals (the searched storage dtype) lower in the ELL
    kernel and the fused seg kernel."""
    _compile(one_chip,
             functools.partial(ops.ell_spmv, tiles_per_step=8,
                               interpret=False),
             ((131_072, 8, 27), jnp.bfloat16), ((131_072, 8, 27), jnp.int32),
             ((N_COLS,), jnp.float32))
    T, S, L, M = SEG_GEOMS["powerlaw_c2048"]
    _compile(one_chip,
             functools.partial(ops.seg_spmv_fused, seg_rows=M,
                               n_rows=N_COLS, n_out=N_COLS + M,
                               mode="seg_scan", interpret=False),
             ((T, S, L), jnp.bfloat16),
             *_seg_args(T, S, L, M, (N_COLS,), True)[1:])


DIA_OFFSETS = {
    "hpcg27": tuple(sorted(dz * 10_816 + dy * 104 + dx for dz in (-1, 0, 1)
                           for dy in (-1, 0, 1) for dx in (-1, 0, 1))),
    "band7": tuple(range(-3, 4)),
}


@pytest.mark.parametrize("n_rows", [131_072, 1_124_864 // 8 + 77])
@pytest.mark.parametrize("offsets", sorted(DIA_OFFSETS))
def test_dia_entry_point_lowers(one_chip, offsets, n_rows):
    """The diagonal kernel with x whole-resident in VMEM, at a row count
    that fills whole tiles and one that ends in a partial tile."""
    _compile_dia(one_chip, DIA_OFFSETS[offsets], n_rows, jnp.float32)


@pytest.mark.parametrize("n_rows", [300, 1_500, 5_000, 140_685])
def test_dia_bf16_storage_lowers(one_chip, n_rows):
    """bf16-stored vals, also in tiles shorter than one chunk and with a
    short last chunk (static starts)."""
    _compile_dia(one_chip, DIA_OFFSETS["band7"], n_rows, jnp.bfloat16)


def _compile_dia(sharding, offs, n_rows, dtype):
    from repro.kernels.dia_spmv import geometry
    pad_left, x_rows = geometry(n_rows, offs)
    nb = -(-n_rows // 128)
    op = functools.partial(ops.dia_spmv, offsets=offs, pad_left=pad_left,
                           n_rows=n_rows, interpret=False)
    _compile(sharding, op, ((len(offs), nb, 128), dtype),
             ((x_rows, 128), jnp.float32))
