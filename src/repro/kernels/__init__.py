"""Pallas TPU kernels for AlphaSparse-generated formats.

Each kernel family has: the ``pl.pallas_call`` implementation with explicit
BlockSpec VMEM tiling (``ell_spmv.py``, ``seg_spmv.py``), a jitted wrapper
(``ops.py``), and a pure-jnp oracle (``ref.py``). On a TPU they compile
through Mosaic; elsewhere the same entry points run in the Pallas
interpreter (``repro.runtime.resolve_interpret``).
"""
from . import ops, ref  # noqa: F401
from .ops import ell_spmv, seg_spmv  # noqa: F401
