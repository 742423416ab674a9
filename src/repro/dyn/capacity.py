"""Capacity accounting for machine-designed formats.

A compiled plan's packed arrays carry more room than the pattern that
built them: ELL lanes are padded to the tile width (``LANE_PAD`` rounds
further), seg streams are padded to the chunk size, and removals free
slots behind them. This module turns a plan's JSON kernel spec + format
arrays into an explicit capacity model that :mod:`repro.dyn.update`
consumes to prove a :class:`~repro.dyn.delta.PatternDelta` fits in place,
and that ``SpmvPlan.describe()`` / ``cost_analysis()`` surface as
headroom metadata.

The free-slot invariant mirrors ``SparseMatrix.canonical``: *a stored
value of 0 marks a free slot* (the builders zero-fill padding and
``canonical()`` drops explicit zeros, so no live entry is ever stored as
0). Capacity semantics per family:

* **ELL** (``LANE_ROW_BLOCK``): each mapped row owns one lane of width W;
  headroom per row is ``W - row_len``. Adds need a mutable (array-mode)
  cols array and slack in the target row's lane.
* **seg** (``LANE_NNZ_BLOCK``): row ownership of every stream position is
  frozen in the segment descriptors; adds can only fill a free position
  *already owned by the same row* (a prior removal, or tail padding for
  the stream's last row). Removals and revalues always fit.
* **dia** (``DIAG_BLOCK``): slot (d, r) holds the entry on diagonal d of
  row r; its zero slots on the matrix are free. Adds fit on a stored
  diagonal and never on another: the offsets are the step's pattern.
* **model-elided cols**: the column array was replaced by a fitted model
  at pack time — the pattern is frozen; only revalues and removals fit.
* **int16 cols**: narrowing only happens when ``n_cols`` fits int16, so
  any in-bounds column index fits; the margin is reported anyway.

Fused-combine metadata (affine rowmaps, ``fused_rows`` slabs, seg
descriptors) is never touched by an in-place update, so fused-kernel
preconditions hold by construction.
"""
from __future__ import annotations

import numpy as np

__all__ = ["capacity_report", "capacity_lines", "INT16_COL_LIMIT"]

INT16_COL_LIMIT = 32767


def ell_lane_rows(step: dict, fmt: dict) -> np.ndarray:
    """Global row owning each (tile, lane) of an ELL step; -1 = padding.

    Reads the rowmap array when stored, or rebuilds it from the affine
    combine parameters (slope-1 elided rowmap: lane ``i`` of the flat
    tile stream owns row ``b0 + i`` for ``i < nv``)."""
    comb = step["combine"]
    vals = fmt[f"{step['key']}_vals"]
    T, R = vals.shape[0], vals.shape[1]
    if comb["mode"] == "rowmap":
        return np.asarray(fmt[comb["key"]]).astype(np.int64)
    flat = np.arange(T * R, dtype=np.int64)
    rows = np.where(flat < int(comb["nv"]), int(comb["b0"]) + flat, -1)
    return rows.reshape(T, R)


def seg_position_rows(step: dict, fmt: dict) -> np.ndarray:
    """Global row owning each flat stream position of a seg step.

    Three sources, in order of directness: the stored global row stream
    (``gmem_atom``), the local-segment array composed with the rowmap
    (``onehot_mxu``), or the CSR5-style segment-end descriptor
    (``seg_scan`` — position p belongs to the first segment whose
    exclusive end exceeds p)."""
    key = step["key"]
    vals = np.asarray(fmt[f"{key}_vals"])
    T = vals.shape[0]
    chunk = int(np.prod(vals.shape[1:]))
    if f"{key}_rows" in fmt:
        return np.asarray(fmt[f"{key}_rows"]).reshape(T, chunk).astype(np.int64)
    rowmap = np.asarray(fmt[f"{key}_rowmap"]).astype(np.int64)
    if f"{key}_local" in fmt:
        local = np.asarray(fmt[f"{key}_local"]).reshape(T, chunk)
        return np.take_along_axis(rowmap, local.astype(np.int64), axis=1)
    seg_end = np.asarray(fmt[f"{key}_end"])         # (T, seg_rows), ends
    pos = np.arange(chunk)
    # segment index per position: ends are non-decreasing per tile
    # (existing segments ascend, absent ones sit at `chunk`)
    seg_of = (seg_end[:, None, :] <= pos[None, :, None]).sum(axis=2)
    return np.take_along_axis(rowmap, seg_of, axis=1)


def _occupancy(vals: np.ndarray) -> np.ndarray:
    return np.asarray(vals).astype(np.float32) != 0.0


def _dia_matrix_slots(step: dict, n_cols: int) -> int:
    """Slots of a dia step that lie on the matrix: row r of diagonal d
    where 0 <= r + offsets[d] < n_cols."""
    off = np.asarray(step["offsets"], np.int64)
    n_rows = int(step["n_rows"])
    lo = np.maximum(0, -off)
    hi = np.minimum(n_rows, n_cols - off)
    return int(np.maximum(hi - lo, 0).sum())


def capacity_report(plan) -> dict:
    """Headroom metadata for every step of a dense ``SpmvPlan``.

    Returns a JSON-able dict: per-step occupancy/slack plus the headline
    aggregates (``ell_slack``, ``seg_headroom``, ``dia_headroom``,
    ``frozen_steps``,
    ``int16_col_margin``, ``live_nnz``) the capacity checker and
    ``describe()`` share."""
    spec = plan.spec
    fmt = plan.fmt
    steps_out = []
    ell_slack = seg_headroom = dia_headroom = live_nnz = frozen = 0
    int16_margin = None
    for step in spec["steps"]:
        key = step["key"]
        vals = np.asarray(fmt[f"{key}_vals"])
        occ = _occupancy(vals)
        used = int(occ.sum())
        live_nnz += used
        if step["kind"] == "dia":        # no column array to freeze
            free = _dia_matrix_slots(step, int(spec["n_cols"])) - used
            dia_headroom += free
            steps_out.append({"key": key, "kind": "dia",
                              "mutable_cols": False, "slots": int(occ.size),
                              "used": used, "free_slots": free,
                              "diagonals": len(step["offsets"])})
            continue
        mutable = step["cols"]["mode"] == "array"
        if not mutable:
            frozen += 1
        entry = {"key": key, "kind": step["kind"], "mutable_cols": mutable,
                 "slots": int(occ.size), "used": used}
        if step["kind"] == "ell":
            rows = ell_lane_rows(step, fmt)
            W = vals.shape[2]
            lane_len = occ.sum(axis=2)
            mapped = rows >= 0
            free = int((W - lane_len[mapped]).sum())
            entry.update(width=int(W), mapped_rows=int(mapped.sum()),
                         free_slots=free,
                         min_row_slack=int((W - lane_len[mapped]).min())
                         if mapped.any() else 0)
            if mutable:
                ell_slack += free
            else:
                entry["free_slots"] = 0  # frozen pattern: slack unusable
        else:
            free = int(occ.size - used)
            entry.update(free_slots=free if mutable else 0)
            if mutable:
                seg_headroom += free
        if mutable:
            dt = np.asarray(fmt[step["cols"]["key"]]).dtype
            entry["cols_dtype"] = str(dt)
            if dt == np.int16:
                margin = INT16_COL_LIMIT - (int(spec["n_cols"]) - 1)
                entry["int16_col_margin"] = margin
                int16_margin = (margin if int16_margin is None
                                else min(int16_margin, margin))
        steps_out.append(entry)
    return {"plan_version": int(getattr(plan, "plan_version", 0)),
            "live_nnz": live_nnz, "birth_nnz": int(spec["nnz"]),
            "ell_slack": ell_slack, "seg_headroom": seg_headroom,
            "dia_headroom": dia_headroom,
            "frozen_steps": frozen, "int16_col_margin": int16_margin,
            "steps": steps_out}


def capacity_lines(plan) -> list:
    """``describe()`` rendering of :func:`capacity_report`."""
    rep = capacity_report(plan)
    head = (f"  capacity: live_nnz={rep['live_nnz']} "
            f"(birth {rep['birth_nnz']}) ell_slack={rep['ell_slack']} "
            f"seg_headroom={rep['seg_headroom']} "
            f"version={rep['plan_version']}")
    if any(s["kind"] == "dia" for s in rep["steps"]):
        head += f" dia_headroom={rep['dia_headroom']}"
    if rep["frozen_steps"]:
        head += f" frozen_steps={rep['frozen_steps']}"
    if rep["int16_col_margin"] is not None:
        head += f" int16_col_margin={rep['int16_col_margin']}"
    lines = [head]
    for s in rep["steps"]:
        detail = (f"    step {s['key']}: used {s['used']}/{s['slots']}"
                  f" free={s['free_slots']}")
        if s["kind"] == "dia":
            detail += f" diagonals={s['diagonals']}"
        elif not s["mutable_cols"]:
            detail += " cols=frozen(model-elided)"
        lines.append(detail)
    return lines
