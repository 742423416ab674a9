"""The least bytes any correct plan moves for one call, from the matrix
alone: the fp32 values once, x read and y written once per right-hand
side. Column indices are left out on purpose: a format may compress or
elide them, but random fp32 values cannot be compressed, so no correct
plan moves fewer bytes than this."""


def floor_bytes(nnz: int, n_rows: int, n_cols: int, batch: int) -> int:
    return 4 * nnz + 4 * (n_rows + n_cols) * max(batch, 1)
