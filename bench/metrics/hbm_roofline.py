"""hbm_roofline: the least time the chip's HBM needs for a call's floor
bytes (bench/lib/floor.py), over the device's busy time per call in the
trace, in percent. The floor is a lower bound on what any correct plan
moves, so the share cannot pass 100 unless the trace misses work."""
from bench.lib.floor import floor_bytes


def read(run):
    if run.trace is None or not run.calls or run.trace.busy_s <= 0:
        return None
    f = run.facts
    floor_s = (floor_bytes(f["nnz"], f["n_rows"], f["n_cols"], f["batch"])
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / (run.trace.busy_s / run.calls)
