"""kernel_ms: device time of the Mosaic kernels per call, summed from the
trace's device ops over the calls traced."""


def read(run):
    if run.trace is None or not run.calls or run.trace.kernel_s <= 0:
        return None
    return run.trace.kernel_s / run.calls * 1e3
