"""xla_ms: device time of every other op (gathers, combine, the chain's
rescale) per call, from the trace's device ops over the calls traced."""


def read(run):
    if run.trace is None or not run.calls or run.trace.other_s <= 0:
        return None
    return run.trace.other_s / run.calls * 1e3
