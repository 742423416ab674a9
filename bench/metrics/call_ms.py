"""call_ms: the window's wall-clock (host clock, ending in
block_until_ready) over the plan calls completed in it."""


def read(run):
    if not run.calls:
        return None
    return run.window_s / run.calls * 1e3
