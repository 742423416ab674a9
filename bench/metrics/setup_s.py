"""setup_s: process start to the window's start (host clock): imports,
device start-up, the matrix, the plan (a search on the first run in a
checkout, a load afterwards) and the warm call."""


def read(run):
    return run.setup_s
