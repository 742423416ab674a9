"""plan_load_s: seconds the program spent in its plan store's lookup
(span ``repro.store.get``: the matrix fingerprint and key, the plan
file's read and placement, its content checksum), as the program times
it from inside. None where the program has no such span."""
from bench.lib.telemetry import span_total_s


def read(run):
    return span_total_s("repro.store.get")
