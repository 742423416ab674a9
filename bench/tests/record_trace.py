"""Record the small chip traces that test_trace.py reduces.

    python3 bench/tests/record_trace.py <out_dir> [--loop]

Runs the tests' tiny HPCG cell (8^3 grid) once with ``--trace 1`` on the
chip, and writes the raw ``.xplane.pb`` and the reduction made from it at
the time (``.expected.json``) into ``out_dir``: ``tiny_chain.*`` with one
call a dispatch, or with ``--loop`` ``tiny_loop.*``, K chained calls a
program in a ``fori_loop``. Copy both into bench/tests/data/ to refresh
the committed trace.
"""
import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run as runmod  # noqa: E402
from bench.lib import trace as tracemod  # noqa: E402
from bench.tests.test_harness import TINY_K, tiny_registry  # noqa: E402


def main(out: Path, loop: bool) -> int:
    out.mkdir(parents=True, exist_ok=True)
    k = max(TINY_K) if loop else 1
    stem = "tiny_loop" if loop else "tiny_chain"
    xplane = out / f"{stem}.xplane.pb"

    class KeepTracer(runmod.Tracer):
        def reduce(self):
            shutil.copy(tracemod.find_xplane(self.dir), xplane)
            return super().reduce()

    runmod.Tracer = KeepTracer
    with tempfile.TemporaryDirectory() as tmp:
        tmp = Path(tmp)
        res = runmod.run_cell(tiny_registry(tmp), TINY_K[k], 5, 0.02, True,
                              cache_dir=tmp / "cache")
    s = tracemod.reduce(str(xplane))
    expected = {"calls": res["attempted"], "calls_per_dispatch": k,
                "window_s": s.window_s, "busy_s": s.busy_s,
                "kernel_s": s.kernel_s, "other_s": s.other_s,
                "breakdown": s.breakdown(), "result": res}
    (out / f"{stem}.expected.json").write_text(json.dumps(expected, indent=1))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out", type=Path)
    ap.add_argument("--loop", action="store_true")
    args = ap.parse_args()
    sys.exit(main(args.out, args.loop))
