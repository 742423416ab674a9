"""Record the small chip trace that test_trace.py reduces.

    python3 bench/tests/record_trace.py <out_dir>

Runs the tests' tiny HPCG cell (8^3 grid) once with ``--trace 1`` on the
chip, and writes the raw ``.xplane.pb`` and the reduction made from it at
the time (``tiny_chain.expected.json``) into ``out_dir``. Copy both into
bench/tests/data/ to refresh the committed trace.
"""
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run as runmod  # noqa: E402
from bench.lib import trace as tracemod  # noqa: E402
from bench.tests.test_harness import TINY_CELL, tiny_registry  # noqa: E402


def main(out: Path) -> int:
    out.mkdir(parents=True, exist_ok=True)

    class KeepTracer(runmod.Tracer):
        def reduce(self):
            shutil.copy(tracemod.find_xplane(self.dir),
                        out / "tiny_chain.xplane.pb")
            return super().reduce()

    runmod.Tracer = KeepTracer
    with tempfile.TemporaryDirectory() as tmp:
        tmp = Path(tmp)
        res = runmod.run_cell(tiny_registry(tmp), TINY_CELL, 5, 0.02, True,
                              cache_dir=tmp / "cache")
    s = tracemod.reduce(str(out / "tiny_chain.xplane.pb"))
    expected = {"calls": res["attempted"], "window_s": s.window_s,
                "busy_s": s.busy_s, "kernel_s": s.kernel_s,
                "other_s": s.other_s, "breakdown": s.breakdown(),
                "result": res}
    (out / "tiny_chain.expected.json").write_text(
        json.dumps(expected, indent=1))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
