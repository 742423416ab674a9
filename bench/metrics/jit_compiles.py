"""jit_compiles: executables XLA compiled in this run, rather than loaded
from the persistent compilation cache (the program's counter
``jax.compiles``). A warm run reads 0; any other value names a program
that compiled again (``jax.compiles_by_fun``). None where the program
keeps no such counter."""
from bench.lib.telemetry import counter


def read(run):
    return counter("jax.compiles")
