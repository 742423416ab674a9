"""Platform-derived execution settings.

* :func:`resolve_interpret` — Pallas interpret mode is not a user choice:
  kernels run through Mosaic on a TPU and in the interpreter everywhere
  else. An explicit bool still wins (tests pin it).
* :func:`enable_compilation_cache` — JAX's persistent compilation cache for
  entry points (``chip_smoke.py``, ``repro-compile``). Where
  ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself; otherwise the
  cache lives at the fixed ``<checkout>/.jax_cache`` (the path is part of
  the cache key, so it must not move between runs). Either way source
  locations keep one frame, so that a cache key does not depend on the
  caller.
* :func:`refuse_if_chip_held` — a TPU belongs to one process: a parent
  that holds it blocks every child that needs it. Code that starts such
  children calls this first.

Importing this module does not import jax.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["resolve_interpret", "on_tpu", "enable_compilation_cache",
           "refuse_if_chip_held", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for the Pallas kernels: ``interpret`` when given,
    else True exactly when the default backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return not on_tpu()


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax
    # A Mosaic kernel's serialized body carries its ops' source locations,
    # which by default name up to 10 caller frames: the same plan called
    # from two call sites would then have two cache keys and compile
    # twice. One frame keeps the key the same. (Innermost-frame locations,
    # jax_include_full_tracebacks_in_locations=False, would too, but they
    # drop the name stack from the device names of ops lowered in line.)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def refuse_if_chip_held(what: str) -> None:
    """Raise before ``what`` starts a child that needs the TPU while this
    process holds it (the child would fail or hang). A process that has
    not initialised a JAX backend yet holds nothing."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and on_tpu():
        raise RuntimeError(
            f"{what} starts a child process that needs the TPU, but this "
            "process already holds it; run it before this process touches "
            "a JAX device, or under JAX_PLATFORMS=cpu")
