"""The one compile API: ``repro.compile(matrix, target) -> SpmvPlan``.

AlphaSparse's contract is "arbitrary sparse matrix in, performant
machine-designed format + kernel out" (paper §III). This module is that
contract as a single surface:

* :class:`Target` — where the program runs: backend ("jax" | "pallas"),
  an optional device mesh (sharded execution), partition mode/balance,
  decode batch size, dtype.
* :func:`compile` — matrix + Target (+ search budget) in, :class:`SpmvPlan`
  out. ``budget`` is a ``SearchConfig`` (or seconds); ``graph=`` skips the
  search and designs with a fixed Operator Graph.
* :class:`SpmvPlan` / :class:`ShardedSpmvPlan` — THE program artifact: a
  registered JAX pytree whose *leaves* are the packed format arrays (no
  jitted-closure constants) and whose static treedef is the winning
  Operator Graph + kernel spec + Target. Plans call (1-D SpMV / 2-D fused
  SpMM dispatch), ``save``/``load`` through npz (graph + arrays — the
  loaded plan is bit-identical, no graph replay needed), ``describe()``
  and ``cost_analysis()``.
* :class:`PlanStore` — a directory of saved plans keyed by
  (matrix fingerprint, budget, Target); supersedes ``ProgramCache``'s
  replay-only entries for serving restarts.

The historical entrypoints (``search``, ``build_spmv``,
``sparsify_linear*``) are thin deprecated shims over this module.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import os
import tempfile
import warnings
from pathlib import Path
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.graph import OperatorGraph, run_graph
from repro.core.kernel_builder import build_kernel, build_program, spec_slots
from repro.core.matrices import SparseMatrix
from repro.core.search import (ProgramCache, SearchConfig, SearchResult,
                               _graph_from_jsonable, _graph_to_jsonable,
                               run_search)
from repro.runtime import resolve_interpret

__all__ = ["Target", "SpmvPlan", "ShardedSpmvPlan", "PlanStore", "PlanWatch",
           "PlanIntegrityError", "compile", "load_plan"]

# Version 2 adds bf16 storage (arrays saved as uint16 views under
# "bf16!"-marked keys). Plans without bf16 arrays are still written as
# version 1, so older readers keep loading everything they can actually
# restore and get the clean "format too new" error otherwise.
PLAN_FORMAT_VERSION = 2


class PlanIntegrityError(ValueError):
    """A saved plan's content checksum does not match its arrays.

    Distinct from a truncated file (which fails inside ``np.load``): the
    zip container is intact but the payload differs from what ``save``
    wrote — silent disk corruption, a partial copy, or tampering.
    ``PlanStore.get`` treats it like any other unusable entry (recompile);
    ``PlanStore.verify``/``repair`` surface and quarantine it."""


def _content_checksum(header: dict, arrays: dict) -> str:
    """sha256 over the header (checksum field excluded) and every array's
    (key, dtype, shape, bytes), in sorted key order."""
    h = hashlib.sha256()
    h.update(json.dumps({k: v for k, v in header.items()
                         if k != "checksum"}, sort_keys=True).encode())
    for k in sorted(arrays):
        a = np.ascontiguousarray(arrays[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _atomic_savez(path, header: dict, arrays: dict) -> None:
    """Crash-safe plan write: checksum the content, write to a tempfile in
    the destination directory, fsync, then ``os.replace`` — readers (and
    ``PlanStore.watch`` pollers) only ever observe the old file or the
    complete new one, never a half-written npz.

    ``np.savez`` is handed an open file object (not a path) because the
    path form appends ".npz" when the suffix is missing, which would break
    the atomic rename."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    header = dict(header)
    header["checksum"] = _content_checksum(header, arrays)
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, __plan__=np.str_(json.dumps(header)), **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _atomic_write_text(path, text: str) -> None:
    path = Path(path)
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# --------------------------------- Target ----------------------------------

@dataclasses.dataclass(frozen=True)
class Target:
    """Where a compiled plan runs.

    ``backend="jax"`` is the pure-jnp program (CPU oracle / timing);
    ``"pallas"`` the TPU kernels: Mosaic-lowered on a TPU, the Pallas
    interpreter elsewhere (``repro.runtime.resolve_interpret``). A
    non-None ``mesh`` compiles a sharded plan over
    ``axis_name`` with the given ``partition`` mode ("row" | "col") and
    boundary ``balance`` ("nnz" | "rows"). ``batch_size`` is the number of
    right-hand sides the plan is tuned for (B > 1 makes the search time
    candidates on the fused SpMM path). ``dtype`` is the activation AND
    preferred storage dtype: ``"bfloat16"`` feeds x as bf16 and lets the
    search choose bf16-stored vals (+ int16 cols where n_cols fits) per
    matrix — kernels always accumulate in float32, so outputs stay fp32.
    """

    backend: str = "jax"
    mesh: Optional[object] = None          # jax.sharding.Mesh
    axis_name: str = "data"
    partition: str = "row"
    balance: str = "nnz"
    batch_size: int = 1
    dtype: str = "float32"

    def __post_init__(self):
        if self.backend not in ("jax", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.partition not in ("row", "col"):
            raise ValueError(f"unknown partition {self.partition!r}")
        if self.dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unsupported dtype {self.dtype!r} "
                             "(float32 | bfloat16)")

    def spec_dict(self) -> dict:
        """JSON-able identity (mesh reduced to its axis shape)."""
        d = {f.name: getattr(self, f.name)
             for f in dataclasses.fields(self) if f.name != "mesh"}
        d["mesh"] = (None if self.mesh is None
                     else sorted((str(k), int(v))
                                 for k, v in dict(self.mesh.shape).items()))
        return d

    def key(self) -> str:
        blob = json.dumps(self.spec_dict(), sort_keys=True)
        return hashlib.sha1(blob.encode()).hexdigest()[:8]

    @property
    def runs_interpreted(self) -> bool:
        """Whether this Target's kernels run in the Pallas interpreter on
        this platform (backend="pallas" off a TPU)."""
        return self.backend == "pallas" and resolve_interpret()


def _x_dtype(target: Target):
    return jnp.bfloat16 if target.dtype == "bfloat16" else jnp.float32


# npz cannot serialize ml_dtypes extension dtypes (bfloat16 lands as a raw
# void field); bf16 arrays travel as uint16 views under a marked key and
# are view-cast back on load — a bit-identical round trip.
_BF16_PREFIX = "bf16!"


def _npz_arrays(prefix: str, arrays: dict) -> dict:
    out = {}
    for k, v in arrays.items():
        a = np.asarray(v)
        if a.dtype == np.dtype(jnp.bfloat16):
            out[f"{prefix}::{_BF16_PREFIX}{k}"] = a.view(np.uint16)
        else:
            out[f"{prefix}::{k}"] = a
    return out


def _format_version(npz_arrays: dict) -> int:
    """1 for plans any reader can restore; 2 when bf16 keys are present
    (older readers would mis-restore them, so the version gate fires)."""
    tag = f"::{_BF16_PREFIX}"
    return 2 if any(tag in k for k in npz_arrays) else 1


def _npz_restore(prefix: str, z) -> dict:
    tag = f"{prefix}::"
    out = {}
    for k in z.files:
        if not k.startswith(tag):
            continue
        name = k[len(tag):]
        a = z[k]
        if name.startswith(_BF16_PREFIX):
            name = name[len(_BF16_PREFIX):]
            a = a.view(np.dtype(jnp.bfloat16))
        out[name] = jnp.asarray(a)
    return out


# ------------------------------ dense plans ---------------------------------

@functools.lru_cache(maxsize=256)
def _dense_kernel(spec_json: str, backend: str):
    # runs on a cache miss only: a new jitted program, traced on first call
    telemetry.count("repro.plan.kernel_builds")
    spec = json.loads(spec_json)
    free, gathered = spec_slots(spec)
    telemetry.count("repro.plan.nnz_gather_free", free)
    telemetry.count("repro.plan.nnz_gathered", gathered)
    return jax.jit(build_kernel(spec, backend=backend))


@dataclasses.dataclass(eq=False)
class SpmvPlan:
    """A compiled (single-mesh-less) SpMV/SpMM program artifact.

    Pytree: leaves are the format arrays (``fmt``), everything else is
    static treedef — so a plan can be passed through ``jax.jit`` /
    ``shard_map`` boundaries, donated, or checkpointed like any other
    parameter pytree.
    """

    supports_batch = True

    fmt: dict                       # name -> array  (the pytree leaves)
    spec_json: str                  # kernel spec (kernel_builder schema)
    graph_json: Optional[str]       # winning OperatorGraph, if any
    target: Target
    search_gflops: Optional[float] = None
    # failure-reason counts from the search that produced this plan, as a
    # sorted tuple of (taxonomy bucket, count) pairs — serialized, so a
    # plan born from a crash-riddled search stays visible after the fact
    failure_counts: Optional[tuple] = None
    # monotonic lineage version, bumped by every in-place update() and
    # background re-search adoption (repro.dyn). Serialized in the plan
    # header so hot-swap admission can reject a stale re-published store
    # entry; deliberately NOT part of the pytree aux data — bumping it
    # must never retrace jitted callers
    plan_version: int = 0
    # ephemeral: the full SearchResult when this plan came from a live
    # search in this process (not serialized, not part of the pytree)
    search_result: Optional[SearchResult] = dataclasses.field(
        default=None, compare=False, repr=False)

    # -- geometry ----------------------------------------------------------
    @functools.cached_property
    def spec(self) -> dict:
        return json.loads(self.spec_json)

    @property
    def n_rows(self) -> int:
        return self.spec["n_rows"]

    @property
    def n_cols(self) -> int:
        return self.spec["n_cols"]

    @property
    def nnz(self) -> int:
        return self.spec["nnz"]

    @property
    def graph(self) -> Optional[OperatorGraph]:
        if self.graph_json is None:
            return None
        return _graph_from_jsonable(json.loads(self.graph_json))

    @property
    def stored_bytes(self) -> int:
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in self.fmt.values())

    # -- execution ---------------------------------------------------------
    def __call__(self, x) -> jax.Array:
        """x: (n_cols,) -> (n_rows,), or (n_cols, B) -> (n_rows, B)."""
        with telemetry.span("repro.plan.call"):
            dtype = _x_dtype(self.target)
            if not (isinstance(x, jax.Array) and x.dtype == dtype):
                # an array already on the device in its dtype skips it:
                # 10-15 us of host time on every call
                x = jnp.asarray(x, dtype)
            fn = _dense_kernel(self.spec_json, self.target.backend)
            return fn(self.fmt, x)

    # -- dynamic sparsity --------------------------------------------------
    def update(self, delta) -> "SpmvPlan":
        """Patch-in-place dynamic-sparsity step (``repro.dyn``).

        Applies a :class:`repro.dyn.PatternDelta` to the packed format
        arrays — new leaves, same static treedef, no Operator Graph
        replay, no kernel rebuild, no jit retrace — and returns the
        patched plan with ``plan_version + 1``. Raises
        ``repro.dyn.CapacityError`` when the delta does not fit the
        format in place (escalate to ``repro.dyn.DynamicSparsityManager``
        or a fresh :func:`compile`). For streams of deltas, hold a
        ``repro.dyn.PlanPatcher`` instead: it keeps the capacity index
        across calls, making each update O(delta)."""
        from repro.dyn.update import update_plan
        return update_plan(self, delta)

    # -- reporting ---------------------------------------------------------
    def describe(self) -> str:
        spec = self.spec
        g = self.graph
        lines = [f"SpmvPlan {spec['n_rows']}x{spec['n_cols']} "
                 f"nnz={spec['nnz']} padded={spec['padded_nnz']} "
                 f"stored={self.stored_bytes}B",
                 f"  target: backend={self.target.backend} "
                 f"interpret={self.target.runs_interpreted} "
                 f"batch_size={self.target.batch_size} "
                 f"dtype={self.target.dtype}",
                 f"  graph: {g.label() if g else '(heuristic)'}"]
        if self.search_gflops is not None:
            lines.append(f"  searched: {self.search_gflops:.3f} GFLOPS")
        if self.failure_counts:
            buckets = ", ".join(f"{k}={v}" for k, v in self.failure_counts)
            lines.append(f"  search failures: {buckets}")
        for s in spec["steps"]:
            lines.append(f"  step {s['key']}: {s['report']}")
        from repro.dyn.capacity import capacity_lines
        lines.extend(capacity_lines(self))
        return "\n".join(lines)

    def cost_analysis(self, batch_size: Optional[int] = None) -> dict:
        """XLA cost analysis of the compiled call."""
        b = batch_size if batch_size is not None else self.target.batch_size
        shape = (self.n_cols,) if b <= 1 else (self.n_cols, b)
        x = jax.ShapeDtypeStruct(shape, _x_dtype(self.target))
        fn = _dense_kernel(self.spec_json, self.target.backend)
        compiled = fn.lower(self.fmt, x).compile()
        out = dict(compiled.cost_analysis())
        # format capacity headroom (repro.dyn): how much pattern mutation
        # this plan can absorb in place before a re-search is needed
        from repro.dyn.capacity import capacity_report
        out["capacity"] = capacity_report(self)
        return out

    # -- serialization -----------------------------------------------------
    def save(self, path) -> None:
        arrays = _npz_arrays("fmt", self.fmt)
        header = {"format_version": _format_version(arrays), "kind": "dense",
                  "spec": self.spec, "graph": (None if self.graph_json is None
                                               else json.loads(self.graph_json)),
                  "target": self.target.spec_dict(),
                  "search_gflops": self.search_gflops,
                  "plan_version": int(self.plan_version),
                  "failure_counts": (None if self.failure_counts is None
                                     else [list(p)
                                           for p in self.failure_counts])}
        _atomic_savez(path, header, arrays)

    @staticmethod
    def load(path, mesh=None) -> "SpmvPlan | ShardedSpmvPlan":
        """Load any saved plan; sharded plans need ``mesh`` re-attached."""
        return load_plan(path, mesh=mesh)


def _target_from_dict(d: dict, mesh=None) -> Target:
    # plans saved before interpret mode was derived carry an "interpret"
    # key; it no longer selects anything
    kw = {k: v for k, v in d.items() if k not in ("mesh", "interpret")}
    return Target(mesh=mesh, **kw)


def _tree_flatten_plan(plan: SpmvPlan):
    keys = tuple(sorted(plan.fmt))
    leaves = tuple(plan.fmt[k] for k in keys)
    aux = (keys, plan.spec_json, plan.graph_json, plan.target,
           plan.search_gflops, plan.failure_counts)
    return leaves, aux


def _tree_unflatten_plan(aux, leaves) -> SpmvPlan:
    keys, spec_json, graph_json, target, gflops, failure_counts = aux
    return SpmvPlan(fmt=dict(zip(keys, leaves)), spec_json=spec_json,
                    graph_json=graph_json, target=target,
                    search_gflops=gflops, failure_counts=failure_counts)


jax.tree_util.register_pytree_node(SpmvPlan, _tree_flatten_plan,
                                   _tree_unflatten_plan)


# ------------------------------ sharded plans -------------------------------

@functools.lru_cache(maxsize=64)
def _sharded_fn(steps_json: str, mode: str, n_out: int, mesh, axis_name: str,
                sizes: tuple, n_cols: int, backend: str):
    from repro.dist.spmv import make_stacked_fn
    return make_stacked_fn(json.loads(steps_json), mode, n_out, mesh,
                           axis_name, sizes, n_cols, backend=backend)


@dataclasses.dataclass(eq=False)
class ShardedSpmvPlan:
    """A compiled sharded plan: per-family stacked format arrays (leaves,
    leading dim sharded over the mesh axis) + static shard geometry.

    Unlike the old closure design, each device stores only its 1/n_shards
    slice of every family stack; the shard_map body receives the stacks as
    operands and needs no ``lax.switch``.
    """

    supports_batch = True

    stacks: dict                    # name -> (n_shards, ...) arrays (leaves)
    steps_json: str                 # synthetic per-family kernel spec
    mode: str                       # 'row' | 'col'
    n_rows: int
    n_cols: int
    nnz: int
    band_rows: int                  # row mode: padded per-device band size
    bounds: tuple                   # ((start, stop), ...) per shard
    target: Target
    replicated_bytes: int = 0       # closure-design baseline (all shards)
    # aggregated per-shard failure taxonomy (sorted (bucket, count) pairs);
    # a "fallback" entry counts shards substituted with the baseline
    failure_counts: Optional[tuple] = None
    search_result: Optional[object] = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def n_shards(self) -> int:
        return len(self.bounds)

    @property
    def per_device_format_bytes(self) -> int:
        n = max(self.n_shards, 1)
        return sum(v.nbytes // n for v in self.stacks.values())

    @property
    def replicated_format_bytes(self) -> int:
        return self.replicated_bytes

    @classmethod
    def from_program(cls, sprog, target: Target,
                     search_result=None) -> "ShardedSpmvPlan":
        """Adopt a ``dist.spmv.ShardedSpmvProgram``'s stacked operands."""
        failure_counts = None
        if search_result is not None and getattr(search_result,
                                                 "failure_counts", None):
            failure_counts = tuple(
                sorted(search_result.failure_counts.items()))
        return cls(stacks=dict(sprog.stacks),
                   steps_json=json.dumps(sprog.steps),
                   mode=sprog.mode, n_rows=sprog.n_rows,
                   n_cols=sprog.n_cols, nnz=sprog.nnz,
                   band_rows=sprog.band_rows,
                   bounds=tuple((s.start, s.stop) for s in sprog.shards),
                   target=target,
                   replicated_bytes=sprog.replicated_format_bytes,
                   failure_counts=failure_counts,
                   search_result=search_result)

    def _fn(self):
        n_out = self.band_rows if self.mode == "row" else self.n_rows
        return _sharded_fn(self.steps_json, self.mode, n_out,
                           self.target.mesh, self.target.axis_name,
                           tuple(stop - start for start, stop in self.bounds),
                           self.n_cols, self.target.backend)

    def __call__(self, x) -> jax.Array:
        if self.target.mesh is None:
            raise ValueError("sharded plan has no mesh attached; load with "
                             "SpmvPlan.load(path, mesh=...) or rebuild the "
                             "Target with a mesh")
        with telemetry.span("repro.plan.call"):
            return self._fn()(self.stacks,
                              jnp.asarray(x, _x_dtype(self.target)))

    def update(self, delta):
        """Sharded plans do not support patch-in-place updates: a delta
        can move nnz across shard bounds, which changes the static shard
        geometry. Re-compile for the mutated matrix instead."""
        raise NotImplementedError(
            "ShardedSpmvPlan.update is not supported (a PatternDelta can "
            "cross shard bounds); re-run repro.compile on the mutated "
            "matrix")

    def describe(self) -> str:
        steps = json.loads(self.steps_json)
        lines = [f"ShardedSpmvPlan {self.n_rows}x{self.n_cols} "
                 f"nnz={self.nnz} mode={self.mode} "
                 f"shards={self.n_shards}",
                 f"  target: backend={self.target.backend} "
                 f"interpret={self.target.runs_interpreted} "
                 f"axis={self.target.axis_name}",
                 f"  format bytes/device: {self.per_device_format_bytes} "
                 f"(closure baseline {self.replicated_bytes})"]
        if self.failure_counts:
            buckets = ", ".join(f"{k}={v}" for k, v in self.failure_counts)
            lines.append(f"  shard-search failures: {buckets}")
        for s in steps:
            lines.append(f"  family {s['key']}: {s['report']}")
        return "\n".join(lines)

    def cost_analysis(self, batch_size: Optional[int] = None) -> dict:
        if self.target.mesh is None:
            raise ValueError("sharded plan has no mesh attached; load with "
                             "SpmvPlan.load(path, mesh=...) first")
        b = batch_size if batch_size is not None else self.target.batch_size
        shape = (self.n_cols,) if b <= 1 else (self.n_cols, b)
        x = jax.ShapeDtypeStruct(shape, _x_dtype(self.target))
        compiled = self._fn().lower(self.stacks, x).compile()
        return dict(compiled.cost_analysis())

    def save(self, path) -> None:
        arrays = _npz_arrays("stack", self.stacks)
        header = {"format_version": _format_version(arrays),
                  "kind": "sharded",
                  "steps": json.loads(self.steps_json), "mode": self.mode,
                  "n_rows": self.n_rows, "n_cols": self.n_cols,
                  "nnz": self.nnz, "band_rows": self.band_rows,
                  "bounds": [list(b) for b in self.bounds],
                  "replicated_bytes": self.replicated_bytes,
                  "failure_counts": (None if self.failure_counts is None
                                     else [[p[0], int(p[1])]
                                           for p in self.failure_counts]),
                  "target": self.target.spec_dict()}
        _atomic_savez(path, header, arrays)

    load = staticmethod(SpmvPlan.load)


def _tree_flatten_sharded(plan: ShardedSpmvPlan):
    keys = tuple(sorted(plan.stacks))
    leaves = tuple(plan.stacks[k] for k in keys)
    aux = (keys, plan.steps_json, plan.mode, plan.n_rows, plan.n_cols,
           plan.nnz, plan.band_rows, plan.bounds, plan.target,
           plan.replicated_bytes, plan.failure_counts)
    return leaves, aux


def _tree_unflatten_sharded(aux, leaves) -> ShardedSpmvPlan:
    (keys, steps_json, mode, n_rows, n_cols, nnz, band_rows, bounds,
     target, repl, failure_counts) = aux
    return ShardedSpmvPlan(stacks=dict(zip(keys, leaves)),
                           steps_json=steps_json, mode=mode, n_rows=n_rows,
                           n_cols=n_cols, nnz=nnz, band_rows=band_rows,
                           bounds=bounds, target=target,
                           replicated_bytes=repl,
                           failure_counts=failure_counts)


jax.tree_util.register_pytree_node(ShardedSpmvPlan, _tree_flatten_sharded,
                                   _tree_unflatten_sharded)


def load_plan(path, mesh=None) -> Union[SpmvPlan, ShardedSpmvPlan]:
    """Load a saved plan. Sharded plans need a live ``mesh`` re-attached
    (meshes name physical devices and are deliberately not serialized)."""
    with np.load(path, allow_pickle=False) as z:
        header = json.loads(str(z["__plan__"]))
        if header.get("format_version", 0) > PLAN_FORMAT_VERSION:
            raise ValueError(f"plan {path} has format_version "
                             f"{header['format_version']} > supported "
                             f"{PLAN_FORMAT_VERSION}")
        want = header.get("checksum")
        if want is not None:
            with telemetry.span("repro.store.verify"):
                arrays = {k: z[k] for k in z.files if k != "__plan__"}
                got = _content_checksum(header, arrays)
            if got != want:
                raise PlanIntegrityError(
                    f"plan {path} failed its content checksum "
                    f"(stored {want[:12]}…, computed {got[:12]}…): the "
                    "file is corrupt or was modified after save")
        with telemetry.span("repro.store.read"):
            return _plan_from_npz(path, z, header, mesh)


def _plan_from_npz(path, z, header: dict,
                   mesh) -> Union[SpmvPlan, ShardedSpmvPlan]:
    """The plan in an open plan file, its arrays read and placed."""
    if header["kind"] == "dense":
        fmt = _npz_restore("fmt", z)
        fc = header.get("failure_counts")
        return SpmvPlan(
            fmt=fmt, spec_json=json.dumps(header["spec"]),
            graph_json=(None if header["graph"] is None
                        else json.dumps(header["graph"])),
            target=_target_from_dict(header["target"]),
            search_gflops=header.get("search_gflops"),
            failure_counts=(None if fc is None
                            else tuple((k, int(v)) for k, v in fc)),
            plan_version=int(header.get("plan_version", 0)))
    target = _target_from_dict(header["target"], mesh=mesh)
    stacks = _npz_restore("stack", z)
    if mesh is not None:
        n_saved = len(header["bounds"])
        n_mesh = dict(mesh.shape).get(target.axis_name)
        if n_mesh != n_saved:
            raise ValueError(
                f"plan {path} was compiled for {n_saved} shards but the "
                f"attached mesh has {n_mesh} devices on axis "
                f"{target.axis_name!r}; re-compile for this mesh or "
                "attach a matching one")
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(mesh, P(target.axis_name))
        stacks = {k: jax.device_put(v, sharding)
                  for k, v in stacks.items()}
    fc = header.get("failure_counts")
    return ShardedSpmvPlan(
        stacks=stacks, steps_json=json.dumps(header["steps"]),
        mode=header["mode"], n_rows=header["n_rows"],
        n_cols=header["n_cols"], nnz=header["nnz"],
        band_rows=header["band_rows"],
        bounds=tuple(tuple(b) for b in header["bounds"]),
        target=target, replicated_bytes=header["replicated_bytes"],
        failure_counts=(None if fc is None
                        else tuple((k, int(v)) for k, v in fc)))


# -------------------------------- compile -----------------------------------

def _as_search_config(budget, target: Target) -> SearchConfig:
    if budget is None:
        cfg = SearchConfig()
    elif isinstance(budget, SearchConfig):
        cfg = budget
    elif isinstance(budget, (int, float)):
        cfg = SearchConfig(max_seconds=float(budget))
    else:
        raise TypeError(f"budget must be a SearchConfig or seconds, got "
                        f"{type(budget).__name__}")
    bsz = target.batch_size if target.batch_size > 1 else cfg.batch_size
    cfg = dataclasses.replace(cfg, backend=target.backend,
                              batch_size=max(bsz, 1))
    # widen the SET_RESOURCES knob choices from the Target, but only when
    # the budget left them at None ("auto") — an explicit tuple, even the
    # single-default one, pins the knob and is respected as-is: pallas
    # kernels have the fused megatile path, so the search tunes
    # tiles_per_step; dtype="bfloat16" means both precisions are searched
    # and the winner is picked per matrix.
    if target.backend == "pallas" and cfg.tiles_per_step_choices is None:
        cfg = dataclasses.replace(cfg, tiles_per_step_choices=(1, 4, 8))
    if target.dtype == "bfloat16" and cfg.dtype_choices is None:
        cfg = dataclasses.replace(cfg,
                                  dtype_choices=("float32", "bfloat16"))
    return cfg


def _plan_from_program(prog, graph: Optional[OperatorGraph],
                       target: Target, search_result=None) -> SpmvPlan:
    graph_json = (None if graph is None
                  else json.dumps(_graph_to_jsonable(graph)))
    failure_counts = None
    if search_result is not None and getattr(search_result,
                                             "failure_counts", None):
        failure_counts = tuple(sorted(search_result.failure_counts.items()))
    plan = SpmvPlan(fmt=dict(prog.fmt), spec_json=json.dumps(prog.spec),
                    graph_json=graph_json, target=target,
                    search_gflops=(search_result.gflops
                                   if search_result else None),
                    failure_counts=failure_counts,
                    search_result=search_result)
    return plan


@telemetry.span("repro.compile")
def compile(matrix: SparseMatrix, target: Optional[Target] = None,
            budget=None, *, graph: Optional[OperatorGraph] = None,
            strategy=None, warm_start=None, deadline_s: Optional[float] = None,
            cache: Optional[ProgramCache] = None,
            store: Optional["PlanStore"] = None
            ) -> Union[SpmvPlan, ShardedSpmvPlan]:
    """Matrix in, machine-designed program artifact out (paper §III).

    * ``target`` — where the plan runs (defaults to ``Target()``: jax
      backend, single device).
    * ``budget`` — search budget: a ``SearchConfig``, a number of seconds,
      or None for the default budget. With ``target.mesh`` set and
      ``budget=None``, shards take the search-free heuristic design.
    * ``graph`` — skip the search entirely and design with this Operator
      Graph (sharded targets apply it per shard).
    * ``strategy`` — the search policy walking the design space: a
      ``repro.design.SearchStrategy`` instance/class or a registered name
      ("anneal" | "grid" | "cost_model" | "learned" | "portfolio").
      Store-aware strategies get ``bind_store(store)`` called before the
      search, which is how "portfolio" reaches reuse suggestions and the
      trained corpus model. None = ``AnnealStrategy``, the
      historical SA walk (behavioral parity). Sharded targets pass the
      strategy to every per-shard search (no-op with ``budget=None``,
      where shards take the search-free heuristic design).
    * ``warm_start`` — optional iterable of ``OperatorGraph`` objects timed
      before the strategy's walk (dense targets only; per-shard searches
      ignore it). With a ``store`` given and no explicit warm start,
      ``store.suggest(matrix)`` (statistics-keyed nearest stored plan)
      seeds the search automatically.
    * ``deadline_s`` — wall-clock budget for the whole compile (dense
      searched targets). The search's ``max_seconds`` is clamped to it,
      the seed pass loses its 2x extension, every candidate runs under a
      per-candidate deadline derived from the time left, and a candidate
      whose estimated compile (its kernel steps x the slowest compile per
      step so far) does not fit is skipped as a timeout. A compile once
      started cannot be interrupted, so the first one can still overrun:
      ``plan.search_result.deadline_overrun_s`` says by how much.
      ``compile`` returns the best plan found (at worst the baseline
      jax-backend source-format program, never an error, as long as the
      matrix itself is designable).
    * ``cache`` — a ``ProgramCache`` memoising raw search results (keyed
      by matrix, budget AND strategy).
    * ``store`` — a :class:`PlanStore`; a prior plan for the same
      (matrix, budget, target) is loaded instead of recompiled, and new
      plans are saved. Store hits carry no ``search_result`` (the full
      ``SearchResult`` is process-ephemeral and not serialized) —
      ``search_gflops`` survives the round trip.
    """
    target = target or Target()
    if strategy is not None:
        # normalize once so store keys see the *bound* strategy: a
        # store-aware strategy ("portfolio", "learned") keys on its model
        # fingerprint, and get/put must agree on it
        from repro.design.strategies import make_strategy
        strategy = make_strategy(strategy)
        if store is not None and hasattr(strategy, "bind_store"):
            strategy.bind_store(store)
    if store is not None:
        hit = store.get(matrix, target, budget, graph, strategy)
        if hit is not None:
            return hit
        if warm_start is None and graph is None and target.mesh is None:
            # statistics-keyed warm start from the nearest stored plan
            # (dense targets only: per-shard warm-start is future work)
            suggested = store.suggest(matrix)
            warm_start = (suggested,) if suggested is not None else None

    if target.mesh is None:
        if graph is not None:
            meta = run_graph(matrix, graph)
            # Target.dtype overrides the storage dtype for fixed-graph
            # compiles (searched compiles pick it via SET_RESOURCES)
            prog = build_program(meta, backend=target.backend, jit=False,
                                 storage_dtype=(target.dtype
                                                if target.dtype != "float32"
                                                else None))
            plan = _plan_from_program(prog, graph, target)
        else:
            cfg = _as_search_config(budget, target)
            if deadline_s is not None:
                # the whole search — seed pass included — must fit inside
                # the caller's wall-clock budget; candidates inherit a
                # per-candidate deadline from the time remaining
                cfg = dataclasses.replace(
                    cfg, max_seconds=min(cfg.max_seconds, float(deadline_s)),
                    hard_deadline=True)
            res = run_search(matrix, cfg, cache=cache, strategy=strategy,
                             warm_start=warm_start)
            plan = _plan_from_program(res.best_program, res.best_graph,
                                      target, search_result=res)
    else:
        from repro.dist.search import ShardedSearchConfig, dist_search
        from repro.dist.spmv import shard_map_spmv
        search_result = None
        if graph is not None:
            sprog = shard_map_spmv(matrix, target.mesh,
                                   axis_name=target.axis_name,
                                   mode=target.partition,
                                   balance=target.balance,
                                   graph_for=lambda m: graph,
                                   backend=target.backend,
                                   storage_dtype=target.dtype)
        elif budget is None:
            sprog = shard_map_spmv(matrix, target.mesh,
                                   axis_name=target.axis_name,
                                   mode=target.partition,
                                   balance=target.balance,
                                   backend=target.backend,
                                   storage_dtype=target.dtype)
        else:
            if isinstance(budget, ShardedSearchConfig):
                # full per-shard control (min_nnz_for_search, seeds, ...);
                # the Target still decides placement and backend
                dcfg = dataclasses.replace(
                    budget, axis_name=target.axis_name,
                    mode=target.partition, balance=target.balance,
                    backend=target.backend)
                if strategy is not None:
                    dcfg = dataclasses.replace(dcfg, strategy=strategy)
            else:
                dcfg = ShardedSearchConfig(axis_name=target.axis_name,
                                           mode=target.partition,
                                           balance=target.balance,
                                           search=_as_search_config(
                                               budget, target),
                                           backend=target.backend,
                                           strategy=strategy)
            search_result = dist_search(matrix, target.mesh, dcfg,
                                        cache=cache)
            sprog = search_result.program
        plan = ShardedSpmvPlan.from_program(sprog, target,
                                            search_result=search_result)

    if store is not None:
        store.put(matrix, target, budget, graph, plan, strategy)
    return plan


# -------------------------------- PlanStore ---------------------------------

def _matrix_stats(matrix: SparseMatrix) -> list[float]:
    """Statistics key for nearest-plan lookup: size + row-length shape.

    The features are the ones the §VI-B pruning rules key on: row count,
    mean/std of nnz per row, and the row-length coefficient of variation
    (irregularity). Two matrices close in this space tend to get the same
    winning design, which is what makes the stored graph a useful warm
    start for *any* strategy."""
    lengths = np.bincount(np.asarray(matrix.rows, np.int64),
                          minlength=matrix.n_rows).astype(np.float64)
    mean = float(lengths.mean()) if lengths.size else 0.0
    std = float(lengths.std()) if lengths.size else 0.0
    cv = std / mean if mean > 0 else 0.0
    return [float(matrix.n_rows), mean, std, cv]


def _stats_distance(a, b) -> float:
    """Scale-normalized distance: log-scale for counts, linear for CV."""
    d = 0.0
    d += (np.log10(1.0 + a[0]) - np.log10(1.0 + b[0])) ** 2
    d += (np.log10(1.0 + a[1]) - np.log10(1.0 + b[1])) ** 2
    d += (np.log10(1.0 + a[2]) - np.log10(1.0 + b[2])) ** 2
    d += (a[3] - b[3]) ** 2
    return float(np.sqrt(d))


class PlanWatch:
    """Poll one PlanStore entry for changes (the serving hot-swap hook).

    Created by :meth:`PlanStore.watch`. ``poll()`` stats the entry's file
    and returns a freshly loaded plan iff its (mtime_ns, size) stamp
    changed since the last observation — None otherwise. A poll is one
    ``stat`` call, cheap enough for serving engines to issue between
    every decode step; a half-written or corrupt entry is skipped (the
    old plan keeps serving) and retried on the next poll.
    """

    def __init__(self, store: "PlanStore", key: str, mesh=None):
        self.store = store
        self.key = key
        self.mesh = mesh
        self._seen = self._stamp()

    @property
    def path(self) -> Path:
        return self.store._path(self.key)

    def _stamp(self):
        try:
            st = self.path.stat()
            return (st.st_mtime_ns, st.st_size)
        except OSError:
            return None

    def poll(self):
        stamp = self._stamp()
        if stamp is None or stamp == self._seen:
            return None
        try:
            plan = load_plan(self.path, mesh=self.mesh)
        except Exception:
            return None   # mid-write or corrupt: retry on the next poll
        self._seen = stamp
        return plan


class PlanStore:
    """A directory of saved plans keyed by (matrix, budget/graph, strategy,
    Target).

    Supersedes ``ProgramCache``'s replay-only disk entries: where the
    program cache stores the winning *graph* and re-runs the Designer +
    kernel builder on a hit, the plan store round-trips the full artifact
    (spec + format arrays) — a hit is a load, bit-identical to the saved
    plan, with no matrix or Designer replay required.

    Beyond exact hits, the store answers :meth:`suggest` — a statistics-
    keyed nearest-plan lookup (first step of the ROADMAP "autotune cache
    keyed on matrix statistics" item): each ``put`` writes a small
    ``.stats.json`` sidecar (matrix row statistics + winning graph), and
    ``suggest(matrix)`` returns the stored winning ``OperatorGraph`` of
    the statistically closest plan, which ``repro.compile`` uses to
    warm-start the search.
    """

    def __init__(self, cache_dir):
        self.cache_dir = Path(cache_dir)
        self.hits = 0
        self.misses = 0
        # suggest() sidecar index: path -> ((mtime_ns, size), payload).
        # payload is None for corrupt sidecars (negative cache). The whole
        # index is revalidated only when the *directory* mtime moves —
        # sidecars are written atomically (os.replace into the directory),
        # so every add/replace/remove bumps it.
        self._sidecars: dict[Path, tuple[tuple[int, int], Optional[dict]]] = {}
        self._sidecar_dir_stamp: Optional[int] = None

    @staticmethod
    def key(matrix: SparseMatrix, target: Target, budget=None,
            graph: Optional[OperatorGraph] = None, strategy=None) -> str:
        from repro.design.strategies import make_strategy
        mfp = ProgramCache.matrix_fingerprint(matrix)
        if graph is not None:
            bkey = "g" + hashlib.sha1(json.dumps(
                _graph_to_jsonable(graph)).encode()).hexdigest()[:8]
        elif budget is None:
            bkey = "default"
        elif dataclasses.is_dataclass(budget):   # SearchConfig / sharded cfg
            blob = json.dumps(dataclasses.asdict(budget), sort_keys=True,
                              default=str)
            bkey = hashlib.sha1(blob.encode()).hexdigest()[:8]
        else:
            bkey = f"s{float(budget):g}"
        if graph is None:
            # the strategy identity is part of the key (same collision
            # rule as ProgramCache): a grid-searched plan must not serve
            # an anneal-searched request for the same matrix/budget
            bkey += "-" + hashlib.sha1(
                make_strategy(strategy).key().encode()).hexdigest()[:8]
        return f"{mfp}-{bkey}-{target.key()}"

    def _path(self, key: str) -> Path:
        return self.cache_dir / f"{key}.plan.npz"

    @telemetry.span("repro.store.get")
    def get(self, matrix, target, budget=None, graph=None, strategy=None):
        with telemetry.span("repro.store.key"):
            key = self.key(matrix, target, budget, graph, strategy)
        path = self._path(key)
        if not path.exists():
            self.misses += 1
            return None
        try:
            plan = load_plan(path, mesh=target.mesh)
        except Exception as e:  # truncated/corrupt npz or checksum
            # mismatch (PlanIntegrityError): recompile, like ProgramCache,
            # instead of failing forever
            warnings.warn(f"plan store entry {path} unusable ({e!r}); "
                          "recompiling", RuntimeWarning)
            self.misses += 1
            return None
        self.hits += 1
        return plan

    def put(self, matrix, target, budget, graph, plan,
            strategy=None) -> None:
        self.cache_dir.mkdir(parents=True, exist_ok=True)
        key = self.key(matrix, target, budget, graph, strategy)
        plan.save(self._path(key))
        graph_json = getattr(plan, "graph_json", None)
        if graph_json is not None:
            from repro.corpus.features import matrix_features
            sidecar = {"stats": _matrix_stats(matrix),
                       "features": matrix_features(matrix).tolist(),
                       "graph": json.loads(graph_json),
                       "gflops": getattr(plan, "search_gflops", None)}
            _atomic_write_text(self.cache_dir / f"{key}.stats.json",
                               json.dumps(sidecar))

    def verify(self) -> dict:
        """Integrity sweep over every stored entry.

        Loads each ``*.plan.npz`` (no mesh attached — sharded geometry
        checks are deferred to serving) and returns
        ``{"ok": [keys], "corrupt": [(key, reason)]}``. Truncated files,
        bad zip containers and checksum mismatches all land in
        ``corrupt``; nothing is modified — use :meth:`repair` to
        quarantine them."""
        ok, corrupt = [], []
        if self.cache_dir.is_dir():
            for path in sorted(self.cache_dir.glob("*.plan.npz")):
                key = path.name[:-len(".plan.npz")]
                try:
                    load_plan(path)
                except Exception as e:
                    corrupt.append((key, repr(e)))
                else:
                    ok.append(key)
        return {"ok": ok, "corrupt": corrupt}

    def repair(self) -> list[str]:
        """Quarantine every corrupt entry found by :meth:`verify`.

        Corrupt ``*.plan.npz`` files (and their ``.stats.json`` sidecars,
        so ``suggest`` stops reading them) are moved into a
        ``quarantine/`` subdirectory — kept for post-mortem, never served
        again; the next ``get`` for that key recompiles. Returns the
        quarantined keys."""
        quarantined = []
        qdir = self.cache_dir / "quarantine"
        for key, _reason in self.verify()["corrupt"]:
            qdir.mkdir(parents=True, exist_ok=True)
            for suffix in (".plan.npz", ".stats.json"):
                src = self.cache_dir / f"{key}{suffix}"
                if src.exists():
                    os.replace(src, qdir / src.name)
            quarantined.append(key)
        return quarantined

    def watch(self, matrix, target, budget=None, graph=None,
              strategy=None) -> PlanWatch:
        """A :class:`PlanWatch` on this (matrix, budget/graph, strategy,
        Target) key. The watch records the entry's current stamp at
        creation, so only *subsequent* puts (a better plan landing from
        an offline search, a re-tune) trigger a reload — serving engines
        poll it between steps for zero-downtime hot-swap."""
        return PlanWatch(self, self.key(matrix, target, budget, graph,
                                        strategy),
                         mesh=target.mesh)

    def _refresh_sidecars(self) -> None:
        """Revalidate the in-memory sidecar index, O(changed files).

        Cheap path: one ``stat`` of the directory; if its mtime_ns is
        unchanged since the last suggest(), nothing on disk was atomically
        added/replaced/removed and the index is served as-is. Otherwise
        files are re-statted and only entries whose (mtime_ns, size) stamp
        moved are re-parsed; corrupt files are negative-cached so a bad
        sidecar is parsed (and skipped) once, not per call."""
        try:
            dir_stamp = self.cache_dir.stat().st_mtime_ns
        except OSError:
            self._sidecars.clear()
            self._sidecar_dir_stamp = None
            return
        if dir_stamp == self._sidecar_dir_stamp:
            return
        seen = set()
        for path in self.cache_dir.glob("*.stats.json"):
            try:
                st = path.stat()
            except OSError:
                continue   # removed between glob and stat
            seen.add(path)
            stamp = (st.st_mtime_ns, st.st_size)
            cached = self._sidecars.get(path)
            if cached is not None and cached[0] == stamp:
                continue
            try:
                payload = json.loads(path.read_text())
                payload["stats"][0]   # shape check: stats must index
                payload["graph"]
            except (OSError, ValueError, KeyError, IndexError, TypeError):
                payload = None        # negative cache: skip until it changes
            self._sidecars[path] = (stamp, payload)
        for path in list(self._sidecars):
            if path not in seen:
                del self._sidecars[path]
        self._sidecar_dir_stamp = dir_stamp

    def suggest(self, matrix: SparseMatrix, max_distance: float = 1.0,
                with_distance: bool = False):
        """Winning graph of the statistically nearest stored plan.

        Returns None when the store is empty or nothing is within
        ``max_distance`` in normalized statistics space (a candidate at
        exactly ``max_distance`` is accepted). The returned graph
        warm-starts any strategy (``repro.compile(..., warm_start=[g])``);
        it is *timed like any other candidate*, so a bad suggestion costs
        one evaluation, never correctness.

        With ``with_distance=True`` returns ``(graph_or_None, distance)``
        (``math.inf`` when nothing matched) — the portfolio strategy
        gates its refinement phase on this confidence signal.

        Sidecars are indexed in memory and revalidated by directory
        mtime, so corpus-scale stores (hundreds of entries) pay parsing
        only for files that actually changed."""
        if not self.cache_dir.is_dir():
            return (None, math.inf) if with_distance else None
        self._refresh_sidecars()
        want = _matrix_stats(matrix)
        best_d, best_graph = math.inf, None
        for _stamp, payload in self._sidecars.values():
            if payload is None:
                continue
            try:
                d = _stats_distance(want, payload["stats"])
            except (ValueError, KeyError, IndexError, TypeError):
                continue
            if d < best_d:
                best_d, best_graph = d, payload["graph"]
        if best_graph is None or best_d > max_distance:
            return (None, math.inf) if with_distance else None
        graph = _graph_from_jsonable(best_graph)
        return (graph, best_d) if with_distance else graph
