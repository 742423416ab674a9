"""Finds every piece of the benchmark by the name BENCHMARK.json gives it.

A configuration is ``configs/<name>.json``, a cell's parameters are
``workloads/<name>.json``, a traffic kind is ``traffic/<name>.py``, a
metric reader is ``metrics/<name>.py`` and a matrix generator is
``gen/<name>.py``, each under one of the registry's directories (the
first that holds the file wins). Adding a cell, a configuration or a
metric adds files; no existing file changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(RuntimeError):
    """The benchmark cannot run as asked; no result is printed."""


class Registry:
    def __init__(self, spec: dict, dirs=(BENCH_DIR,)):
        self.spec = spec
        self.dirs = [Path(d) for d in dirs]
        self._modules = {}

    @staticmethod
    def from_file(path=ROOT / "BENCHMARK.json", dirs=(BENCH_DIR,)):
        return Registry(json.loads(Path(path).read_text()), dirs)

    def path(self, kind: str, name: str, ext: str) -> Path:
        if not _NAME.match(name):
            raise BenchError(f"bad {kind} name {name!r}")
        for d in self.dirs:
            p = d / kind / f"{name}{ext}"
            if p.is_file():
                return p
        raise BenchError(f"no {kind}/{name}{ext} under "
                         f"{[str(d) for d in self.dirs]}")

    def data(self, kind: str, name: str) -> dict:
        return json.loads(self.path(kind, name, ".json").read_text())

    def module(self, kind: str, name: str):
        """The module ``<kind>/<name>.py``, loaded once per registry."""
        key = (kind, name)
        if key not in self._modules:
            p = self.path(kind, name, ".py")
            mod_name = f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
            spec = importlib.util.spec_from_file_location(mod_name, p)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def metrics(self, cell: str, section: str) -> list[dict]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
        those that list it, and those that list no cells."""
        return [m for m in self.spec[section]
                if "workloads" not in m or cell in m["workloads"]]
