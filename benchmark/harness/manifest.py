"""BENCHMARK.json, and the files that its names resolve to.

The harness is driven by data: a configuration, a traffic mix, a cell, a
per-layer metric, a reader, a driver and an end-to-end metric are each a
file of their own, found by name under one of the `search` directories.
A later PR adds files and manifest entries and edits nothing here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from typing import Any, Iterable, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads",
    "end_to_end", "per_layer",
}


class BenchmarkError(Exception):
    """The manifest or a file it names is missing or malformed."""


class Bench:
    """One benchmark tree: a manifest and the directories searched for
    the files it names (the first directory that has the file wins)."""

    def __init__(
        self,
        root: str,
        manifest_path: Optional[str] = None,
        search: Optional[Iterable[str]] = None,
    ):
        self.root = os.path.abspath(root)
        self.manifest_path = manifest_path or os.path.join(self.root, "BENCHMARK.json")
        self.search = [os.path.abspath(p) for p in search] if search else [
            os.path.join(self.root, "benchmark")
        ]
        try:
            with open(self.manifest_path) as f:
                self.manifest = json.load(f)
        except OSError as e:
            raise BenchmarkError(f"cannot read {self.manifest_path}: {e}") from e
        self._modules: dict[str, Any] = {}

    # ------------------------------------------------------------- files
    def find(self, kind: str, filename: str) -> str:
        for base in self.search:
            path = os.path.join(base, kind, filename)
            if os.path.isfile(path):
                return path
        raise BenchmarkError(
            f"no {kind}/{filename} under {', '.join(self.search)}"
        )

    def load_json(self, kind: str, name: str) -> dict:
        path = self.find(kind, name + ".json")
        with open(path) as f:
            return json.load(f)

    def load_module(self, kind: str, filename: str):
        """A Python file of the benchmark, loaded by path (names carry
        `-` and `.`, so these are not importable packages)."""
        path = self.find(kind, filename)
        if path not in self._modules:
            modname = "benchmark_" + re.sub(r"\W", "_", f"{kind}_{filename[:-3]}")
            spec = importlib.util.spec_from_file_location(modname, path)
            module = importlib.util.module_from_spec(spec)
            sys.modules[modname] = module  # dataclasses look their module up
            spec.loader.exec_module(module)
            self._modules[path] = module
        return self._modules[path]

    # ----------------------------------------------------------- entries
    def _entry(self, section: str, name: str) -> dict:
        for entry in self.manifest[section]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.manifest[section])
        raise BenchmarkError(f"no {section} entry named {name!r} (have: {known})")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration as it is run: the file the manifest names."""
        entry = self._entry("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        return self.load_json("traffic", name)

    def cell(self, name: str) -> dict:
        return self.load_json("cells", name)

    def layer_metric(self, name: str) -> dict:
        return self.load_json("layer_metrics", name)

    def metrics_of(self, section: str, cell: str) -> list[dict]:
        """The metrics of `end_to_end` or `per_layer` that this cell
        reports: those with no `workloads` key, or with the cell in it."""
        return [
            m for m in self.manifest[section]
            if "workloads" not in m or cell in m["workloads"]
        ]
