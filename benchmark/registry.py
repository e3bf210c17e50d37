"""Find the benchmark's parts by the names ``BENCHMARK.json`` gives.

Each part is a file of its own, so a later change adds a cell, a mix, a
configuration or a metric by adding files and editing none:

- ``configs/<config>.json``: a deployment, its sizes and its source;
- ``traffic/<mix>.json``: the parameters of a traffic mix;
- ``loops/<loop>.py``: the general loop a mix names under ``loop``;
- ``generators/<generator>.py``: what a configuration names to make its
  objects;
- ``references/<reference>.py``: the plain reference a configuration
  names;
- ``metrics/<metric>.py``: one metric reader, end-to-end or per-layer,
  ``read(record)``.

A ``Registry`` searches its roots in order, so a test can put a part in a
temporary directory ahead of the benchmark's own.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class NotFound(LookupError):
    """No file of that kind has that name."""


class Registry:
    def __init__(self, roots: Optional[Sequence[str]] = None,
                 manifest: Optional[str] = None):
        self.roots: List[str] = list(roots) if roots else [HERE]
        self.manifest_path = manifest or os.path.join(CHECKOUT, "BENCHMARK.json")
        self._modules: Dict[str, ModuleType] = {}

    def _path(self, kind: str, name: str, ext: str) -> str:
        if not _NAME.match(name):
            raise NotFound(f"{kind}: {name!r} is not a valid name")
        for root in self.roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise NotFound(f"no {kind}/{name}{ext} under {self.roots}")

    def data(self, kind: str, name: str) -> dict:
        with open(self._path(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str) -> ModuleType:
        path = self._path(kind, name, ".py")
        mod = self._modules.get(path)
        if mod is None:
            mod_name = "benchmark_part_" + re.sub(r"\W", "_", f"{kind}_{name}")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return mod

    # ---- the manifest ----
    def manifest(self) -> dict:
        with open(self.manifest_path) as f:
            return json.load(f)

    def cell(self, workload: str) -> dict:
        """The cell, its configuration and mix, and the metrics it
        reports, resolved from the manifest."""
        m = self.manifest()
        cells = {w["name"]: w for w in m["workloads"]}
        if workload not in cells:
            raise NotFound(f"no workload {workload!r} in {self.manifest_path}")
        w = cells[workload]
        configs = {c["name"]: c for c in m["configs"]}
        entry = configs[w["config"]]

        def reports(metric: dict) -> bool:
            return workload in metric.get("workloads", [workload])

        return {
            "workload": w,
            "config_entry": entry,
            "config": self.data("configs", w["config"]),
            "traffic": self.data("traffic", w["traffic"]),
            "end_to_end": [x for x in m["end_to_end"] if reports(x)],
            "per_layer": [x for x in m["per_layer"] if reports(x)],
            "run_seconds": m["run_seconds"],
        }
