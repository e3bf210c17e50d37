"""Shared by the benchmark's tests: its cells at a size a test can hold.

Not a test module (no ``test_`` prefix); the test files import it by
path, since ``tests/`` has no packages."""

import json
import os

from benchmark.registry import HERE, Registry

# 12 ClusterQueues in 2 cohorts: a cycle has fewer heads than the
# scheduler's per-cycle solver threshold (16), so the tiny runs never
# switch between the per-cycle device and host paths, whose mix does not
# yet decide as the reference does (PERF.md, Open questions); 240 pending
# (14/4/2 of the three classes per ClusterQueue) stay under the bulk
# drain's 256, so nothing compiles and the tests stay light beside the
# suite's timing-sensitive ones
TINY = {"cohorts": 2, "counts": [14, 4, 2]}


def tiny_config(cfg: dict) -> dict:
    cfg = dict(cfg, cohorts=TINY["cohorts"])
    cfg["workloadsSets"] = [
        dict(s, count=n) for s, n in zip(cfg["workloadsSets"], TINY["counts"])
    ]
    return cfg


def tiny_registry(tmp_path) -> Registry:
    """The benchmark's own parts, with every configuration cut to
    ``TINY`` by a file of the same name in a root searched first."""
    root = tmp_path / "parts"
    (root / "configs").mkdir(parents=True)
    for name in os.listdir(os.path.join(HERE, "configs")):
        with open(os.path.join(HERE, "configs", name)) as f:
            cfg = json.load(f)
        (root / "configs" / name).write_text(json.dumps(tiny_config(cfg)))
    return Registry(roots=[str(root), HERE])
