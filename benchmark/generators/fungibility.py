"""Deployments of Kueue's flavor fungibility on upstream scheduler-perf's
cohort layout, in the flat batch wire format.

A configuration names this generator with ``"generator": "fungibility"``
and gives:

- ``cohorts`` cohorts of ``queuesPerCohort`` ClusterQueues, one
  LocalQueue each;
- ``resourceGroups``: every ClusterQueue's resource groups, in the wire
  form of a ClusterQueue (``coveredResources`` and ``flavors``); every
  flavor named there is a ResourceFlavor with no labels or taints;
- ``whenCanBorrow``: one value per position of a ClusterQueue in its
  cohort, and ``whenCanPreempt`` for all of them;
- ``withinClusterQueue`` and ``reclaimWithinCohort``, the preemption
  policies of every ClusterQueue;
- ``workloadsSets``: the workload classes as in ``schedperf``, each with
  its ``request`` in whole cpus and its ``gpu``, and ``memoryPerCpu``
  the memory each cpu brings along. A class asking 0 gpu sends no gpu
  request.

Every ClusterQueue's backlog holds the same workloads: ``count`` of each
class. The seed decides only their order, so every seed asks for the
same work.
"""

from __future__ import annotations

import json
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark.generators.schedperf import NAMESPACE, _rng, backlog, n_queues

__all__ = ["NAMESPACE", "WorkloadStream", "backlog", "deployment", "n_queues"]


def deployment(config: dict, seed: int) -> dict:
    """The wire body of the flavors, ClusterQueues and LocalQueues."""
    per_cohort = config["queuesPerCohort"]
    borrow = config["whenCanBorrow"]
    if len(borrow) != per_cohort:
        raise ValueError("whenCanBorrow needs one value per ClusterQueue of a cohort")
    cqs = [
        {
            "name": f"cq-{i}",
            "cohort": f"cohort-{i // per_cohort}",
            "namespaceSelector": {},
            "preemption": {
                "withinClusterQueue": config["withinClusterQueue"],
                "reclaimWithinCohort": config["reclaimWithinCohort"],
            },
            "flavorFungibility": {
                "whenCanBorrow": borrow[i % per_cohort],
                "whenCanPreempt": config["whenCanPreempt"],
            },
            "resourceGroups": config["resourceGroups"],
        }
        for i in range(n_queues(config))
    ]
    flavors = [f["name"] for g in config["resourceGroups"] for f in g["flavors"]]
    return {
        "resourceflavors": [{"name": f} for f in flavors],
        "clusterqueues": cqs,
        "localqueues": [
            {"namespace": NAMESPACE, "name": f"lq-{i}", "clusterQueue": f"cq-{i}"}
            for i in range(len(cqs))
        ],
    }


_WIRE = (
    '{"namespace": "%s", "name": "%s", "queueName": "lq-%d", '
    '"priority": %d, "creationTime": %r, "podSets": [{"name": "main", '
    '"count": 1, "requests": %s}]}'
)
_QTY = re.compile(r"^([0-9]+)(Ki|Mi|Gi|Ti)$")


def _requests(config: dict, s: dict) -> str:
    """The JSON of one class's requests: cpu, memory at ``memoryPerCpu``
    a cpu, and gpu where the class asks for any."""
    m = _QTY.match(config["memoryPerCpu"])
    if m is None:
        raise ValueError(f"memoryPerCpu {config['memoryPerCpu']!r}")
    req: Dict[str, str] = {
        "cpu": str(s["request"]),
        "memory": f"{int(m.group(1)) * s['request']}{m.group(2)}",
    }
    if s["gpu"]:
        req["gpu"] = str(s["gpu"])
    return json.dumps(req)


class WorkloadStream:
    """Seeded workloads in a fixed order, dealt as ``schedperf`` deals
    them: each LocalQueue draws from a deck of its own that holds every
    class ``count`` times, shuffled from the seed and dealt anew once
    empty; creation times rise by one second per workload."""

    def __init__(self, config: dict, seed: int, prefix: str, first_time: float,
                 stream: int = 1):
        self.prefix = prefix
        self.first_time = first_time
        self.classes = [(s["priority"], _requests(config, s))
                        for s in config["workloadsSets"]]
        self._deck = np.repeat(
            np.arange(len(self.classes)),
            [s["count"] for s in config["workloadsSets"]],
        )
        self._rng = _rng(seed, stream)
        self._dealt: Dict[int, List[int]] = {}
        self.count = 0

    def _next_class(self, lq: int) -> int:
        hand = self._dealt.get(lq)
        if not hand:
            hand = self._rng.permutation(self._deck).tolist()
            hand.reverse()
            self._dealt[lq] = hand
        return hand.pop()

    def batch(self, lq_indices: Sequence[int]) -> Tuple[str, List[str]]:
        """The next ``len(lq_indices)`` workloads, the ``i``-th sent to
        LocalQueue ``lq_indices[i]``: (the JSON of a batch body
        ``{"workloads": [...]}``, their keys)."""
        first = self.count
        names = [f"{self.prefix}{first + i}" for i in range(len(lq_indices))]
        items = []
        for i, (name, lq) in enumerate(zip(names, lq_indices)):
            priority, requests = self.classes[self._next_class(lq)]
            items.append(_WIRE % (NAMESPACE, name, lq, priority,
                                  self.first_time + float(first + i), requests))
        self.count += len(names)
        return (
            '{"workloads": [' + ", ".join(items) + "]}",
            [f"{NAMESPACE}/{name}" for name in names],
        )
