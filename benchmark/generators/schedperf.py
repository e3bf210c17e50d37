"""Deployments of upstream Kueue's scheduler-perf generator, in the flat
batch wire format.

A configuration names this generator with ``"generator": "schedperf"``
and gives the keys of upstream's ``default_generator_config.yaml``
(``test/performance/scheduler``), at its own counts:

- ``cohorts`` cohorts of ``queuesPerCohort`` ClusterQueues, one
  LocalQueue each, all on one ResourceFlavor covering cpu only;
- per ClusterQueue, ``nominalQuota`` and ``borrowingLimit`` whole cpus
  and the preemption policies ``reclaimWithinCohort`` and
  ``withinClusterQueue``;
- ``workloadsSets``: the workload classes, each with the ``count`` of a
  ClusterQueue's backlog that belongs to it, its ``priority`` and its
  ``request`` in whole cpus (one pod). ``creationIntervalMs`` and
  ``runtimeMs`` are upstream's and are kept, but a backlog mix sends
  every workload at once and never lets one finish.

Every ClusterQueue's backlog holds the same workloads: ``count`` of each
class. The seed decides only their order, so every seed asks for the
same work.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

NAMESPACE = "perf"
FLAVOR = "default"


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def n_queues(config: dict) -> int:
    return config["cohorts"] * config["queuesPerCohort"]


def backlog(config: dict) -> List[int]:
    """LocalQueue indices of one full backlog, round-robin."""
    n = n_queues(config)
    per_queue = sum(s["count"] for s in config["workloadsSets"])
    return [j % n for j in range(n * per_queue)]


def deployment(config: dict, seed: int) -> dict:
    """The wire body of the flavor, ClusterQueues and LocalQueues."""
    per_cohort = config["queuesPerCohort"]
    cqs = [
        {
            "name": f"cq-{i}",
            "cohort": f"cohort-{i // per_cohort}",
            "namespaceSelector": {},
            "preemption": {
                "reclaimWithinCohort": config["reclaimWithinCohort"],
                "withinClusterQueue": config["withinClusterQueue"],
            },
            "resourceGroups": [{
                "coveredResources": ["cpu"],
                "flavors": [{
                    "name": FLAVOR,
                    "resources": [{
                        "name": "cpu",
                        "nominalQuota": str(config["nominalQuota"]),
                        "borrowingLimit": str(config["borrowingLimit"]),
                    }],
                }],
            }],
        }
        for i in range(n_queues(config))
    ]
    return {
        "resourceflavors": [{"name": FLAVOR}],
        "clusterqueues": cqs,
        "localqueues": [
            {"namespace": NAMESPACE, "name": f"lq-{i}", "clusterQueue": f"cq-{i}"}
            for i in range(len(cqs))
        ],
    }


# one workload in the flat wire format; the stream writes the JSON text
# directly, so a batch of 50k costs one string, not 200k objects
_WIRE = (
    '{"namespace": "%s", "name": "%s", "queueName": "lq-%d", '
    '"priority": %d, "creationTime": %r, "podSets": [{"name": "main", '
    '"count": 1, "requests": {"cpu": "%d"}}]}'
)


class WorkloadStream:
    """Seeded workloads in a fixed order. Each LocalQueue deals its
    workloads from a deck of its own that holds every class ``count``
    times, shuffled from the seed and dealt anew once empty, so any
    whole backlog asks for the same work whatever the seed. Creation
    times rise by one second per workload from ``first_time``, so no two
    workloads tie in queue order."""

    def __init__(self, config: dict, seed: int, prefix: str, first_time: float,
                 stream: int = 1):
        self.prefix = prefix
        self.first_time = first_time
        self.classes = [(s["priority"], s["request"]) for s in config["workloadsSets"]]
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
            priority, request = self.classes[self._next_class(lq)]
            items.append(_WIRE % (NAMESPACE, name, lq, priority,
                                  self.first_time + float(first + i), request))
        self.count += len(names)
        return (
            '{"workloads": [' + ", ".join(items) + "]}",
            [f"{NAMESPACE}/{name}" for name in names],
        )
