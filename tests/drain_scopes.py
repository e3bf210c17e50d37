"""The fair, fair-preempt and TAS drain problems ``test_chip_compile.py``
compiles for a v5e, built from fixed seeds. Each builder returns the
drain's arguments; nothing here launches or times a drain.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import numpy as np

from kueue_tpu.core.cache import Cache
from kueue_tpu.core.queue_manager import QueueManager, queue_order_timestamp
from kueue_tpu.core.snapshot import Snapshot, take_snapshot
from kueue_tpu.core.workload_info import make_admission
from kueue_tpu.models import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    Preemption,
    ResourceFlavor,
    Workload,
    WorkloadConditionType,
)
from kueue_tpu.models.cluster_queue import FairSharing, ResourceGroup
from kueue_tpu.models.constants import (
    PreemptionPolicy,
    ReclaimWithinCohortPolicy,
)
from kueue_tpu.models.topology import Topology, TopologyLevel
from kueue_tpu.models.workload import PodSet, PodSetTopologyRequest
from kueue_tpu.tas import Node, TASCache
from kueue_tpu.utils.clock import FakeClock

FAIR_WEIGHTS = (500, 1000, 1000, 2000)


class DrainProblem(NamedTuple):
    snapshot: Snapshot
    pending: list
    flavors: dict
    timestamp_fn: Callable
    tas: Optional[TASCache] = None


def _problem(cache: Cache, mgr: QueueManager, tas=None) -> DrainProblem:
    """Every pending workload in heap order per ClusterQueue, as the
    bulk path hands a backlog to a drain."""
    pending = [
        (wl, cq_name)
        for cq_name, pq in mgr.cluster_queues.items()
        for wl in pq.snapshot_sorted()
    ]
    return DrainProblem(
        take_snapshot(cache),
        pending,
        cache.flavors,
        lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
        tas,
    )


def _add_cq(cache: Cache, mgr: QueueManager, cq: ClusterQueue) -> None:
    cache.add_or_update_cluster_queue(cq)
    mgr.add_cluster_queue(cq)
    mgr.add_local_queue(
        LocalQueue(namespace="ns", name=f"lq-{cq.name}", cluster_queue=cq.name)
    )


def fair_problem(seed: int = 7) -> DrainProblem:
    """Fair sharing without preemption: 100 ClusterQueues in cohorts of
    10, 8 cpu nominal each, weights 0.5-2, 5 pending a ClusterQueue."""
    n_cq, cohort_size, wl_per_cq = 100, 10, 5
    cache, mgr = Cache(), QueueManager(FakeClock(0.0))
    cache.add_or_update_flavor(ResourceFlavor(name="default"))
    rng = np.random.default_rng(seed)
    for i in range(n_cq):
        name = f"fcq-{i}"
        _add_cq(cache, mgr, ClusterQueue(
            name=name,
            cohort=f"fcohort-{i // cohort_size}",
            namespace_selector={},
            resource_groups=(
                ResourceGroup(
                    ("cpu",), (FlavorQuotas.build("default", {"cpu": "8"}),)
                ),
            ),
            fair_sharing=FairSharing(
                weight_milli=FAIR_WEIGHTS[int(rng.integers(0, 4))]
            ),
        ))
        for w in range(wl_per_cq):
            mgr.add_or_update_workload(
                Workload(
                    namespace="ns", name=f"fwl-{i}-{w}",
                    queue_name=f"lq-{name}",
                    priority=int(rng.integers(0, 3)) * 10,
                    creation_time=float(i * wl_per_cq + w),
                    pod_sets=(
                        PodSet.build(
                            "main", 1, {"cpu": str(int(rng.integers(2, 7)))}
                        ),
                    ),
                )
            )
    return _problem(cache, mgr)


def fair_preempt_problem(seed: int = 11) -> DrainProblem:
    """Fair sharing with fair preemption: 60 ClusterQueues in cohorts
    of 6; every other one holds 3 admitted workloads of 5 cpu against 8
    nominal (borrowing, so its cohort is reclaim bait), and each has 4
    pending."""
    n_cq, cohort_size, wl_per_cq, victims_per_cq = 60, 6, 4, 3
    prem = Preemption(
        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
    )
    cache, mgr = Cache(), QueueManager(FakeClock(0.0))
    cache.add_or_update_flavor(ResourceFlavor(name="default"))
    rng = np.random.default_rng(seed)
    t = 0.0
    for i in range(n_cq):
        name = f"fpcq-{i}"
        _add_cq(cache, mgr, ClusterQueue(
            name=name,
            cohort=f"fpcohort-{i // cohort_size}",
            namespace_selector={},
            resource_groups=(
                ResourceGroup(
                    ("cpu",), (FlavorQuotas.build("default", {"cpu": "8"}),)
                ),
            ),
            fair_sharing=FairSharing(
                weight_milli=FAIR_WEIGHTS[int(rng.integers(0, 4))]
            ),
            preemption=prem,
        ))
        if i % 2 == 0:
            for v in range(victims_per_cq):
                t += 1.0
                wl = Workload(
                    namespace="ns", name=f"fpv-{i}-{v}",
                    queue_name=f"lq-{name}",
                    priority=int(rng.integers(0, 2)) * 5,
                    creation_time=t,
                    pod_sets=(PodSet.build("main", 1, {"cpu": "5"}),),
                )
                wl.admission = make_admission(
                    name, {"main": {"cpu": "default"}}, wl
                )
                wl.set_condition(
                    WorkloadConditionType.QUOTA_RESERVED, True,
                    reason="QuotaReserved", now=t,
                )
                cache.add_or_update_workload(wl)
        for w in range(wl_per_cq):
            t += 1.0
            mgr.add_or_update_workload(
                Workload(
                    namespace="ns", name=f"fpwl-{i}-{w}",
                    queue_name=f"lq-{name}",
                    priority=20 + int(rng.integers(0, 3)) * 10,
                    creation_time=t,
                    pod_sets=(
                        PodSet.build(
                            "main", 1, {"cpu": str(int(rng.integers(2, 6)))}
                        ),
                    ),
                )
            )
    return _problem(cache, mgr)


def tas_problem(seed: int = 4) -> DrainProblem:
    """Topology-aware gangs: 1,024 hosts (16 blocks x 8 racks x 8
    hosts, 8 cpu and 32 pods each), 100 ClusterQueues of 100 gangs of
    2-16 pods, each Required, Preferred or Unconstrained at a rack,
    block or host level."""
    block = "cloud.google.com/topology-block"
    rack = "cloud.google.com/topology-rack"
    host = "kubernetes.io/hostname"
    n_blocks, racks_per_block, hosts_per_rack = 16, 8, 8
    n_cq, wl_per_cq = 100, 100
    rng = np.random.default_rng(seed)
    cache, mgr = Cache(), QueueManager(FakeClock(0.0))
    topo = Topology(
        name="default",
        levels=(TopologyLevel(block), TopologyLevel(rack), TopologyLevel(host)),
    )
    flavor = ResourceFlavor(name="tas-flavor", topology_name="default")
    tas = TASCache()
    tas.add_or_update_topology(topo)
    cache.add_or_update_topology(topo)
    cache.add_or_update_flavor(flavor)
    tas.add_or_update_flavor(flavor)
    for b in range(n_blocks):
        for r in range(racks_per_block):
            for h in range(hosts_per_rack):
                tas.add_or_update_node(
                    Node(
                        name=f"n-{b}-{r}-{h}",
                        labels={
                            block: f"b{b}",
                            rack: f"b{b}-r{r}",
                            host: f"h-{b}-{r}-{h}",
                        },
                        allocatable={"cpu": 8000, "pods": 32},
                    )
                )
    cache.tas_cache = tas
    levels = (rack, rack, block, host)
    for i in range(n_cq):
        name = f"tcq-{i}"
        _add_cq(cache, mgr, ClusterQueue(
            name=name,
            namespace_selector={},
            resource_groups=(
                ResourceGroup(
                    ("cpu",),
                    (FlavorQuotas.build("tas-flavor", {"cpu": "9999"}),),
                ),
            ),
        ))
        for w in range(wl_per_cq):
            mode = ("Required", "Preferred", "Unconstrained")[
                int(rng.integers(0, 3))
            ]
            level = (
                None if mode == "Unconstrained"
                else levels[int(rng.integers(0, len(levels)))]
            )
            mgr.add_or_update_workload(
                Workload(
                    namespace="ns", name=f"twl-{i}-{w}",
                    queue_name=f"lq-{name}",
                    priority=int(rng.integers(0, 3)) * 10,
                    creation_time=float(i * wl_per_cq + w),
                    pod_sets=(
                        PodSet.build(
                            "main", int(rng.integers(2, 17)),
                            {"cpu": str(int(rng.integers(1, 3)))},
                            topology_request=PodSetTopologyRequest(
                                mode=mode, level=level
                            ),
                        ),
                    ),
                )
            )
    return _problem(cache, mgr, tas)
