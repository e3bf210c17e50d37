"""MultiKueue-at-scale scenario — BASELINE config #5: N worker
clusters x M workloads through batched cross-cluster dispatch.

Reference: pkg/controller/admissionchecks/multikueue/workload.go:298-425
(remote copies on every configured cluster, first-reserving wins with
losers dropped, status sync-back, finish propagation, orphan GC) and
multikueuecluster.go:76-187 (per-cluster remote clients).

The manager and every worker are full ClusterRuntimes sharing ONE
virtual clock, so the measured semantics — dispatch waves, reservation
races, finish sync-back — are host-speed independent; the wall time of
the whole run is the throughput number. Worker capacity is sized below
the workload count so dispatch proceeds in waves: every worker receives
copies of the whole backlog, the over-subscribed head of each worker's
queue reserves everywhere at once (the first-reserving race), losers'
copies are dropped, their freed quota pulls the next tranche, and the
spread emerges from the race resolution — the same dynamics the
reference's multikueue e2e drives with real clusters.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from kueue_tpu.admissionchecks.multikueue import (
    MultiKueueCluster,
    MultiKueueConfig,
    MultiKueueController,
)
from kueue_tpu.admissionchecks.multikueue_transport import (
    ORIGIN_LABEL,
    InProcessTransport,
)
from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.models import (
    AdmissionCheck,
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    ResourceFlavor,
    Workload,
)
from kueue_tpu.models.cluster_queue import ResourceGroup
from kueue_tpu.models.constants import (
    MULTIKUEUE_CONTROLLER_NAME,
    WorkloadConditionType,
)
from kueue_tpu.models.workload import PodSet
from kueue_tpu.utils.clock import FakeClock


class CountingTransport(InProcessTransport):
    """Wire telemetry: every op counted, batched-create sizes recorded
    (the scenario's floor is that creates flow ONLY through the batched
    exchange — workload.go:298's per-object creates amortized into one
    wire round trip per cluster per pass)."""

    def __init__(self, runtime):
        super().__init__(runtime)
        self.op_counts: Dict[str, int] = {}
        self.batch_sizes: List[int] = []

    def _count(self, op: str) -> None:
        self.op_counts[op] = self.op_counts.get(op, 0) + 1

    def get_workload(self, key):
        self._count("get_workload")
        return super().get_workload(key)

    def create_workload(self, wl):
        self._count("create_workload")
        super().create_workload(wl)

    def create_workloads(self, wls):
        self._count("create_workloads")
        self.batch_sizes.append(len(wls))
        for wl in wls:
            super().create_workload(wl)

    def delete_workload(self, key):
        self._count("delete_workload")
        super().delete_workload(key)

    def list_workload_keys(self, origin):
        self._count("list_workload_keys")
        return super().list_workload_keys(origin)


@dataclass
class MKRunResult:
    wall_s: float
    virtual_s: float
    n_workers: int
    total: int
    dispatched: int  # workloads that found a reserving winner
    finished: int  # local workloads Finished via sync-back
    driver_iterations: int
    # wire telemetry
    unbatched_creates: int  # must be 0 under batch_dispatch
    batched_exchanges: int  # create_workloads calls across clusters
    total_batched_creates: int  # sum of batch sizes
    max_batch: int
    avg_batch: float
    # race / spread telemetry
    first_reserving_races: int
    winner_counts: Dict[str, int] = field(default_factory=dict)
    # hygiene
    orphans_gced: int = 0
    remote_leftovers: int = 0  # origin-labeled remotes after final GC

    @property
    def dispatch_per_sec_wall(self) -> float:
        return self.finished / max(self.wall_s, 1e-9)


class _PinnedOpenGate:
    """Latency-gate stand-in that keeps the bulk drain always on: this
    scenario measures dispatch SEMANTICS and wire efficiency at scale,
    not the latency auto-gate (which has its own tests) — a CPU-backend
    compile blip mid-run must not flip half the waves to the host path
    and make the batch-size floors nondeterministic."""

    value = 0.0

    def observe(self, dt: float) -> None:
        pass

    def erode(self) -> None:
        pass


def _manager_runtime(
    clock, n_workloads: int, wl_cpu: int, n_queues: int
) -> ClusterRuntime:
    """n_queues ClusterQueues all gated by the one MultiKueue check —
    the drain pops one head per queue per kernel cycle, so queue count
    bounds the drain's cycle depth (and many tenant queues feeding one
    dispatch check is the realistic shape anyway)."""
    rt = ClusterRuntime(clock=clock, drain_gate=_PinnedOpenGate())
    rt.add_flavor(ResourceFlavor(name="default"))
    rt.add_admission_check(
        AdmissionCheck(
            name="mk",
            controller_name=MULTIKUEUE_CONTROLLER_NAME,
            parameters="cfg",
        )
    )
    per_q = -(-n_workloads // n_queues) * wl_cpu  # ceil: local quota ample
    for j in range(n_queues):
        rt.add_cluster_queue(
            ClusterQueue(
                name=f"mk-cq-{j}",
                namespace_selector={},
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (FlavorQuotas.build("default", {"cpu": str(per_q)}),),
                    ),
                ),
                admission_checks=("mk",),
            )
        )
        rt.add_local_queue(
            LocalQueue(namespace="ns", name=f"lq-{j}", cluster_queue=f"mk-cq-{j}")
        )
    return rt


def _worker_runtime(clock, cpu_quota: int, n_queues: int) -> ClusterRuntime:
    rt = ClusterRuntime(clock=clock, drain_gate=_PinnedOpenGate())
    rt.add_flavor(ResourceFlavor(name="default"))
    per_q = max(1, cpu_quota // n_queues)
    for j in range(n_queues):
        rt.add_cluster_queue(
            ClusterQueue(
                name=f"worker-cq-{j}",
                namespace_selector={},
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (FlavorQuotas.build("default", {"cpu": str(per_q)}),),
                    ),
                ),
            )
        )
        rt.add_local_queue(
            LocalQueue(
                namespace="ns", name=f"lq-{j}", cluster_queue=f"worker-cq-{j}"
            )
        )
    return rt


def run_multikueue(
    n_workers: int = 4,
    n_workloads: int = 10_000,
    worker_cpu_each: Optional[int] = None,
    runtime_s: float = 60.0,
    wl_cpu: int = 1,
    n_queues: int = 16,
    max_virtual_s: float = 1e7,
    max_driver_iterations: int = 10_000,
) -> MKRunResult:
    """Drive the full dispatch lifecycle to completion.

    ``worker_cpu_each`` defaults to a quarter of the per-worker fair
    share, so the whole backlog needs ~4 dispatch waves per worker and
    the first-reserving race path is exercised on every wave."""
    clock = FakeClock(0.0)
    if worker_cpu_each is None:
        worker_cpu_each = max(1, (n_workloads * wl_cpu) // (4 * n_workers))

    manager = _manager_runtime(clock, n_workloads, wl_cpu, n_queues)
    workers: Dict[str, MultiKueueCluster] = {}
    transports: Dict[str, CountingTransport] = {}
    for i in range(n_workers):
        name = f"worker{i}"
        wrt = _worker_runtime(clock, worker_cpu_each, n_queues)
        tr = CountingTransport(wrt)
        transports[name] = tr
        workers[name] = MultiKueueCluster(name=name, transport=tr)
    ctrl = MultiKueueController(
        manager,
        clusters=workers,
        configs={
            "cfg": MultiKueueConfig(name="cfg", clusters=tuple(workers))
        },
        batch_dispatch=True,
    )
    manager.admission_check_controllers.append(ctrl)

    for i in range(n_workloads):
        manager.add_workload(
            Workload(
                namespace="ns",
                name=f"mk-{i:06d}",
                queue_name=f"lq-{i % n_queues}",
                pod_sets=(PodSet.build("main", 1, {"cpu": str(wl_cpu)}),),
            )
        )

    # finish events for remote copies: (virtual time, seq, worker, key)
    finish_events: List[tuple] = []
    scheduled_finish: set = set()
    seq = 0
    iterations = 0
    t_start = time.perf_counter()

    def pump() -> None:
        """One round of the distributed control loop at a virtual
        instant: manager pass (reserve + buffer creates + flush), then
        cascade worker-reserve / manager-observe rounds until the race
        resolution quiesces — every round the losers' freed quota pulls
        the next tranche, so capacity fills instead of advancing time
        with three quarters of the fleet idled by lost races."""
        manager.run_until_idle()
        for _ in range(4 * n_workers + 4):
            before = (
                sum(ctrl.winner_counts.values()),
                len(ctrl._reserving),
            )
            for w in workers.values():
                w.runtime.run_until_idle()
            manager.run_until_idle()
            if (
                sum(ctrl.winner_counts.values()),
                len(ctrl._reserving),
            ) == before:
                break

    while iterations < max_driver_iterations and clock.now() <= max_virtual_s:
        iterations += 1
        pump()
        # schedule finishes for newly admitted remote copies
        for name, w in workers.items():
            for wl in w.runtime.workloads.values():
                if wl.has_quota_reservation and (name, wl.key) not in scheduled_finish:
                    scheduled_finish.add((name, wl.key))
                    heapq.heappush(
                        finish_events,
                        (clock.now() + runtime_s, seq, name, wl.key),
                    )
                    seq += 1
        if all(w.is_finished for w in manager.workloads.values()):
            break
        if not finish_events:
            break  # stalled: nothing running remotely, nothing to wait on
        # advance virtual time to the next remote completion(s)
        t = finish_events[0][0]
        clock.set(max(clock.now(), t))
        while finish_events and finish_events[0][0] <= clock.now():
            _, _, name, key = heapq.heappop(finish_events)
            wrt = workers[name].runtime
            rwl = wrt.workloads.get(key)
            # the copy may have lost the race and been deleted since
            if rwl is None or rwl.is_finished:
                continue
            rwl.set_condition(
                WorkloadConditionType.FINISHED,
                True,
                "JobFinished",
                "Job finished successfully",
                now=clock.now(),
            )
            wrt.on_workload_finished(rwl)

    orphans = ctrl.gc_orphans()
    leftovers = sum(
        1
        for w in workers.values()
        for wl in w.runtime.workloads.values()
        if wl.labels.get(ORIGIN_LABEL) == ctrl.origin
    )
    wall_s = time.perf_counter() - t_start

    batch_sizes = [s for tr in transports.values() for s in tr.batch_sizes]
    return MKRunResult(
        wall_s=wall_s,
        virtual_s=clock.now(),
        n_workers=n_workers,
        total=n_workloads,
        dispatched=len(ctrl._reserving)
        + sum(
            1 for wl in manager.workloads.values() if wl.is_finished
        ),
        finished=sum(
            1 for wl in manager.workloads.values() if wl.is_finished
        ),
        driver_iterations=iterations,
        unbatched_creates=sum(
            tr.op_counts.get("create_workload", 0)
            for tr in transports.values()
        ),
        batched_exchanges=len(batch_sizes),
        total_batched_creates=sum(batch_sizes),
        max_batch=max(batch_sizes, default=0),
        avg_batch=(
            sum(batch_sizes) / len(batch_sizes) if batch_sizes else 0.0
        ),
        first_reserving_races=ctrl.first_reserving_races,
        winner_counts=dict(ctrl.winner_counts),
        orphans_gced=orphans,
        remote_leftovers=leftovers,
    )


@dataclass
class MKRangeSpec:
    """Floors for the at-scale dispatch run (the multikueue e2e's
    all-dispatched / no-orphan assertions plus wire-efficiency floors
    the batched path is for)."""

    require_all_finished: bool = True
    max_unbatched_creates: int = 0
    min_avg_batch: float = 2.0  # batching actually amortizes the wire
    min_races: int = 1  # the first-reserving race path really ran
    # every worker must carry a real share of the load (spread emerges
    # from race resolution + freed-quota waves, not round-robin)
    min_winner_share: float = 0.05
    max_remote_leftovers: int = 0
    max_wall_s: Optional[float] = None


def check_mk(result: MKRunResult, spec: MKRangeSpec) -> List[str]:
    errs: List[str] = []
    if spec.require_all_finished and result.finished < result.total:
        errs.append(f"finished {result.finished}/{result.total} workloads")
    if result.unbatched_creates > spec.max_unbatched_creates:
        errs.append(
            f"{result.unbatched_creates} creates bypassed the batched exchange"
        )
    if result.batched_exchanges and result.avg_batch < spec.min_avg_batch:
        errs.append(
            f"avg batch {result.avg_batch:.1f} < {spec.min_avg_batch}"
        )
    if result.first_reserving_races < spec.min_races:
        errs.append(
            f"only {result.first_reserving_races} first-reserving races "
            f"(scenario exercises no contention)"
        )
    if len(result.winner_counts) < result.n_workers:
        # a worker absent from winner_counts won NOTHING — exactly the
        # rotation-collapse regression the share floor exists to catch
        errs.append(
            f"only {len(result.winner_counts)}/{result.n_workers} workers "
            f"ever won a dispatch"
        )
    for name, wins in result.winner_counts.items():
        if wins / max(result.total, 1) < spec.min_winner_share:
            errs.append(
                f"{name} won only {wins}/{result.total} dispatches "
                f"(< {spec.min_winner_share:.0%} share)"
            )
    if result.remote_leftovers > spec.max_remote_leftovers:
        errs.append(
            f"{result.remote_leftovers} origin-labeled remotes survived GC"
        )
    if spec.max_wall_s is not None and result.wall_s > spec.max_wall_s:
        errs.append(f"wall time {result.wall_s:.1f}s > {spec.max_wall_s}s")
    return errs


MULTIKUEUE_RANGE_SPEC = MKRangeSpec()
