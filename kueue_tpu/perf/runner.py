"""Virtual-time minimalkueue runner.

Reference: test/performance/scheduler/{minimalkueue,runner}. Drives the
scheduling core only (queue manager + cache + scheduler — no webhooks
or job integrations) through a discrete-event simulation: workload
creations at generator timestamps, finishes at admission + runtime.
Virtual time decouples the measured admission ORDER/latency semantics
from host speed; it measures no wall time (``python3 -m benchmark``
does, on the served path).
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from kueue_tpu.core.cache import Cache
from kueue_tpu.core.preemption import Preemptor
from kueue_tpu.core.queue_manager import QueueManager, RequeueReason
from kueue_tpu.core.scheduler import Scheduler
from kueue_tpu.models.constants import WorkloadConditionType
from kueue_tpu.perf.generator import GeneratorConfig, Scenario, generate
from kueue_tpu.utils.clock import FakeClock


@dataclass
class RunResult:
    wall_s: float  # host wall time of the whole run
    virtual_s: float  # simulated makespan
    admitted: int
    total: int
    cycles: int
    # class -> list of time-to-admission (s, virtual)
    time_to_admission: Dict[str, List[float]] = field(default_factory=dict)
    # cq -> time-weighted average cpu utilization (fraction of nominal)
    cq_avg_utilization: Dict[str, float] = field(default_factory=dict)
    # fraction of virtual time with a non-empty pending backlog (at
    # scheduler quiescence — workloads that COULD not admit)
    backlog_fraction: float = 0.0
    # cq -> time-weighted average utilization restricted to backlogged
    # intervals (the no-idle-capacity-under-backlog floor)
    cq_backlogged_utilization: Dict[str, float] = field(default_factory=dict)

    def avg_tta(self, class_name: str) -> float:
        vals = self.time_to_admission.get(class_name, [])
        return sum(vals) / len(vals) if vals else 0.0


def run(
    config: GeneratorConfig,
    max_virtual_s: float = 100_000.0,
    use_solver: Optional[bool] = None,
    scenario_mutator=None,  # callable(Scenario) -> None, applied post-generate
) -> RunResult:
    """Drive one generated scenario to completion in virtual time.

    ``scenario_mutator`` edits the generated Scenario in place before
    the run — the hook the planner's forecast-validation path uses to
    apply a recommended quota delta (perf/generator.override_nominal_cpu)
    and then measure the REAL time-to-admission against the forecast
    band."""
    scenario = generate(config)
    if scenario_mutator is not None:
        scenario_mutator(scenario)
    clock = FakeClock(0.0)
    cache = Cache()
    queues = QueueManager(clock)
    preemptor = Preemptor(clock)
    sched = Scheduler(
        queues=queues, cache=cache, clock=clock, preemptor=preemptor,
        use_solver=use_solver,
    )

    cache.add_or_update_flavor(scenario.flavor)
    for cq in scenario.cluster_queues:
        cache.add_or_update_cluster_queue(cq)
        queues.add_cluster_queue(cq)
    for lq in scenario.local_queues:
        cache.add_or_update_local_queue(lq)
        queues.add_local_queue(lq)

    by_key = {gw.workload.key: gw for gw in scenario.workloads}

    # event heap: (time, seq, kind, payload, epoch). The epoch stamps a
    # finish event with the admission it belongs to — a victim preempted
    # and re-admitted later must not be finished by the STALE event.
    events: List[Tuple[float, int, str, object, int]] = []
    admission_epoch: Dict[str, int] = {}
    seq = 0
    for gw in scenario.workloads:
        heapq.heappush(events, (gw.creation_s, seq, "create", gw, 0))
        seq += 1

    tta: Dict[str, List[float]] = {}
    # cq -> (last_event_time, integral of used_cpu dt)
    usage_integral: Dict[str, float] = {name: 0.0 for name in scenario.nominal_cpu}
    backlog_integral: Dict[str, float] = {name: 0.0 for name in scenario.nominal_cpu}
    # cohorts are independent capacity pools (borrowing is within-cohort
    # only), so a CQ only counts as idle-under-backlog while ITS cohort
    # has pending work
    cohort_of = {cq.name: cq.cohort for cq in scenario.cluster_queues}
    cohort_backlog_time: Dict[object, float] = {}
    backlog_time = 0.0
    last_t = 0.0

    def accrue_usage(now: float) -> None:
        nonlocal last_t, backlog_time
        dt = now - last_t
        if dt <= 0:
            return
        # backlog at quiescence: the scheduler ran to a fixed point at
        # last_t, so anything still pending could NOT be admitted
        backlogged_cohorts = {
            cohort_of.get(name)
            for name, pq in queues.cluster_queues.items()
            if pq.pending_active() > 0 or len(pq.inadmissible) > 0
        }
        if backlogged_cohorts:
            backlog_time += dt
        for co in backlogged_cohorts:
            cohort_backlog_time[co] = cohort_backlog_time.get(co, 0.0) + dt
        for name in usage_integral:
            used = sum(
                qty for fr, qty in cache.usage_for(name).items() if fr.resource == "cpu"
            )
            usage_integral[name] += used * dt
            if cohort_of.get(name) in backlogged_cohorts:
                backlog_integral[name] += used * dt
        last_t = now

    admitted_keys: set = set()
    cycles = 0
    t_start = time.perf_counter()

    def drive_scheduler() -> None:
        """Run cycles until quiescent at the current virtual instant."""
        nonlocal cycles, seq
        while True:
            result = sched.schedule()
            cycles += 1
            progressed = False
            for e in result.admitted:
                gw = by_key[e.workload.key]
                if e.workload.key not in admitted_keys:
                    # first admission only: re-admissions after a
                    # preemption must not double-count tta/admitted
                    tta.setdefault(gw.class_name, []).append(
                        clock.now() - gw.creation_s
                    )
                    admitted_keys.add(e.workload.key)
                epoch = admission_epoch.get(gw.workload.key, 0) + 1
                admission_epoch[gw.workload.key] = epoch
                heapq.heappush(
                    events, (clock.now() + gw.runtime_s, seq, "finish", gw, epoch)
                )
                seq += 1
                progressed = True
            for e in result.preempting:
                # preemption targets got Evicted conditions; complete the
                # eviction synchronously (minimalkueue has no job
                # controller): release quota + requeue the victims
                progressed = True
            # release evicted victims (scan cache for Evicted conditions)
            for cq_name in scenario.nominal_cpu:
                cached = cache.cluster_queues.get(cq_name)
                if cached is None:
                    continue
                for wl in list(cached.workloads.values()):
                    if wl.condition_true(WorkloadConditionType.EVICTED):
                        gw = by_key[wl.key]
                        # invalidate the in-flight finish event
                        admission_epoch[wl.key] = admission_epoch.get(wl.key, 0) + 1
                        cache.delete_workload(wl)
                        wl.admission = None
                        wl.set_condition(
                            WorkloadConditionType.QUOTA_RESERVED, False,
                            "Pending", "evicted", now=clock.now(),
                        )
                        wl.conditions.pop(WorkloadConditionType.EVICTED, None)
                        wl.set_condition(
                            WorkloadConditionType.REQUEUED, True, "Preempted",
                            "", now=clock.now(),
                        )
                        queues.requeue_workload(wl, RequeueReason.GENERIC)
                        queues.queue_associated_inadmissible_workloads_after(cq_name)
                        progressed = True
            if not progressed:
                break

    def apply_event(kind: str, gw, epoch: int, t: float) -> None:
        if kind == "create":
            queues.add_or_update_workload(gw.workload)
            return
        wl = gw.workload
        if admission_epoch.get(wl.key, 0) != epoch:
            return  # stale finish: the admission it belonged to was evicted
        cq_name = wl.admission.cluster_queue if wl.admission else None
        wl.set_condition(
            WorkloadConditionType.FINISHED, True, "Succeeded", "", now=t
        )
        queues.delete_workload(wl)
        if cache.delete_workload(wl) and cq_name:
            queues.queue_associated_inadmissible_workloads_after(cq_name)

    while events and clock.now() <= max_virtual_s:
        t = events[0][0]
        accrue_usage(t)
        clock.set(t)
        # apply every event at this instant before scheduling
        while events and events[0][0] == t:
            _, _, kind, gw, epoch = heapq.heappop(events)
            apply_event(kind, gw, epoch, t)
        drive_scheduler()

    virtual_s = clock.now()
    accrue_usage(virtual_s)
    wall_s = time.perf_counter() - t_start

    cq_avg = {}
    cq_backlogged = {}
    for name, integral in usage_integral.items():
        nominal = scenario.nominal_cpu[name]
        cq_avg[name] = (
            integral / (nominal * virtual_s) if virtual_s > 0 and nominal else 0.0
        )
        co_time = cohort_backlog_time.get(cohort_of.get(name), 0.0)
        cq_backlogged[name] = (
            backlog_integral[name] / (nominal * co_time)
            if co_time > 0 and nominal
            else 1.0  # never backlogged: the floor is vacuously met
        )

    return RunResult(
        wall_s=wall_s,
        virtual_s=virtual_s,
        admitted=len(admitted_keys),
        total=len(scenario.workloads),
        cycles=cycles,
        time_to_admission=tta,
        cq_avg_utilization=cq_avg,
        backlog_fraction=backlog_time / virtual_s if virtual_s > 0 else 0.0,
        cq_backlogged_utilization=cq_backlogged,
    )
