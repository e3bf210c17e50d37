"""The runtime loop's quiescence check (``ClusterRuntime._quiescence_walk``).

``run_until_idle`` stops when an iteration changed nothing it watches:
each workload's active flag, admission ClusterQueue, condition statuses
and reasons, and admission-check states; each job's suspended flag; and
the events recorder's resourceVersion. The oracle here is a full sorted
fingerprint of exactly that state, built before and after each step and
compared whole. The incremental walk must answer "changed" exactly when
the two fingerprints differ, and count the entries that differ.
"""

import copy
import random

import pytest

from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.controllers.cluster import _LastWalk
from kueue_tpu.controllers.jobs import BatchJob
from kueue_tpu.models import (
    AdmissionCheck,
    ClusterQueue,
    LocalQueue,
    ResourceFlavor,
    Workload,
)
from kueue_tpu.models.admission_check import AdmissionCheckState
from kueue_tpu.models.cluster_queue import FlavorQuotas, Preemption, ResourceGroup
from kueue_tpu.models.constants import (
    EVICTED_BY_PREEMPTION,
    AdmissionCheckStateType,
    PreemptionPolicy,
    WorkloadConditionType,
)
from kueue_tpu.models.workload import Admission, PodSet
from kueue_tpu.utils.clock import FakeClock

T = WorkloadConditionType
STATES = list(AdmissionCheckStateType)
REASONS = ["Pending", "QuotaReserved", "Preempted", "Admitted"]


def oracle_fingerprint(rt):
    """The full sorted fingerprint the loop compared before and after
    every iteration until the check became incremental."""
    parts = []
    for key in sorted(rt.workloads):
        wl = rt.workloads[key]
        parts.append(
            (
                key,
                wl.active,
                wl.admission.cluster_queue if wl.admission else None,
                tuple(
                    (t.value, c.status, c.reason)
                    for t, c in sorted(wl.conditions.items())
                ),
                tuple(
                    (n, s.state.value)
                    for n, s in sorted(wl.admission_check_states.items())
                ),
            )
        )
    for key in sorted(rt.jobs):
        job = rt.jobs[key]
        parts.append((key, job.is_suspended()))
    return tuple(parts), rt.events.resource_version


def oracle_changed(before, after) -> int:
    """Entries that differ between two oracle fingerprints: workloads
    and jobs added, removed or projected differently, plus one for the
    resourceVersion."""
    def split(fp):
        parts, rv = fp
        wls = {p[0]: p[1:] for p in parts if len(p) == 5}
        jobs = {p[0]: p[1] for p in parts if len(p) == 2}
        return wls, jobs, rv

    (bw, bj, brv), (aw, aj, arv) = split(before), split(after)
    n = int(brv != arv)
    for b, a in ((bw, aw), (bj, aj)):
        n += len(b.keys() ^ a.keys())
        n += sum(1 for k in b.keys() & a.keys() if b[k] != a[k])
    return n


def oracle_run_until_idle(rt, max_iterations: int = 50) -> int:
    """The loop as it was: a full fingerprint before and after each
    iteration."""
    cycles = 0
    for _ in range(max_iterations):
        before = oracle_fingerprint(rt)
        rt.reconcile_once()
        if rt.bulk_drain() is None:
            rt.schedule_once()
        rt.reconcile_once()
        cycles += 1
        if oracle_fingerprint(rt) == before:
            break
    return cycles


def base_runtime(quota="4", preemption=None):
    rt = ClusterRuntime(clock=FakeClock(1000.0))
    rt.add_flavor(ResourceFlavor(name="default"))
    kw = {"preemption": preemption} if preemption is not None else {}
    rt.add_cluster_queue(
        ClusterQueue(
            name="cq",
            namespace_selector={},
            resource_groups=(
                ResourceGroup(("cpu",), (FlavorQuotas.build("default", {"cpu": quota}),)),
            ),
            **kw,
        )
    )
    rt.add_local_queue(LocalQueue(namespace="ns", name="lq", cluster_queue="cq"))
    return rt


def workload(name, cpu="1", priority=0, t=0.0):
    return Workload(
        namespace="ns", name=name, queue_name="lq", priority=priority,
        creation_time=t, pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),),
    )


# ---- random mutation sequences ----


def _pick(rng, rt):
    return rt.workloads[rng.choice(sorted(rt.workloads))] if rt.workloads else None


def op_set_condition(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        wl.set_condition(rng.choice(list(T)), rng.random() < 0.5,
                         rng.choice(REASONS), f"m{rng.random()}")


def op_flip_condition(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None and wl.conditions:
        t = rng.choice(sorted(wl.conditions))
        c = wl.conditions[t]
        wl.set_condition(t, not c.status, c.reason, c.message)


def op_message_only(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None and wl.conditions:
        t = rng.choice(sorted(wl.conditions))
        c = wl.conditions[t]
        wl.set_condition(t, c.status, c.reason, f"other {rng.random()}")


def op_transition_time_only(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None and wl.conditions:
        c = wl.conditions[rng.choice(sorted(wl.conditions))]
        c.last_transition_time = rng.random() * 1e6


def op_reorder_conditions(rng, rt):
    """Pop and re-set every condition in another order: the same
    mapping, a different insertion order."""
    wl = _pick(rng, rt)
    if wl is not None and len(wl.conditions) > 1:
        items = list(wl.conditions.items())
        rng.shuffle(items)
        wl.conditions.clear()
        wl.conditions.update(items)


def op_pop_evicted(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        wl.conditions.pop(T.EVICTED, None)


def op_toggle_active(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        wl.active = not wl.active


def op_set_admission(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        wl.admission = Admission(cluster_queue=rng.choice(["cq", "cq-b"]))


def op_clear_admission(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        wl.admission = None


def op_check_state_in_place(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None and wl.admission_check_states:
        st = wl.admission_check_states[rng.choice(sorted(wl.admission_check_states))]
        st.state = rng.choice(STATES)


def op_add_check(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        name = rng.choice(["prov", "mk", "extra"])
        wl.admission_check_states[name] = AdmissionCheckState(
            name=name, state=rng.choice(STATES))


def op_toggle_job(rng, rt):
    if rt.jobs:
        job = rt.jobs[rng.choice(sorted(rt.jobs))]
        job.suspended = not job.suspended


def op_add_workload(rng, rt):
    rt.add_workload(workload(f"w{rng.randrange(10_000)}", t=rng.random()))


def op_delete_workload(rng, rt):
    wl = _pick(rng, rt)
    if wl is not None:
        rt.delete_workload(wl)


def op_readd_same_key(rng, rt):
    """Delete a workload and add back an equal copy under its key in
    the same step: unchanged unless the copy is then altered."""
    wl = _pick(rng, rt)
    if wl is not None:
        twin = copy.deepcopy(wl)
        rt.delete_workload(wl)
        rt.add_workload(twin)
        if rng.random() < 0.5:
            twin.active = not twin.active


def op_add_or_delete_job(rng, rt):
    key = f"ns/j{rng.randrange(4)}"
    if key in rt.jobs and rng.random() < 0.5:
        del rt.jobs[key]
    else:
        rt.jobs[key] = BatchJob.build("ns", key.split("/")[1], "lq",
                                      requests={"cpu": "1"})


def op_event_dedup_bump(rng, rt):
    # the same (kind, object, message) again: one series, count + 1
    rt.events.record("Pending", "ns/w-event", "same message")


CHANGING = [
    op_set_condition, op_flip_condition, op_pop_evicted, op_toggle_active,
    op_set_admission, op_clear_admission, op_check_state_in_place,
    op_add_check, op_toggle_job, op_add_workload, op_delete_workload,
    op_readd_same_key, op_add_or_delete_job, op_event_dedup_bump,
]
QUIET = [op_message_only, op_transition_time_only, op_reorder_conditions]


def seeded_runtime(rng):
    rt = base_runtime()
    for i in range(12):
        wl = workload(f"w{i}", t=float(i))
        rt.add_workload(wl)
        for t in rng.sample(list(T), rng.randrange(3)):
            wl.set_condition(t, rng.random() < 0.5, rng.choice(REASONS), "m")
        if rng.random() < 0.3:
            wl.admission_check_states["prov"] = AdmissionCheckState(name="prov")
    for i in range(3):
        rt.jobs[f"ns/j{i}"] = BatchJob.build("ns", f"j{i}", "lq",
                                             requests={"cpu": "1"})
    return rt


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 11])
def test_walk_answers_exactly_what_the_full_fingerprint_answers(seed):
    rng = random.Random(seed)
    rt = seeded_runtime(rng)
    last = _LastWalk()
    rt._quiescence_walk(last)
    answers = {True: 0, False: 0}
    for step in range(300):
        before = oracle_fingerprint(rt)
        ops = QUIET if rng.random() < 0.4 else CHANGING + QUIET
        for _ in range(rng.randrange(4)):
            rng.choice(ops)(rng, rt)
        after = oracle_fingerprint(rt)
        changed = rt._quiescence_walk(last)
        assert (changed > 0) == (before != after), step
        assert changed == oracle_changed(before, after), step
        answers[changed > 0] += 1
    # both answers are exercised
    assert min(answers.values()) > 30, answers


def test_quiet_writes_read_as_unchanged():
    rng = random.Random(0)
    rt = seeded_runtime(rng)
    last = _LastWalk()
    rt._quiescence_walk(last)
    for op in QUIET * 20:
        op(rng, rt)
    assert rt._quiescence_walk(last) == 0


def test_delete_and_readd_of_an_equal_copy_is_unchanged():
    rt = base_runtime()
    wl = workload("a")
    rt.add_workload(wl)
    wl.set_condition(T.QUOTA_RESERVED, False, "Pending", "m")
    last = _LastWalk()
    rt._quiescence_walk(last)
    twin = copy.deepcopy(wl)
    rt.delete_workload(wl)
    rt.add_workload(twin)
    assert rt._quiescence_walk(last) == 0
    rt.delete_workload(twin)
    assert rt._quiescence_walk(last) == 1


# ---- run_until_idle against the oracle-driven loop ----


def scenario_classic_preemption():
    rt = base_runtime(preemption=Preemption(
        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY))

    def steps():
        for i in range(4):
            rt.add_workload(workload(f"low-{i}", t=float(i)))
        yield
        rt.add_workload(workload("high", cpu="2", priority=100, t=10.0))
        yield
        rt.clock.advance(5.0)
        yield

    return rt, steps


def scenario_check_pending_to_ready():
    rt = base_runtime()
    rt.add_admission_check(
        AdmissionCheck(name="prov-check", controller_name="test-controller"))
    rt.cache.cluster_queues["cq"].model.admission_checks = ("prov-check",)

    def steps():
        rt.add_job(BatchJob.build("ns", "j", "lq", requests={"cpu": "1"}))
        yield
        wl = rt.workloads["ns/job-j"]
        assert wl.admission_check_states["prov-check"].state == (
            AdmissionCheckStateType.PENDING)
        wl.admission_check_states["prov-check"].state = (
            AdmissionCheckStateType.READY)
        yield
        assert wl.is_admitted

    return rt, steps


def scenario_evicted_and_requeued():
    rt = base_runtime()

    def steps():
        rt.add_job(BatchJob.build("ns", "victim", "lq", requests={"cpu": "1"}))
        yield
        wl = rt.workloads["ns/job-victim"]
        wl.set_condition(T.EVICTED, True, EVICTED_BY_PREEMPTION,
                         "Preempted to accommodate a higher priority Workload",
                         now=rt.clock.now())
        yield
        assert wl.has_quota_reservation

    return rt, steps


@pytest.mark.parametrize("make", [
    scenario_classic_preemption,
    scenario_check_pending_to_ready,
    scenario_evicted_and_requeued,
])
def test_run_until_idle_returns_what_the_oracle_loop_returns(make):
    rt, steps = make()
    twin, twin_steps = make()
    got, want = [], []
    for _, _ in zip(steps(), twin_steps()):
        got.append(rt.run_until_idle())
        want.append(oracle_run_until_idle(twin))
        assert oracle_fingerprint(rt) == oracle_fingerprint(twin)
    assert got == want
    assert max(got) >= 2, got
