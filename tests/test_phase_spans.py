"""Program phases (kueue_tpu/tracing/phases.py): the scheduler cycle's
sub-phases and counts on ``CycleTrace``, their lowering into the cycle's
span tree, the runtime loop's and the batch apply's cumulative totals,
and the ``kueue.*`` profiler annotations they carry.

The scenario: 12 ClusterQueues of 4 cpu each, every cycle solved on the
device. Five 1-cpu workloads per ClusterQueue: four cycles admit four
of them, and the fifth cycle's heads all come back from the kernel
non-Fit, go to victim search (none is lower priority) and are parked.
Then one 2-cpu workload per ClusterQueue at a higher priority: the
kernel hands every head back again, and the host walk finds two victims
for each.
"""

import glob
import json
import os
import urllib.request

import pytest

from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.models import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    ResourceFlavor,
    ResourceGroup,
    Workload,
)
from kueue_tpu.models.cluster_queue import Preemption
from kueue_tpu.models.constants import PreemptionPolicy, ReclaimWithinCohortPolicy
from kueue_tpu.models.workload import PodSet
from kueue_tpu.testing import faults
from kueue_tpu.tracing import (
    CYCLE_PHASE_SPANS,
    CYCLE_SPAN_NAMES,
    CYCLE_SUBPHASE_SPANS,
    EVENT_SPANS,
    PHASE_COUNTERS,
    PHASE_NAMES,
    RUNTIME_PHASE_NAMES,
    SPAN_NAMES,
    Phase,
    PhaseTotals,
    phase,
)
from kueue_tpu.utils.clock import FakeClock

N_CQ = 12
SUB_NAMES = {name for name, _ in CYCLE_SUBPHASE_SPANS.values()}
LOOP_NAMES = {"loop.fingerprint", "loop.reconcile", "loop.drain_gate",
              "loop.report", "loop.audit"}
LOOP_COUNTERS = {"loop.iterations", "loop.fingerprint_walks",
                 "loop.fingerprint_changed"}


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


def build_rt(tracing=True, solver_path="auto"):
    rt = ClusterRuntime(
        clock=FakeClock(0.0),
        use_solver=True,
        solver_path=solver_path,
        # victim search on the host loop: no victim kernel to compile
        preempt_solver_threshold=10_000,
        bulk_drain_threshold=None,
        tracing=tracing,
    )
    rt.add_flavor(ResourceFlavor(name="default"))
    for i in range(N_CQ):
        rt.add_cluster_queue(
            ClusterQueue(
                name=f"cq-{i}",
                namespace_selector={},
                preemption=Preemption(
                    within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
                ),
                resource_groups=(
                    ResourceGroup(
                        ("cpu",), (FlavorQuotas.build("default", {"cpu": "4"}),)
                    ),
                ),
            )
        )
        rt.add_local_queue(
            LocalQueue(namespace="ns", name=f"lq-{i}", cluster_queue=f"cq-{i}")
        )
    return rt


def add_backlog(rt, prefix, per_cq, cpu, priority, t0):
    for j in range(per_cq):
        for i in range(N_CQ):
            rt.add_workload(
                Workload(
                    namespace="ns",
                    name=f"{prefix}-{i}-{j}",
                    queue_name=f"lq-{i}",
                    priority=priority,
                    creation_time=t0 + j * N_CQ + i,
                    pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),),
                )
            )


def run_scenario(rt):
    """Returns the cycle traces of the two phases of the scenario."""
    add_backlog(rt, "low", 5, "1", 0, 0.0)
    rt.run_until_idle()
    first = list(rt.scheduler.last_traces)
    add_backlog(rt, "high", 1, "2", 100, 1000.0)
    rt.run_until_idle()
    return first, list(rt.scheduler.last_traces)[len(first):]


def decisions(rt):
    return {
        k: (w.admission.cluster_queue,
            tuple(sorted(w.admission.pod_set_assignments[0].flavors.items())))
        for k, w in rt.workloads.items()
        if w.has_quota_reservation
    }


@pytest.fixture(scope="module")
def scenario():
    rt = build_rt()
    first, second = run_scenario(rt)
    return rt, first, second


def test_every_new_name_is_emitted_and_registered(scenario):
    rt, first, second = scenario
    stored = {
        s.name
        for summary in rt.tracer.traces_summary(limit=10_000)
        if summary["root"] == "cycle"
        for s in rt.tracer.trace(summary["traceId"])
    }
    assert SUB_NAMES <= stored, SUB_NAMES - stored
    assert stored <= SPAN_NAMES
    totals = rt.phase_totals()
    assert LOOP_NAMES <= set(totals["spansMs"])
    assert set(totals["spansMs"]) <= RUNTIME_PHASE_NAMES
    assert totals["counts"]["loop.iterations"] >= 2
    assert LOOP_COUNTERS <= set(totals["counts"])
    # counts and sub-phases reach the /debug/cycles wire form
    d = second[-1].to_dict()
    assert set(d["subMs"]) == set(second[-1].sub)
    assert d["counts"] == second[-1].counts


def test_sub_phases_sum_to_no_more_than_their_parent(scenario):
    rt, first, second = scenario
    for trace in first + second:
        assert trace.sub, trace.cycle
        for parent in ("nominate", "admit"):
            inside = sum(
                v for k, v in trace.sub.items()
                if CYCLE_SUBPHASE_SPANS[k][1] == parent
            )
            assert inside <= trace.spans[parent], (trace.cycle, parent)


def test_sub_phase_spans_are_children_of_their_phase(scenario):
    rt, first, second = scenario
    by_parent_name = {name: CYCLE_PHASE_SPANS[p]
                      for name, p in CYCLE_SUBPHASE_SPANS.values()}
    for trace in first + second:
        spans = rt.tracer.trace(trace.trace_id)
        by_id = {s.span_id: s for s in spans}
        subs = [s for s in spans if s.name in SUB_NAMES]
        assert len(subs) == len(trace.sub)
        for s in subs:
            parent = by_id[s.parent_id]
            assert parent.name == by_parent_name[s.name]
            assert s.start >= parent.start - 1e-9
            assert s.start + s.duration <= parent.start + parent.duration + 1e-9


def test_counts_equal_hand_counts(scenario):
    rt, first, second = scenario
    # cycles 1-4 admit four per ClusterQueue on the device
    assert [t.admitted for t in first] == [N_CQ] * 4 + [0]
    for t in first[:4]:
        assert t.resolution == "device"
        assert t.counts["lowered"] == N_CQ
        assert t.counts["launches"] == 1
        assert t.counts["returned"] == 0
        assert t.counts["parked"] == 0
        assert "assign" not in t.sub
    # cycle 5: every head comes back from the kernel non-Fit (preempt
    # mode, with no lower-priority candidate), the host walk defers each
    # to victim search and parks it: the host loop decides the cycle,
    # whose resolution reads "host" although it launched. The admitted
    # workloads share the heads' priority, so the candidate index cannot
    # rule them out and each takes the full candidate scan.
    fifth = first[4]
    assert fifth.resolution == "host"
    assert fifth.counts == {
        "forest_walks": 1, "prevalidated": N_CQ, "lowered": N_CQ, "unrepresentable": 0,
        "launches": 1, "returned": N_CQ, "walked": N_CQ,
        "deferred": N_CQ, "parked": N_CQ, "no_candidates": 0,
    }
    assert {"assign", "victims", "launch", "fetch"} <= set(fifth.sub)
    # the higher-priority heads: launched, all handed back, every one
    # finds its victims on the host and waits for their preemption
    preempting = [t for t in second if t.preempting]
    assert len(preempting) == 1
    t = preempting[0]
    assert t.resolution == "host"
    assert t.preempting == N_CQ
    assert t.counts["launches"] == 1
    assert t.counts["lowered"] == t.counts["returned"] == N_CQ
    assert t.counts["deferred"] == N_CQ
    assert t.counts["parked"] == N_CQ  # pending the preemption
    # over the scenario: every cycle launched once, and its snapshot
    # walked the cohort forest for cycles once
    cycles = first + second
    assert sum(t.counts["launches"] for t in cycles) == len(cycles)
    assert [t.counts["forest_walks"] for t in cycles] == [1] * len(cycles)
    assert sum(t.counts["lowered"] for t in cycles) == N_CQ * len(cycles)


@pytest.mark.parametrize("victim_threshold", [1, 10_000], ids=["batched", "host"])
def test_no_candidates_equals_deferred_when_the_large_fills_nominal(victim_threshold):
    """Two cohorts of six ClusterQueues (20 cpu nominal, 100 borrowing,
    reclaim Any, LowerPriority), each with one large that fills its
    nominal quota, two mediums and three smalls. Once the larges are
    in, every head outranked by its large is deferred to victim search
    with nothing borrowed in its cohort, and the snapshot's candidate
    index answers each one."""
    rt = ClusterRuntime(
        clock=FakeClock(0.0),
        use_solver=True,
        preempt_solver_threshold=victim_threshold,
        bulk_drain_threshold=None,
    )
    rt.add_flavor(ResourceFlavor(name="default"))
    prem = Preemption(
        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
        reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
    )
    for i in range(N_CQ):
        rt.add_cluster_queue(
            ClusterQueue(
                name=f"cq-{i}",
                cohort=f"cohort-{i // 6}",
                namespace_selector={},
                preemption=prem,
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (FlavorQuotas.build("default", {"cpu": ("20", "100", None)}),),
                    ),
                ),
            )
        )
        rt.add_local_queue(
            LocalQueue(namespace="ns", name=f"lq-{i}", cluster_queue=f"cq-{i}")
        )
    classes = [("large", 1, 200, "20"), ("medium", 2, 100, "5"), ("small", 3, 50, "1")]
    t = 0.0
    for cls, count, priority, cpu in classes:
        for j in range(count):
            for i in range(N_CQ):
                t += 1.0
                rt.add_workload(
                    Workload(
                        namespace="ns", name=f"{cls}-{i}-{j}", queue_name=f"lq-{i}",
                        priority=priority, creation_time=t,
                        pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),),
                    )
                )
    rt.run_until_idle()
    traces = list(rt.scheduler.last_traces)
    assert sorted(k for k, w in rt.workloads.items() if w.has_quota_reservation) == sorted(
        f"ns/large-{i}-0" for i in range(N_CQ)
    )
    assert traces[0].admitted == N_CQ and traces[0].counts["no_candidates"] == 0
    deferring = [tr for tr in traces if tr.counts.get("deferred")]
    assert len(deferring) == 5  # two mediums and three smalls a queue
    for tr in deferring:
        assert tr.counts["no_candidates"] == tr.counts["deferred"] == N_CQ
        assert tr.to_dict()["counts"]["no_candidates"] == N_CQ


def test_admitted_sets_identical_with_tracing_on_and_off(tmp_path):
    from kueue_tpu._jax import jax

    on = build_rt(tracing=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_scenario(on)
    finally:
        jax.profiler.stop_trace()
    off = build_rt(tracing=False)
    run_scenario(off)
    assert decisions(on) == decisions(off)
    # per ClusterQueue: the high one, and two of the four low ones it
    # left room for
    assert len(decisions(on)) == 3 * N_CQ
    # the profiler capture holds the program's phases as kueue.* host
    # events, on the clock of the capture
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    names = {
        ev.name
        for path in paths
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("kueue.")
    }
    want = {"kueue." + n for n in SUB_NAMES | LOOP_NAMES}
    want |= {"kueue.cycle." + p for p in ("heads", "snapshot", "nominate",
                                          "admit")}
    assert want <= names, want - names


def test_crash_mid_cycle_leaves_no_sub_span():
    rt = build_rt()
    add_backlog(rt, "low", 2, "1", 0, 0.0)
    faults.arm("cycle.post_solve_pre_apply", "crash")
    with pytest.raises(faults.InjectedCrash):
        rt.run_until_idle()
    # the crashed cycle ran prevalidate, encode, launch, fetch and
    # decode; none of it reached the store or the ring
    assert not rt.scheduler.last_traces
    assert not [
        s for summary in rt.tracer.traces_summary(limit=10_000)
        for s in rt.tracer.trace(summary["traceId"])
        if s.name in SUB_NAMES
    ]
    assert rt.tracer.open_spans("cycle") == []


def test_contained_cycle_records_its_closed_sub_phases():
    """A device-mode dispatch runs without containment, so a raise after
    the launch escapes the nominate phase: the cycle guard contains it,
    and the sub-phases that closed before the raise hang off the root."""
    rt = build_rt(solver_path="device")
    add_backlog(rt, "low", 1, "1", 0, 0.0)

    def hang():
        raise RuntimeError("device lost")

    faults.arm("solver.device_hang", hang)
    result = rt.schedule_once()
    assert faults.fired("solver.device_hang") == 1
    assert result.admitted == []
    assert rt.scheduler.guard.contained_cycles == 1
    (trace,) = rt.scheduler.last_traces
    assert "nominate" not in trace.spans
    assert {"prevalidate", "encode", "launch", "fetch"} <= set(trace.sub)
    spans = rt.tracer.trace(trace.trace_id)
    (root,) = [s for s in spans if s.parent_id is None]
    subs = [s for s in spans if s.name in SUB_NAMES]
    assert len(subs) == len(trace.sub)
    assert all(s.parent_id == root.span_id for s in subs)
    # every head went back to the queue: the next cycle admits them all
    faults.reset()
    assert len(rt.schedule_once().admitted) == N_CQ


def test_batch_apply_phases_over_http():
    from kueue_tpu.server import KueueClient, KueueServer

    rt = build_rt()
    srv = KueueServer(runtime=rt)
    url = f"http://127.0.0.1:{srv.start()}"
    try:
        before = rt.phase_totals()
        wls = [
            {"namespace": "ns", "name": f"h{i}", "queueName": f"lq-{i}",
             "podSets": [{"name": "main", "count": 1,
                          "requests": {"cpu": "1"}}]}
            for i in range(N_CQ)
        ]
        out = KueueClient(url).apply_batch({"workloads": wls})
        assert out["applied"] == {"workloads": N_CQ}
        after = rt.phase_totals()
        with urllib.request.urlopen(url + "/debug/cycles") as resp:
            served = json.loads(resp.read())
    finally:
        srv.stop()
    assert "ingest.decode" not in before["spansMs"]
    assert after["spansMs"]["ingest.decode"] > 0
    assert after["spansMs"]["ingest.apply"] > 0
    assert after["counts"]["ingest.objects"] == N_CQ
    # the auto-reconcile after the apply is the loop's, not ingest's
    assert after["counts"]["loop.iterations"] > before["counts"].get(
        "loop.iterations", 0)
    assert served["runtimePhases"]["counts"]["ingest.objects"] == N_CQ
    assert LOOP_COUNTERS <= set(served["runtimePhases"]["counts"])
    assert all("subMs" in c and "counts" in c for c in served["cycles"])


def test_one_quiescence_walk_per_iteration_plus_one():
    """Each ``run_until_idle`` call walks the state once before its
    first iteration and once after each; an idle runtime's call changes
    no entry, a call that admits a backlog changes some."""
    rt = build_rt()

    def counts_of_one_call():
        before = rt.phase_totals()["counts"]
        rt.run_until_idle()
        after = rt.phase_totals()["counts"]
        return {k: after[k] - before.get(k, 0) for k in LOOP_COUNTERS}

    idle = counts_of_one_call()
    assert idle == {"loop.iterations": 1, "loop.fingerprint_walks": 2,
                    "loop.fingerprint_changed": 0}
    add_backlog(rt, "low", 2, "1", 0, 0.0)
    busy = counts_of_one_call()
    assert busy["loop.iterations"] >= 2
    assert busy["loop.fingerprint_walks"] == busy["loop.iterations"] + 1
    # every workload changed at least once (it was admitted)
    assert busy["loop.fingerprint_changed"] >= 2 * N_CQ
    assert counts_of_one_call()["loop.fingerprint_changed"] == 0


def test_phase_names_are_closed_and_checked_where_defined():
    with pytest.raises(ValueError, match="closed registry"):
        Phase("cycle.bogus")
    p = Phase("loop.report")
    assert (p.key, p.label) == ("report", "kueue.loop.report")
    assert PHASE_NAMES == CYCLE_SPAN_NAMES | RUNTIME_PHASE_NAMES
    assert not RUNTIME_PHASE_NAMES & SPAN_NAMES
    # a phase counts under its registered counter only
    assert set(PHASE_COUNTERS) <= PHASE_NAMES
    assert Phase("cycle.snapshot", counter="forest_walks").counter == "forest_walks"
    with pytest.raises(ValueError, match="PHASE_COUNTERS"):
        Phase("cycle.snapshot", counter="walked")
    with pytest.raises(ValueError, match="PHASE_COUNTERS"):
        Phase("loop.report", counter="loop.reports")


def test_region_adds_seconds_and_count_unless_it_raises():
    totals = PhaseTotals()
    p = Phase("ingest.apply", counter="ingest.objects")
    with phase(p, totals) as region:
        region.count = 3
    assert region.seconds >= 0
    with pytest.raises(RuntimeError):
        with phase(p, totals) as failed:
            failed.count = 5
            raise RuntimeError("boom")
    snap = totals.snapshot()
    assert snap["counts"] == {"ingest.objects": 3}
    assert snap["spansMs"]["ingest.apply"] == round(region.seconds * 1e3, 3)
    with phase(p) as bare:  # no sink: the region keeps its seconds
        pass
    assert bare.seconds >= 0 and totals.snapshot() == snap


def test_every_registered_name_has_an_emitter():
    """Every registry name is emitted somewhere in the program: a
    literal at a recording call site or a ``Phase``, a CycleTrace phase
    key, or an event reason's lifecycle span."""
    import kueue_tpu

    root = os.path.dirname(kueue_tpu.__file__)
    text = ""
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        if path.endswith(os.path.join("tracing", "names.py")):
            continue
        with open(path) as f:
            text += f.read()
    mapped = (set(CYCLE_PHASE_SPANS.values()) | set(EVENT_SPANS.values())
              | {"cycle"})
    silent = sorted(
        n for n in SPAN_NAMES | RUNTIME_PHASE_NAMES
        if n not in mapped and f'"{n}"' not in text
    )
    assert not silent, silent
