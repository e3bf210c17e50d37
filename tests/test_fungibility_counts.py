"""The fungibility counters on ``CycleTrace.counts`` (served on
``/debug/cycles``, registered in ``tracing/names.py``): heads of a
non-default-fungibility ClusterQueue offered to the per-cycle lowering
(``fungible_heads``) and lowered onto the device (``fungible_lowered``),
admissions whose flavor lies past one where they fit only by borrowing
(``borrow_skipped``), and heads lowered with a resume cursor
(``walks_resumed``).

The scenario: one cohort of three ClusterQueues over flavors f1 and f2
(cpu nominal 2/4 in ``cq-t``, whenCanBorrow TryNextFlavor; 4/0 in
``cq-b`` and 0/0 in ``cq-c``, both Borrow), one head each: ``cq-t``
asks 3 cpu, ``cq-b`` 1 and ``cq-c`` 6. In the first cycle ``cq-t``'s
head fits f1 only by borrowing and f2 without, so it takes f2;
``cq-b``'s takes f1; ``cq-c``'s fits f1 by borrowing the whole pool at
the start of the cycle but loses it to ``cq-b``'s, and goes back with
its walk cursor at f2. The second cycle lowers it from f2, where it
cannot fit: it is parked.
"""

import pytest

from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.core.scheduler import CycleTrace
from kueue_tpu.models import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    ResourceFlavor,
    ResourceGroup,
    Workload,
)
from kueue_tpu.models.cluster_queue import FlavorFungibility
from kueue_tpu.models.constants import FlavorFungibilityPolicy as FF
from kueue_tpu.models.workload import PodSet
from kueue_tpu.tracing import CYCLE_COUNTS
from kueue_tpu.utils.clock import FakeClock

NEW = {"fungible_heads", "fungible_lowered", "borrow_skipped", "walks_resumed"}


def build_rt(use_solver, when_can_preempt=FF.TRY_NEXT_FLAVOR):
    rt = ClusterRuntime(
        clock=FakeClock(0.0),
        use_solver=use_solver,
        solver_path="auto" if use_solver else "host",
        bulk_drain_threshold=None,
    )
    for f in ("f1", "f2"):
        rt.add_flavor(ResourceFlavor(name=f))
    quotas = {"cq-t": ("2", "4"), "cq-b": ("4", "0"), "cq-c": ("0", "0")}
    for name, (f1, f2) in quotas.items():
        rt.add_cluster_queue(ClusterQueue(
            name=name,
            cohort="co",
            namespace_selector={},
            flavor_fungibility=FlavorFungibility(
                when_can_borrow=FF.TRY_NEXT_FLAVOR if name == "cq-t" else FF.BORROW,
                when_can_preempt=when_can_preempt if name == "cq-t" else FF.TRY_NEXT_FLAVOR,
            ),
            resource_groups=(ResourceGroup(("cpu",), (
                FlavorQuotas.build("f1", {"cpu": f1}),
                FlavorQuotas.build("f2", {"cpu": f2}),
            )),),
        ))
        rt.add_local_queue(LocalQueue(namespace="ns", name=f"lq-{name}", cluster_queue=name))
    for i, (name, cpu) in enumerate([("cq-t", "3"), ("cq-b", "1"), ("cq-c", "6")]):
        rt.add_workload(Workload(
            namespace="ns", name=f"w-{name}", queue_name=f"lq-{name}",
            creation_time=float(i), pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),),
        ))
    rt.run_until_idle()
    return rt


def flavors(rt):
    return {
        k: w.admission.pod_set_assignments[0].flavors
        for k, w in rt.workloads.items() if w.has_quota_reservation
    }


def test_counts_equal_hand_counts_on_the_device():
    rt = build_rt(use_solver=True)
    first, second = rt.scheduler.last_traces
    assert flavors(rt) == {"ns/w-cq-t": {"cpu": "f2"}, "ns/w-cq-b": {"cpu": "f1"}}
    assert (first.resolution, first.admitted) == ("device", 2)
    # the TryNextFlavor head is lowered onto the device, not handed to
    # the host: unrepresentable stays 0
    assert first.counts == {
        "forest_walks": 1, "prevalidated": 3, "lowered": 3, "unrepresentable": 0,
        "fungible_heads": 1, "fungible_lowered": 1, "launches": 1, "returned": 0,
        "no_candidates": 0, "borrow_skipped": 1, "parked": 1,
    }
    assert second.counts == {
        "forest_walks": 1, "prevalidated": 1, "lowered": 1, "unrepresentable": 0,
        "walks_resumed": 1, "launches": 1, "returned": 1, "walked": 1,
        "no_candidates": 0, "parked": 1,
    }
    # and they reach the /debug/cycles wire form
    assert first.to_dict()["counts"]["borrow_skipped"] == 1
    assert second.to_dict()["counts"]["walks_resumed"] == 1


def test_the_host_walk_counts_borrow_skipped_alike():
    rt = build_rt(use_solver=False)
    first, second = rt.scheduler.last_traces
    assert flavors(rt) == {"ns/w-cq-t": {"cpu": "f2"}, "ns/w-cq-b": {"cpu": "f1"}}
    assert first.counts == {
        "forest_walks": 1, "prevalidated": 3, "walked": 3, "no_candidates": 0,
        "borrow_skipped": 1, "parked": 1,
    }
    # no per-cycle lowering on the host path: nothing offered, lowered
    # or resumed in a lowering
    lowering = {"fungible_heads", "fungible_lowered", "walks_resumed"}
    assert not lowering & (set(first.counts) | set(second.counts))


def test_when_can_preempt_preempt_stays_on_the_host():
    # a walk that may stop at a preempt-mode flavor is not the kernel's
    # rule: the head is offered, not lowered, and decides the same
    rt = build_rt(use_solver=True, when_can_preempt=FF.PREEMPT)
    first = rt.scheduler.last_traces[0]
    assert first.counts["fungible_heads"] == 1
    assert "fungible_lowered" not in first.counts
    assert first.counts["unrepresentable"] == 1
    assert flavors(rt) == {"ns/w-cq-t": {"cpu": "f2"}, "ns/w-cq-b": {"cpu": "f1"}}


def test_the_counts_are_registered():
    assert NEW <= CYCLE_COUNTS
    trace = CycleTrace()
    for name in NEW:
        trace.add_count(name, 1)
    with pytest.raises(ValueError):
        trace.add_count("not_a_count", 1)


def test_drain_rounds_count_the_walks_they_resume():
    """Serial drain rounds of 3 cycles on a random fungibility deployment:
    each round lowers with a cursor exactly the heads the round before
    cut off mid-walk."""
    from fungibility_cases import deployment, run_passes, runtime, workloads

    body, n_groups, rng = deployment(3)
    n_cq = len(body["clusterqueues"])
    rt = runtime("serial", chunk=3)
    handed = []
    resume = rt._resume_walks

    def counting(outcome):
        handed.append(sum(1 for _, _, walks in outcome.resume if walks.max() > 0))
        return resume(outcome)

    rt._resume_walks = counting
    run_passes(rt, body, [workloads(rng, n_groups, n_cq, "w", 0.0)])
    rounds = [t for t in rt.scheduler.last_traces if t.resolution == "drain"]
    assert len(rounds) > 2
    # a round that decided nothing hands its walks on but keeps no trace
    got = [t.counts.get("walks_resumed", 0) for t in rounds]
    assert got == [0] + handed[: len(rounds) - 1]
    assert sum(got) > 0


def _cycle(resolution, admitted, heads, counts):
    return {"resolution": resolution, "admitted": admitted, "heads": heads,
            "counts": counts, "spansMs": {}, "subMs": {}}


def test_the_benchmark_readers_on_a_handmade_record():
    """The three per-layer readers of ``fungibility-1k.fresh`` on two
    passes: a drain round that resumed 5 of its 100 heads, and device
    cycles that lowered 60 heads, 20 of them TryNextFlavor, 3 resumed,
    admitting 40 of which 4 skipped a borrowing flavor; and on the same
    record without the counters, as the parent program writes it."""
    from benchmark.registry import Registry

    reg = Registry()
    cycles = [
        _cycle("drain", 30, 100, {"walks_resumed": 5}),
        _cycle("device", 30, 30, {"lowered": 30, "fungible_heads": 10,
                                  "fungible_lowered": 10, "borrow_skipped": 3,
                                  "walks_resumed": 1}),
        _cycle("host", 10, 30, {"lowered": 30, "fungible_heads": 10,
                                "fungible_lowered": 10, "borrow_skipped": 1,
                                "walks_resumed": 2}),
    ]
    record = {"passes": [{"cycles": cycles[:1]}, {"cycles": cycles[1:]}]}
    read = {m: reg.module("metrics", m).read(record) for m in
            ("fungible_on_device_pct", "borrow_skipped_pct", "walks_resumed_pct")}
    assert read == pytest.approx({
        "fungible_on_device_pct": 100.0,
        "borrow_skipped_pct": 100.0 * 4 / 70,
        "walks_resumed_pct": 100.0 * 8 / 160,
    })
    for c in cycles:
        for name in NEW:
            c["counts"].pop(name, None)
    assert all(reg.module("metrics", m).read(record) is None for m in read)
