"""The program against the plain reference of flavor fungibility
(``benchmark/references/kueue_fungible.py``, which imports nothing of the
program) on seeded random deployments at a small size
(``fungibility_cases``), in host cycles and in per-cycle device solves;
the bulk drains are ``test_fungibility_drains.py``'s. Each deployment has
one or two resource groups of 2-4 flavors, random quotas and a random
``whenCanBorrow`` per ClusterQueue; every pass must admit what the
reference admits.

Also: the reference on upstream's fungibility cases (the ones without
preemption of ``tests/test_reference_truth_tables.py``, restated on the
wire format), and a control: a reference that walks every ClusterQueue
as ``whenCanBorrow: Borrow`` mismatches the program on a small
``fungibility-1k``.
"""

import json
import os
import sys

import pytest

from fungibility_cases import (
    SEEDS,
    Reference,
    admitted,
    assert_equal,
    compare,
    replay,
    runtime,
    settle,
)
from kueue_tpu.server import KueueServer


@pytest.mark.parametrize("seed", SEEDS)
def test_host_cycles_match_the_reference(seed):
    compared, traces = compare("host", seed)
    assert_equal(compared, 2)
    assert {t.resolution for t in traces} == {"host"}


@pytest.mark.parametrize("seed", SEEDS)
def test_device_cycles_match_the_reference(seed):
    compared, traces = compare("device", seed)
    assert_equal(compared, 2)
    assert any(t.resolution == "device" for t in traces)
    # every head, TryNextFlavor ones included, is lowered onto the device
    assert all(t.counts.get("unrepresentable", 0) == 0 for t in traces)


# ---- the reference on upstream's fungibility cases ----
def _cq(name, flavors, cohort=None, when_can_borrow=None):
    obj = {"name": name, "namespaceSelector": {},
           "resourceGroups": [{"coveredResources": ["cpu", "pods"], "flavors": [
               {"name": f, "resources": [
                   dict({"name": r, "nominalQuota": q[0]},
                        **({"borrowingLimit": q[1]} if len(q) > 1 else {}))
                   for r, q in res.items()]}
               for f, res in flavors]}]}
    if cohort:
        obj["cohort"] = cohort
    if when_can_borrow:
        obj["flavorFungibility"] = {"whenCanBorrow": when_can_borrow,
                                    "whenCanPreempt": "TryNextFlavor"}
    return obj


def _wl(name, lq, cpu, t):
    return {"namespace": "ns", "name": name, "queueName": lq, "creationTime": t,
            "podSets": [{"name": "main", "count": 1,
                         "requests": {"cpu": cpu, "pods": "1"}}]}


def _case(cq, secondary=None):
    """The reference after 2 cpu of usage on flavor ``one`` of ``cq``
    and then a 9-cpu workload: the flavor it took."""
    ref = Reference()
    cqs = [cq] + ([secondary] if secondary else [])
    ref.apply({"clusterqueues": cqs, "localqueues": [
        {"namespace": "ns", "name": f"lq-{c['name']}", "clusterQueue": c["name"]}
        for c in cqs]})
    ref.apply({"workloads": [_wl("used", "lq-cq", "2", 0.0)]})
    ref.run()
    assert ref.admitted()["ns/used"][1] == (("main", "cpu", "one"), ("main", "pods", "one"))
    ref.apply({"workloads": [_wl("wl", "lq-cq", "9", 1.0)]})
    ref.run()
    got = ref.admitted().get("ns/wl")
    return None if got is None else dict((r, f) for _, r, f in got[1])


COHORT_CQ2 = _cq("cq2", [("one", {"cpu": ("1",)})], cohort="test-cohort")


@pytest.mark.parametrize("case", [
    # flavorassigner_test.go:1271, default fungibility: flavor one would
    # need preemption, flavor two fits
    ("preempt_try_next_flavor", None, ("10",), ("10",), False, "two"),
    # :1311, TryNextFlavor: one fits by borrowing, two cannot fit at all
    ("borrow_try_next_flavor_found_the_first_flavor", "TryNextFlavor",
     ("10", "1"), ("1",), True, "one"),
    # :1362, TryNextFlavor: one fits by borrowing, two fits without
    ("borrow_try_next_flavor_found_the_second_flavor", "TryNextFlavor",
     ("10", "1"), ("10",), True, "two"),
    # :1413, default whenCanBorrow Borrow: the borrowing fit on one wins
    ("borrow_before_try_next_flavor", None, ("10", "1"), ("10",), True, "one"),
], ids=lambda c: c[0] if isinstance(c, tuple) else str(c))
def test_reference_on_upstream_fungibility_cases(case):
    _, policy, cpu_one, cpu_two, in_cohort, want = case
    cq = _cq("cq", [("one", {"pods": ("10",), "cpu": cpu_one}),
                    ("two", {"pods": ("10",), "cpu": cpu_two})],
             cohort="test-cohort" if in_cohort else None, when_can_borrow=policy)
    got = _case(cq, COHORT_CQ2 if in_cohort else None)
    assert got == {"cpu": want, "pods": want}


def test_reference_refuses_what_it_does_not_decide():
    with pytest.raises(ValueError):
        Reference().apply({"clusterqueues": [dict(
            _cq("cq", [("one", {"cpu": ("1",)})]),
            preemption={"withinClusterQueue": "LowerPriority"})]})
    with pytest.raises(ValueError):
        Reference().apply({"clusterqueues": [dict(
            _cq("cq", [("one", {"cpu": ("1",)})]),
            flavorFungibility={"whenCanPreempt": "Preempt"})]})


def test_a_reference_that_always_borrows_mismatches_the_program(tmp_path):
    """The control: the configuration's reference with every
    ClusterQueue walked as whenCanBorrow Borrow must not agree with the
    program on the first pass of a small fungibility-1k, while the
    intact reference does."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
    try:
        from bench_tiny import tiny_registry
    finally:
        sys.path.pop(0)

    reg = tiny_registry(tmp_path)
    cell = reg.cell("fungibility-1k.fresh")
    loops = reg.module("loops", "passes")
    loop = loops.PassLoop(cell["config"], cell["traffic"], 2**31 + 9,
                          reg.module("generators", "fungibility"))
    rt = runtime("host")
    srv = KueueServer(runtime=rt, auto_reconcile=False)

    class System:
        def load(self, body):
            srv.apply_batch(body)

        def submit(self, payload, count):
            srv.apply_batch(json.loads(payload))

        def settle(self):
            settle(rt)

        def remove(self, keys):
            for key in keys:
                rt.delete_workload(rt.workloads[key])

        def admitted(self):
            return admitted(rt)

    loop.load(System())
    loop.one_pass(System())
    assert [c["mismatched"] for c in replay(Reference(), loop.log)] == [0, 0]
    control = replay(Reference(force_borrow=True), loop.log)
    assert control[1]["mismatched"] > 0, control
