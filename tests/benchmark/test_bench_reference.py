"""The plain reference decides what the program's host cycle loop
decides (no solver, no drain) on the same events; it stops where a
preemption is due, which it does not decide; and it reads quantities as
Kubernetes does."""

import json

import pytest

from bench_tiny import tiny_registry
from benchmark.references.kueue_classic import PreemptionDue, Reference, quantity


def test_quantities():
    assert quantity("cpu", "3") == 3000
    assert quantity("cpu", "250m") == 250
    assert quantity("memory", "2Gi") == 2 * 2**30
    assert quantity("memory", "5k") == 5000
    with pytest.raises(ValueError):
        quantity("cpu", "1.5")


class HostOnly:
    """The program's host-only runtime, driven in-process."""

    def __init__(self):
        from kueue_tpu.controllers import ClusterRuntime
        from kueue_tpu.server import KueueServer

        self.rt = ClusterRuntime(solver_path="host", use_solver=False,
                                 use_preempt_solver=False,
                                 bulk_drain_threshold=None)
        self.srv = KueueServer(runtime=self.rt, auto_reconcile=False)

    def load(self, body):
        self.srv.apply_batch(body)

    def submit(self, payload, count):
        self.srv.apply_batch(json.loads(payload))

    def settle(self):
        while any(pq.pending_active() for pq in self.rt.queues.cluster_queues.values()):
            self.rt.run_until_idle(max_iterations=1000)

    def remove(self, keys):
        for k in keys:
            self.rt.delete_workload(self.rt.workloads[k])

    def admitted(self):
        return {
            k: (wl.admission.cluster_queue,
                tuple(sorted((p.name, r, f) for p in wl.admission.pod_set_assignments
                             for r, f in p.flavors.items())),
                None)
            for k, wl in self.rt.workloads.items() if wl.has_quota_reservation
        }


@pytest.mark.parametrize("seed", [3, 2**31 + 1])
def test_reference_equals_the_host_cycle_loop(seed, tmp_path):
    reg = tiny_registry(tmp_path)
    cfg = reg.data("configs", "northstar-1k")
    loops = reg.module("loops", "passes")
    traffic = reg.data("traffic", "fresh")
    loop = loops.PassLoop(cfg, traffic, seed,
                          reg.module("generators", cfg["generator"]))
    system = HostOnly()
    loop.load(system)
    for _ in range(3):
        loop.one_pass(system)
    compared = loops.replay(Reference(), loop.log)
    assert [c["mismatched"] for c in compared] == [0] * len(loop.log)
    assert all(c["program"] > 0 for c in compared[1:])


def _cq(name, cohort, nominal, preemption):
    return {"name": name, "cohort": cohort, "preemption": preemption,
            "resourceGroups": [{"coveredResources": ["cpu"], "flavors": [{
                "name": "f", "resources": [{"name": "cpu", "nominalQuota": str(nominal),
                                            "borrowingLimit": "10"}]}]}]}


def _wl(name, lq, priority, cpu, t):
    return {"namespace": "n", "name": name, "queueName": lq, "priority": priority,
            "creationTime": t, "podSets": [{"name": "main", "count": 1,
                                             "requests": {"cpu": str(cpu)}}]}


@pytest.mark.parametrize("case", ["within", "reclaim", "never"])
def test_a_due_preemption_stops_the_reference(case):
    prem = {"within": {"withinClusterQueue": "LowerPriority"},
            "reclaim": {"reclaimWithinCohort": "Any"},
            "never": {}}[case]
    ref = Reference()
    ref.apply({"clusterqueues": [_cq("a", "c", 4, prem), _cq("b", "c", 4, prem)],
               "localqueues": [{"namespace": "n", "name": "la", "clusterQueue": "a"},
                               {"namespace": "n", "name": "lb", "clusterQueue": "b"}]})
    # a low-priority workload takes a's quota and borrows all of b's
    first = "la" if case == "within" else "lb"
    ref.apply({"workloads": [_wl("low", first, 0, 8, 1.0)]})
    ref.run()
    assert set(ref.admitted()) == {"n/low"}
    ref.apply({"workloads": [_wl("high", "la", 10, 4, 2.0)]})
    if case == "never":
        ref.run()
        assert set(ref.admitted()) == {"n/low"}
    else:
        with pytest.raises(PreemptionDue):
            ref.run()
