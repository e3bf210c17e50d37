"""The check fails a run whose timed path is broken underneath.

Each fault is planted in the served program of a tiny cell, the rest of
the run is the harness's own (the look for a chip skipped), and
``correct`` must come out false: a step that leaves the state
unchanged, half of a batch left out, and an admission's answer altered
where the program produces it."""

import json

import pytest

from bench_tiny import tiny_registry
from benchmark.harness import run_cell
from benchmark.registry import Registry
from benchmark.served import Served

CELLS = [w["name"] for w in Registry().manifest()["workloads"]]


class StateUnchanged(Served):
    """Every reconcile returns at once and decides nothing."""

    def __init__(self, options):
        from kueue_tpu.core.scheduler import CycleResult

        super().__init__(options)
        self.rt.scheduler.schedule = CycleResult
        self.rt.bulk_drain = lambda: None


class HalfBatch(Served):
    """Every batch POST carries only its first half."""

    def submit(self, payload, count):
        half = json.loads(payload)["workloads"][: count // 2]
        super().submit(json.dumps({"workloads": half}), len(half))


class AlteredAnswer(Served):
    """The first admission of every pass names another flavor than the
    one the program decided, as it is applied."""

    def __init__(self, options):
        super().__init__(options)
        sched = self.rt.scheduler
        inner = sched.admit_prepared
        self.altered = 0

        def admit_prepared(wl, cq_name, admission, cq_model):
            if self.altered < 1:
                self.altered += 1
                for psa in admission.pod_set_assignments:
                    for r, f in list(psa.flavors.items()):
                        psa.flavors[r] = f + "-altered"
            return inner(wl, cq_name, admission, cq_model)

        sched.admit_prepared = admit_prepared

    def reset_counters(self):
        super().reset_counters()
        self.altered = 0


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch, AlteredAnswer])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_fails_the_check(cell, fault, tmp_path):
    reg = tiny_registry(tmp_path)
    res = run_cell(cell, 2**31 + 11, 0.3, False, registry=reg,
                   require_tpu=False, system_factory=fault)
    assert not res["correct"], (fault.__name__, res["checks"])
    assert res["checks"]["mismatched_admissions"]["value"] > 0
