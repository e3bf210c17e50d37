"""On-device bulk drain vs the sequential host scheduler.

The drain kernel runs the whole multi-cycle backlog on device; for
preemption-free, fully-representable backlogs its decisions — who is
admitted, with which flavors, in which cycle — must match running the
host Scheduler cycle-by-cycle to quiescence.
"""

import numpy as np
import pytest

from kueue_tpu.core.drain import run_drain
from kueue_tpu.core.queue_manager import queue_order_timestamp
from kueue_tpu.core.snapshot import take_snapshot

from tests.test_solver_path import build_env, random_spec


def host_drain_trace(spec):
    """Drain via the host scheduler; returns {wl name: (flavors, cycle)}
    plus the parked set."""
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    admitted = {}
    cycle = 0
    for _ in range(200):
        # quiescent only when every active heap is empty — a cycle that
        # parks its head uncovers the next workload behind it
        if not any(
            pq.pending_active() > 0 for pq in mgr.cluster_queues.values()
        ):
            break
        res = sched.schedule()
        for e in res.admitted:
            psa = e.workload.admission.pod_set_assignments[0]
            admitted[e.workload.name] = (dict(psa.flavors), cycle)
        cycle += 1
    parked = {
        wl.name
        for pq in mgr.cluster_queues.values()
        for wl in list(pq.inadmissible.values()) + list(pq.heap.items())
    }
    return admitted, parked


def device_drain_trace(spec):
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    # collect the backlog in per-CQ heap order
    pending = []
    for cq_name, pq in mgr.cluster_queues.items():
        for wl in pq.snapshot_sorted():
            pending.append((wl, cq_name))
    snapshot = take_snapshot(cache)
    outcome = run_drain(
        snapshot,
        pending,
        cache.flavors,
        timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
    )
    admitted = {
        wl.name: (flavors, cycle) for wl, _, flavors, cycle in outcome.admitted
    }
    parked = {wl.name for wl, _ in outcome.parked}
    return admitted, parked, outcome


class TestDrainParity:
    @pytest.mark.parametrize("seed", range(10))
    def test_randomized(self, seed):
        spec = random_spec(seed, workloads_per_cq=8)
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked

    def test_multi_flavor_spillover(self):
        # second flavor absorbs what the first can't; drain must walk
        # candidates exactly like the host
        spec = {
            "flavors": ["fast", "slow"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": "co",
                    "groups": [
                        {
                            "resources": ["cpu"],
                            "flavors": [
                                ("fast", {"cpu": "4"}, None, None),
                                ("slow", {"cpu": "100"}, None, None),
                            ],
                        }
                    ],
                    "preemption": None,
                }
            ],
            "workloads": [
                {
                    "name": f"w{i}",
                    "queue": "lq-cq",
                    "prio": 0,
                    "t": float(i),
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "3"}}
                    ],
                }
                for i in range(6)
            ],
        }
        host_admitted, _ = host_drain_trace(spec)
        dev_admitted, _, _ = device_drain_trace(spec)
        assert dev_admitted == host_admitted
        # first workload on "fast", rest spill to "slow"
        assert dev_admitted["w0"][0] == {"cpu": "fast"}
        assert dev_admitted["w1"][0] == {"cpu": "slow"}

    def test_cohort_borrowing_contention(self):
        # shared cohort capacity: cross-CQ conflicts resolved per cycle
        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": f"cq-{i}",
                    "cohort": "co",
                    "groups": [
                        {
                            "resources": ["cpu"],
                            "flavors": [("f", {"cpu": "4"}, None, None)],
                        }
                    ],
                    "preemption": None,
                }
                for i in range(4)
            ],
            "workloads": [
                {
                    "name": f"w{i}",
                    "queue": f"lq-cq-{i % 4}",
                    "prio": (i * 7) % 3,
                    "t": float(i),
                    "pod_sets": [
                        {
                            "name": "main",
                            "count": 1,
                            "requests": {"cpu": str(2 + (i % 5))},
                        }
                    ],
                }
                for i in range(20)
            ],
        }
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked
        assert outcome.cycles >= 2


def deep_tree_spec(seed, depth=3, fanout=2, workloads_per_cq=5):
    """Cohort tree of the given depth: root holds the quota, interior
    cohorts are pass-through, CQs at the leaves borrow all the way up."""
    rng = np.random.default_rng(seed)
    cohorts = [
        {
            "name": "root",
            "groups": [
                {"resources": ["cpu"], "flavors": [("f", {"cpu": "40"}, None, None)]}
            ],
        }
    ]
    parents = ["root"]
    for d in range(1, depth):
        nxt = []
        for p in parents:
            for i in range(fanout):
                name = f"{p}-{i}"
                cohorts.append({"name": name, "parent": p})
                nxt.append(name)
        parents = nxt
    cqs = []
    workloads = []
    t = 0.0
    for p in parents:
        name = f"cq-{p}"
        cqs.append(
            {
                "name": name,
                "cohort": p,
                "groups": [
                    {
                        "resources": ["cpu"],
                        "flavors": [("f", {"cpu": "2"}, None, None)],
                    }
                ],
                "preemption": None,
            }
        )
        for wi in range(workloads_per_cq):
            t += 1.0
            workloads.append(
                {
                    "name": f"w-{name}-{wi}",
                    "queue": f"lq-{name}",
                    "prio": int(rng.integers(0, 3)),
                    "t": t,
                    "pod_sets": [
                        {
                            "name": "main",
                            "count": 1,
                            "requests": {"cpu": str(int(rng.integers(1, 6)))},
                        }
                    ],
                }
            )
    return {"flavors": ["f"], "cohorts": cohorts, "cqs": cqs, "workloads": workloads}


class TestDrainDeepTree:
    @pytest.mark.parametrize("seed", range(4))
    def test_depth3_parity(self, seed):
        spec = deep_tree_spec(seed)
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert not outcome.truncated
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked


class TestDrainTruncation:
    def test_max_cycles_routes_unprocessed_to_fallback(self):
        spec = random_spec(3, workloads_per_cq=8)
        sched, mgr, cache, _ = build_env(spec, use_solver=False)
        pending = []
        for cq_name, pq in mgr.cluster_queues.items():
            for wl in pq.snapshot_sorted():
                pending.append((wl, cq_name))
        snapshot = take_snapshot(cache)
        kwargs = dict(
            flavors=cache.flavors,
            timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
        )
        cut = run_drain(snapshot, pending, max_cycles=1, **kwargs)
        assert cut.truncated
        assert cut.cycles == 1
        assert cut.fallback  # unprocessed entries are NOT silently parked
        snapshot2 = take_snapshot(cache)
        full = run_drain(snapshot2, pending, **kwargs)
        assert not full.truncated
        # decided prefixes agree; everything else was surfaced as fallback
        decided = {wl.name for wl, *_ in cut.admitted} | {
            wl.name for wl, _ in cut.parked
        }
        full_admitted = {wl.name for wl, *_ in full.admitted}
        for wl, *_ in cut.admitted:
            assert wl.name in full_admitted
        assert (
            decided | {wl.name for wl, _ in cut.fallback}
            == {wl.name for wl, _ in pending}
        )


# ---------------------------------------------------------------- preemption
def _admit_victim(cache, mgr_clock_t, name, cq_name, flavor, cpu, prio, uid_t):
    """flavor/cpu: either a flavor name + cpu quantity (the single-RG
    shorthand) or {resource: flavor} + {resource: quantity} dicts."""
    from kueue_tpu.core.workload_info import make_admission
    from kueue_tpu.models import Workload, WorkloadConditionType
    from kueue_tpu.models.workload import PodSet

    requests = cpu if isinstance(cpu, dict) else {"cpu": cpu}
    flavors = flavor if isinstance(flavor, dict) else {"cpu": flavor}
    wl = Workload(
        namespace="ns", name=name, queue_name=f"lq-{cq_name}", priority=prio,
        creation_time=uid_t,
        pod_sets=(PodSet.build("main", 1, requests),),
    )
    wl.admission = make_admission(cq_name, {"main": flavors}, wl)
    wl.set_condition(
        WorkloadConditionType.QUOTA_RESERVED, True, reason="QuotaReserved",
        now=uid_t,
    )
    cache.add_or_update_workload(wl)
    return wl


def build_preempt_env(spec):
    """build_env + pre-admitted victims from spec['victims']:
    (name, cq, flavor, cpu, prio, t) tuples. The workloads named in
    spec['resume'] carry a LastAssignment that resumes their cpu walk at
    the second flavor, as a walk an earlier cycle cut off does."""
    from kueue_tpu.core.flavor_assigner import AssignmentState

    sched, mgr, cache, workloads = build_env(spec, use_solver=False)
    for name, cq_name, flavor, cpu, prio, t in spec.get("victims", []):
        _admit_victim(cache, None, name, cq_name, flavor, cpu, prio, t)
    for name in spec.get("resume", ()):
        workloads[name].last_assignment = AssignmentState(
            last_tried_flavor_idx=[{"cpu": 0}], cluster_queue_generation=2**40
        )
    return sched, mgr, cache, workloads


def host_preempt_drain_trace(spec):
    """Host truth: scheduler cycles with evictions applied between
    cycles (the reconciler's stop/delete round-trip compressed to the
    cycle boundary), to quiescence."""
    sched, mgr, cache, _ = build_preempt_env(spec)
    admitted, evicted = {}, set()
    for _ in range(300):
        progressed = False
        if any(pq.pending_active() > 0 for pq in mgr.cluster_queues.values()):
            progressed = True  # active heads: the cycle itself is progress
        res = sched.schedule()
        for e in res.admitted:
            psas = e.workload.admission.pod_set_assignments
            if len(psas) == 1:
                admitted[e.workload.name] = dict(psas[0].flavors)
            else:
                admitted[e.workload.name] = {
                    psa.name: dict(psa.flavors) for psa in psas
                }
        victims = []
        for e in res.preempting:
            for t in e.preemption_targets:
                victims.append(t.workload.workload)
        for wl in victims:
            if wl.name in evicted:
                continue
            evicted.add(wl.name)
            cq_name = wl.admission.cluster_queue
            cache.delete_workload(wl)
            mgr.queue_associated_inadmissible_workloads_after(cq_name)
            progressed = True
        if not progressed:
            break
    parked = {
        wl.name
        for pq in mgr.cluster_queues.values()
        for wl in list(pq.inadmissible.values()) + list(pq.heap.items())
    }
    return admitted, evicted, parked


def device_preempt_drain_trace(spec, **kw):
    from kueue_tpu.core.drain import run_drain_preempt

    sched, mgr, cache, _ = build_preempt_env(spec)
    pending = []
    for cq_name, pq in mgr.cluster_queues.items():
        for wl in pq.snapshot_sorted():
            pending.append((wl, cq_name))
    snapshot = take_snapshot(cache)
    outcome = run_drain_preempt(
        snapshot,
        pending,
        cache.flavors,
        timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
        **kw,
    )
    admitted = {wl.name: flavors for wl, _, flavors, _ in outcome.admitted}
    evicted = {wl.name for wl, _, _ in outcome.preempted}
    parked = {wl.name for wl, _ in outcome.parked}
    return admitted, evicted, parked, outcome


def preempt_spec(seed, n_cohorts=2, cqs_per_cohort=3, victims_per_cq=4,
                 workloads_per_cq=4):
    """Random scenario inside the device preemption-drain scope:
    within-CQ preemption, reclaimWithinCohort=Never, single RG."""
    from kueue_tpu.models.cluster_queue import Preemption
    from kueue_tpu.models.constants import PreemptionPolicy

    rng = np.random.default_rng(seed)
    flavors = ["fl-0", "fl-1"]
    cqs, workloads, victims = [], [], []
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            cohort = f"cohort-{ci}" if rng.random() < 0.7 else None
            k = int(rng.integers(1, 3))
            fls = []
            for f in flavors[:k]:
                bl = (
                    str(int(rng.integers(0, 8)))
                    if cohort is not None and rng.random() < 0.4
                    else None
                )
                fls.append((f, {"cpu": str(int(rng.integers(6, 16)))}, bl, None))
            # index the list (rng.choice would coerce enums to numpy
            # strings and corrupt the policies)
            policy_opts = [
                PreemptionPolicy.NEVER,
                PreemptionPolicy.LOWER_PRIORITY,
                PreemptionPolicy.LOWER_PRIORITY,
                PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY,
            ]
            policy = policy_opts[int(rng.integers(0, len(policy_opts)))]
            cqs.append(
                {
                    "name": name,
                    "cohort": cohort,
                    "groups": [{"resources": ["cpu"], "flavors": fls}],
                    "preemption": Preemption(within_cluster_queue=policy),
                }
            )
            for vi in range(int(rng.integers(0, victims_per_cq + 1))):
                t += 1.0
                victims.append(
                    (
                        f"victim-{ci}-{qi}-{vi}", name,
                        fls[int(rng.integers(0, len(fls)))][0],
                        str(int(rng.integers(1, 5))),
                        int(rng.integers(0, 3)) * 10, t,
                    )
                )
            for wi in range(workloads_per_cq):
                t += 1.0
                workloads.append(
                    {
                        "name": f"wl-{ci}-{qi}-{wi}",
                        "queue": f"lq-{name}",
                        "prio": int(rng.integers(0, 4)) * 10,
                        "t": t,
                        "pod_sets": [
                            {
                                "name": "main",
                                "count": int(rng.integers(1, 3)),
                                "requests": {"cpu": str(int(rng.integers(1, 7)))},
                            }
                        ],
                    }
                )
    return {
        "flavors": flavors, "cqs": cqs, "workloads": workloads,
        "victims": victims,
    }


class TestPreemptDrainParity:
    def test_basic_preempt_then_admit(self):
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import PreemptionPolicy

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": None,
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "10"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
                    ),
                }
            ],
            "workloads": [
                {
                    "name": "attacker", "queue": "lq-cq", "prio": 100, "t": 50.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "8"}}
                    ],
                }
            ],
            "victims": [
                ("v0", "cq", "f", "4", 0, 1.0),
                ("v1", "cq", "f", "4", 10, 2.0),
            ],
        }
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted == {"attacker": {"cpu": "f"}}
        assert evicted == h_evicted
        assert parked == h_parked == set()
        assert not outcome.fallback and not outcome.truncated

    def test_minimal_victim_set(self):
        """Fill-back keeps unnecessary victims admitted: only enough
        victims to fit the head are evicted."""
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import PreemptionPolicy

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": None,
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "12"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
                    ),
                }
            ],
            "workloads": [
                {
                    "name": "attacker", "queue": "lq-cq", "prio": 100, "t": 50.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "4"}}
                    ],
                }
            ],
            "victims": [
                ("v-low", "cq", "f", "4", 0, 1.0),
                ("v-mid", "cq", "f", "4", 10, 2.0),
                ("v-high", "cq", "f", "4", 20, 3.0),
            ],
        }
        admitted, evicted, parked, _ = device_preempt_drain_trace(spec)
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted
        assert evicted == h_evicted == {"v-low"}
        assert parked == h_parked

    def test_never_policy_parks(self):
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import PreemptionPolicy

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": None,
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "10"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        within_cluster_queue=PreemptionPolicy.NEVER
                    ),
                }
            ],
            "workloads": [
                {
                    "name": "blocked", "queue": "lq-cq", "prio": 100, "t": 50.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "8"}}
                    ],
                }
            ],
            "victims": [("v0", "cq", "f", "8", 0, 1.0)],
        }
        admitted, evicted, parked, _ = device_preempt_drain_trace(spec)
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted == {}
        assert evicted == h_evicted == set()
        assert parked == h_parked == {"blocked"}

    def test_cohort_reclaim_stays_in_kernel(self):
        # A reclaimWithinCohort CQ is IN the device scope (round 4):
        # the head preempts the lower-priority same-CQ victim in-kernel
        # instead of falling back to the cycle loop.
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import (
            PreemptionPolicy,
            ReclaimWithinCohortPolicy,
        )

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "10"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                        reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
                    ),
                }
            ],
            "workloads": [
                {
                    "name": "w", "queue": "lq-cq", "prio": 100, "t": 50.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "8"}}
                    ],
                }
            ],
            "victims": [("v0", "cq", "f", "8", 0, 1.0)],
        }
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted
        assert evicted == h_evicted == {"v0"}
        assert parked == h_parked

    # tier-1 runtime headroom (ISSUE 14): 4 deterministic seeds stay
    # tier-1, the remainder of the historical sweep rides @slow
    @pytest.mark.parametrize("seed", range(4))
    def test_randomized(self, seed):
        spec = preempt_spec(seed)
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert admitted == h_admitted
        assert evicted == h_evicted
        assert parked == h_parked

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(4, 16))
    def test_randomized_wide(self, seed):
        self.test_randomized(seed)

    @pytest.mark.parametrize("seed", range(4))
    def test_resumed_first_heads(self, seed):
        # each ClusterQueue's first head resumes its walk at the second
        # flavor; where that walk ends without a fit it restarts at the
        # first flavor, on the host and in the drain alike
        spec = preempt_spec(seed)
        spec["resume"] = [
            min((-w["prio"], w["t"], w["name"]) for w in spec["workloads"]
                if w["queue"] == f"lq-{cq['name']}")[2]
            for cq in spec["cqs"]
            if len(cq["groups"][0]["flavors"]) > 1
        ]
        assert spec["resume"]
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert outcome.resumed == len(spec["resume"])
        assert admitted == h_admitted
        assert evicted == h_evicted
        assert parked == h_parked

    def test_reactivated_head_preempts_drain_admitted_same_cq(self):
        # Within-CQ-only cohort (no reclaim anywhere): w-hi parks (its
        # only candidate outranks it), the lower-priority w-lo admits
        # behind it, and an eviction elsewhere in the cohort reactivates
        # w-hi — which must then preempt the DRAIN-ADMITTED w-lo. The
        # part-B candidate pool must exist even without cohort reclaim
        # (regression: slots were gated on reclaim being enabled).
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import PreemptionPolicy

        prem = Preemption(within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY)
        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "8"}, None, None)]}
                    ],
                    "preemption": prem,
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "12"}, None, None)]}
                    ],
                    "preemption": prem,
                },
            ],
            "workloads": [
                {
                    "name": "w-blk", "queue": "lq-cq-b", "prio": 60, "t": 1.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "21"}}
                    ],
                },
                {
                    "name": "w-hi", "queue": "lq-cq-a", "prio": 50, "t": 2.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "8"}}
                    ],
                },
                {
                    "name": "w-lo", "queue": "lq-cq-a", "prio": 0, "t": 3.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "4"}}
                    ],
                },
                {
                    "name": "w-e", "queue": "lq-cq-b", "prio": 50, "t": 6.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "6"}}
                    ],
                },
            ],
            "victims": [
                ("v0", "cq-a", "f", "8", 60, 1.0),
                ("v2", "cq-b", "f", "5", 0, 2.0),
                ("v2b", "cq-b", "f", "2", 70, 3.0),
            ],
        }
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert admitted == h_admitted
        assert evicted == h_evicted
        assert parked == h_parked
        assert "w-lo" in evicted and "w-hi" in admitted


def cohort_reclaim_spec(seed, n_cohorts=2, cqs_per_cohort=3,
                        victims_per_cq=3, workloads_per_cq=3):
    """Random cross-CQ contention: cohorts whose members borrow (some
    victims admitted above nominal), mixed withinClusterQueue /
    reclaimWithinCohort / borrowWithinCohort policies with priority
    thresholds — the preemption scope the round-3 drain routed to host
    fallback (preemption.go:480-524, :194-204)."""
    from kueue_tpu.models.cluster_queue import BorrowWithinCohort, Preemption
    from kueue_tpu.models.constants import (
        BorrowWithinCohortPolicy,
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
    )

    rng = np.random.default_rng(seed + 31000)
    flavors = ["fl-0", "fl-1"]
    cqs, workloads, victims = [], [], []
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            k = int(rng.integers(1, 3))
            fls = []
            for f in flavors[:k]:
                bl = (
                    str(int(rng.integers(0, 10)))
                    if rng.random() < 0.5
                    else None
                )
                fls.append((f, {"cpu": str(int(rng.integers(4, 12)))}, bl, None))
            # index the lists (rng.choice would coerce enums to numpy
            # strings and corrupt the policies)
            wcq_opts = [
                PreemptionPolicy.NEVER,
                PreemptionPolicy.LOWER_PRIORITY,
                PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY,
            ]
            wcq = wcq_opts[int(rng.integers(0, len(wcq_opts)))]
            reclaim_opts = [
                ReclaimWithinCohortPolicy.NEVER,
                ReclaimWithinCohortPolicy.LOWER_PRIORITY,
                ReclaimWithinCohortPolicy.ANY,
                ReclaimWithinCohortPolicy.ANY,
            ]
            reclaim = reclaim_opts[int(rng.integers(0, len(reclaim_opts)))]
            if rng.random() < 0.4 and reclaim != ReclaimWithinCohortPolicy.NEVER:
                bwc = BorrowWithinCohort(
                    policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                    max_priority_threshold=(
                        int(rng.integers(0, 4)) * 10
                        if rng.random() < 0.7
                        else None
                    ),
                )
            else:
                bwc = BorrowWithinCohort()
            cqs.append(
                {
                    "name": name,
                    "cohort": f"cohort-{ci}",
                    "groups": [{"resources": ["cpu"], "flavors": fls}],
                    "preemption": Preemption(
                        within_cluster_queue=wcq,
                        reclaim_within_cohort=reclaim,
                        borrow_within_cohort=bwc,
                    ),
                }
            )
            # victims sized to overshoot nominal sometimes: the CQ then
            # borrows from the cohort, making its workloads reclaimable
            for vi in range(int(rng.integers(0, victims_per_cq + 1))):
                t += 1.0
                victims.append(
                    (
                        f"victim-{ci}-{qi}-{vi}", name,
                        fls[int(rng.integers(0, len(fls)))][0],
                        str(int(rng.integers(1, 9))),
                        int(rng.integers(0, 3)) * 10, t,
                    )
                )
            for wi in range(workloads_per_cq):
                t += 1.0
                workloads.append(
                    {
                        "name": f"wl-{ci}-{qi}-{wi}",
                        "queue": f"lq-{name}",
                        "prio": int(rng.integers(0, 5)) * 10,
                        "t": t,
                        "pod_sets": [
                            {
                                "name": "main",
                                "count": int(rng.integers(1, 3)),
                                "requests": {"cpu": str(int(rng.integers(1, 6)))},
                            }
                        ],
                    }
                )
    return {
        "flavors": flavors, "cqs": cqs, "workloads": workloads,
        "victims": victims,
    }


class TestPreemptDrainCohortReclaim:
    def test_cross_cq_reclaim_releases_borrowed(self):
        # cq-a borrows above nominal; cq-b's head reclaims from it
        # (reclaimWithinCohort=Any) without touching cq-b's own victims
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import (
            PreemptionPolicy,
            ReclaimWithinCohortPolicy,
        )

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "4"}, None, None)]}
                    ],
                    "preemption": Preemption(),
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "6"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
                    ),
                },
            ],
            "workloads": [
                {
                    "name": "wb", "queue": "lq-cq-b", "prio": 0, "t": 50.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "6"}}
                    ],
                }
            ],
            # cq-a holds 8 > nominal 4: borrowing 4 from the cohort
            "victims": [
                ("va-0", "cq-a", "f", "4", 50, 1.0),
                ("va-1", "cq-a", "f", "4", 50, 2.0),
            ],
        }
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted
        assert evicted == h_evicted
        # reclaim succeeds even though the victims have HIGHER priority
        # (reclaimWithinCohort=Any has no priority constraint)
        assert "wb" in admitted and len(evicted) == 1
        assert parked == h_parked

    def test_lower_priority_reclaim_respects_priority(self):
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import ReclaimWithinCohortPolicy

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "4"}, None, None)]}
                    ],
                    "preemption": Preemption(),
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "6"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        reclaim_within_cohort=ReclaimWithinCohortPolicy.LOWER_PRIORITY,
                    ),
                },
            ],
            "workloads": [
                {
                    "name": "wb", "queue": "lq-cq-b", "prio": 10, "t": 50.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "6"}}
                    ],
                }
            ],
            # borrowing victims at prio 50 >= 10: NOT reclaimable
            "victims": [
                ("va-0", "cq-a", "f", "4", 50, 1.0),
                ("va-1", "cq-a", "f", "4", 50, 2.0),
            ],
        }
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted == {}
        assert evicted == h_evicted == set()
        assert parked == h_parked == {"wb"}

    def test_admitted_entry_becomes_reclaim_candidate(self):
        # cq-a's entry admits first (borrowing into the cohort), then
        # cq-b's later head reclaims it — the part-B dynamic-victim
        # flow: the workload ends BOTH admitted and evicted, exactly as
        # the host cycle loop decides it
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import (
            PreemptionPolicy,
            ReclaimWithinCohortPolicy,
        )

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "2"}, None, None)]}
                    ],
                    "preemption": Preemption(),
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "6"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
                    ),
                },
            ],
            "workloads": [
                # admitted in cycle 1, borrowing 4 above cq-a's nominal
                {
                    "name": "wa", "queue": "lq-cq-a", "prio": 50, "t": 10.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "6"}}
                    ],
                },
                # keeps wb off cycle 1: NoFit (100 > total), parks
                {
                    "name": "w-big", "queue": "lq-cq-b", "prio": 90, "t": 5.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "100"}}
                    ],
                },
                # cq-b's cycle-2 head needs its nominal back -> reclaims
                # the DRAIN-ADMITTED wa (borrowing by then)
                {
                    "name": "wb", "queue": "lq-cq-b", "prio": 0, "t": 20.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "6"}}
                    ],
                },
            ],
            "victims": [],
        }
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        assert admitted == h_admitted
        assert evicted == h_evicted
        assert parked == h_parked
        assert "wa" in admitted and "wa" in evicted and "wb" in admitted

    @pytest.mark.parametrize("seed", range(24))
    def test_randomized(self, seed):
        spec = cohort_reclaim_spec(seed)
        h_admitted, h_evicted, h_parked = host_preempt_drain_trace(spec)
        admitted, evicted, parked, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert admitted == h_admitted
        assert evicted == h_evicted
        assert parked == h_parked


def deep_lending_spec(seed, depth=3, workloads_per_cq=6):
    """Nested cohort forest (depth>2) with lending AND borrowing limits
    at every level — the drain must reproduce the host's quota walk
    through interior nodes exactly."""
    rng = np.random.default_rng(seed + 5000)
    flavors = ["fl-0", "fl-1"]
    cohorts = [{"name": "root", "groups": []}]
    parents = ["root"]
    for d in range(1, depth - 1):
        new_parents = []
        for pi, parent in enumerate(parents):
            for k in range(2):
                name = f"co-{d}-{pi}-{k}"
                groups = []
                if rng.random() < 0.5:
                    # quota at interior nodes (hierarchical cohorts)
                    groups = [
                        {
                            "resources": ["cpu"],
                            "flavors": [
                                ("fl-0", {"cpu": str(int(rng.integers(4, 10)))}, None, None)
                            ],
                        }
                    ]
                cohorts.append({"name": name, "parent": parent, "groups": groups})
                new_parents.append(name)
        parents = new_parents
    cqs, workloads = [], []
    t = 0.0
    for pi, parent in enumerate(parents):
        for qi in range(2):
            name = f"cq-{pi}-{qi}"
            fls = []
            for f in flavors[: int(rng.integers(1, 3))]:
                bl = str(int(rng.integers(0, 8))) if rng.random() < 0.6 else None
                ll = str(int(rng.integers(0, 5))) if rng.random() < 0.6 else None
                fls.append((f, {"cpu": str(int(rng.integers(4, 12)))}, bl, ll))
            cqs.append(
                {
                    "name": name,
                    "cohort": parent,
                    "groups": [{"resources": ["cpu"], "flavors": fls}],
                    "preemption": None,
                }
            )
            for wi in range(workloads_per_cq):
                t += 1.0
                workloads.append(
                    {
                        "name": f"wl-{pi}-{qi}-{wi}",
                        "queue": f"lq-{name}",
                        "prio": int(rng.integers(0, 4)) * 10,
                        "t": t,
                        "pod_sets": [
                            {
                                "name": "main",
                                "count": int(rng.integers(1, 4)),
                                "requests": {"cpu": str(int(rng.integers(1, 6)))},
                            }
                        ],
                    }
                )
    return {
        "flavors": flavors, "cohorts": cohorts, "cqs": cqs,
        "workloads": workloads,
    }


class TestDrainParityDeepTrees:
    """VERDICT weak #6: lending-limit and depth>2 drain parity."""

    @pytest.mark.parametrize("seed", range(8))
    def test_deep_tree_with_lending_limits(self, seed):
        spec = deep_lending_spec(seed)
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked

    @pytest.mark.parametrize("seed", range(8, 12))
    def test_depth_four(self, seed):
        spec = deep_lending_spec(seed, depth=4, workloads_per_cq=4)
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked


def multi_rg_spec(seed, n_cohorts=2, cqs_per_cohort=3, workloads_per_cq=6):
    """Backlogs whose CQs cover TWO resource groups ((cpu,memory) and
    gpu): candidates are cartesian products of per-group flavor walks,
    exercising the drain's per-group cursor vectors."""
    import numpy as np

    rng = np.random.default_rng(seed)
    flavors = ["fa", "fb", "ga", "gb"]
    cqs, workloads = [], []
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            kf = int(rng.integers(1, 3))  # 1-2 cpu/mem flavors
            kg = int(rng.integers(1, 3))  # 1-2 gpu flavors
            cpu_flavors = [
                (f, {"cpu": str(int(rng.integers(6, 16))),
                     "memory": f"{int(rng.integers(8, 32))}Gi"},
                 str(int(rng.integers(0, 8))) if rng.random() < 0.4 else None,
                 None)
                for f in ["fa", "fb"][:kf]
            ]
            gpu_flavors = [
                (f, {"gpu": str(int(rng.integers(2, 8)))},
                 str(int(rng.integers(0, 4))) if rng.random() < 0.3 else None,
                 None)
                for f in ["ga", "gb"][:kg]
            ]
            cqs.append({
                "name": name,
                "cohort": f"cohort-{ci}",
                "groups": [
                    {"resources": ["cpu", "memory"], "flavors": cpu_flavors},
                    {"resources": ["gpu"], "flavors": gpu_flavors},
                ],
                "preemption": None,
            })
            for wi in range(workloads_per_cq):
                t += 1.0
                requests = {"cpu": str(int(rng.integers(1, 5))),
                            "memory": f"{int(rng.integers(1, 8))}Gi"}
                if rng.random() < 0.7:  # most workloads touch both RGs
                    requests["gpu"] = str(int(rng.integers(1, 3)))
                workloads.append({
                    "name": f"wl-{ci}-{qi}-{wi}",
                    "queue": f"lq-{name}",
                    "prio": int(rng.integers(0, 4)) * 10,
                    "t": t,
                    "pod_sets": [{
                        "name": "main",
                        "count": int(rng.integers(1, 3)),
                        "requests": requests,
                    }],
                })
    return {"flavors": flavors, "cqs": cqs, "workloads": workloads}


class TestDrainMultiResourceGroup:
    """Multi-RG backlogs run ON DEVICE: the per-group cursor vectors
    must reproduce the sequential scheduler's per-group LastAssignment
    resume exactly (previously these heads were routed to fallback)."""

    @pytest.mark.parametrize("seed", range(16))
    def test_randomized_parity(self, seed):
        spec = multi_rg_spec(seed)
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        # multi-RG heads must actually run on the device now
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked
        assert host_admitted  # non-trivial scenario

    def test_cartesian_cursor_resume_after_conflict(self):
        # Two CQs in one cohort contend for borrowed gpu quota: the
        # loser's retry must resume its (cpu x gpu) cartesian walk at
        # the per-group cursors, not at combo k+1.
        spec = {
            "flavors": ["fa", "fb", "ga", "gb"],
            "cqs": [
                {
                    "name": f"cq-{x}",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [
                            ("fa", {"cpu": "4"}, "4", None),
                            ("fb", {"cpu": "4"}, "4", None),
                        ]},
                        {"resources": ["gpu"], "flavors": [
                            ("ga", {"gpu": "1"}, "1", None),
                            ("gb", {"gpu": "2"}, "2", None),
                        ]},
                    ],
                    "preemption": None,
                }
                for x in ("a", "b")
            ],
            "workloads": [
                {
                    "name": f"w-{x}-{i}",
                    "queue": f"lq-cq-{x}",
                    "prio": 0,
                    "t": float(i + (0 if x == "a" else 10)),
                    "pod_sets": [{
                        "name": "main", "count": 1,
                        "requests": {"cpu": "3", "gpu": "2"},
                    }],
                }
                for x in ("a", "b")
                for i in range(3)
            ],
        }
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked


def multi_rg_preempt_spec(seed, n_cqs=4, victims_per_cq=3, workloads_per_cq=4):
    """Multi-resource-group scenarios INSIDE the preempt-drain scope
    (withinClusterQueue=LowerPriority, no cohort): saturated CQs whose
    victims and pending workloads both span two resource groups, so the
    device's per-group cursors, reclaim-oracle emulation, and victim
    search run together."""
    from kueue_tpu.models.cluster_queue import Preemption
    from kueue_tpu.models.constants import PreemptionPolicy

    rng = np.random.default_rng(seed)
    cqs, workloads, victims = [], [], []
    t = 0.0
    for qi in range(n_cqs):
        name = f"cq-{qi}"
        kf = int(rng.integers(1, 3))
        kg = int(rng.integers(1, 3))
        cpu_flavors = [
            (f, {"cpu": str(int(rng.integers(8, 16)))}, None, None)
            for f in ["fa", "fb"][:kf]
        ]
        gpu_flavors = [
            (f, {"gpu": str(int(rng.integers(4, 8)))}, None, None)
            for f in ["ga", "gb"][:kg]
        ]
        cqs.append({
            "name": name,
            "cohort": None,
            "groups": [
                {"resources": ["cpu"], "flavors": cpu_flavors},
                {"resources": ["gpu"], "flavors": gpu_flavors},
            ],
            "preemption": Preemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
            ),
        })
        for vi in range(victims_per_cq):
            t += 1.0
            requests = {"cpu": str(int(rng.integers(2, 6)))}
            flavors = {"cpu": rng.choice(["fa", "fb"][:kf])}
            if rng.random() < 0.8:
                requests["gpu"] = str(int(rng.integers(1, 3)))
                flavors["gpu"] = rng.choice(["ga", "gb"][:kg])
            victims.append((f"v-{qi}-{vi}", name, flavors, requests, 0, t))
        for wi in range(workloads_per_cq):
            t += 1.0
            requests = {"cpu": str(int(rng.integers(2, 6)))}
            if rng.random() < 0.8:
                requests["gpu"] = str(int(rng.integers(1, 3)))
            workloads.append({
                "name": f"wl-{qi}-{wi}",
                "queue": f"lq-{name}",
                "prio": int(rng.integers(1, 4)) * 10,
                "t": t,
                "pod_sets": [{
                    "name": "main", "count": 1, "requests": requests,
                }],
            })
    return {
        "flavors": ["fa", "fb", "ga", "gb"],
        "cqs": cqs,
        "workloads": workloads,
        "victims": victims,
    }


class TestPreemptDrainMultiResourceGroup:
    """Multi-RG preemption drains on device: per-group cursor vectors +
    reclaim-oracle emulation + in-kernel victim search must match the
    sequential host scheduler with evictions applied at cycle
    boundaries."""

    @pytest.mark.parametrize("seed", range(12))
    def test_randomized_parity(self, seed):
        spec = multi_rg_preempt_spec(seed)
        ha, he, hp = host_preempt_drain_trace(spec)
        da, de, dp, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert da == ha
        assert de == he
        assert dp == hp
        assert ha and he  # scenario admits and evicts


def fungibility_spec(seed, n_cohorts=2, cqs_per_cohort=3, workloads_per_cq=7):
    """Backlogs over CQs with randomized flavorFungibility policies
    (whenCanBorrow Borrow|TryNextFlavor x whenCanPreempt
    TryNextFlavor|Preempt): the drain's policy-aware group walk must
    stop/continue exactly like the host's _should_try_next_flavor."""
    from kueue_tpu.models import FlavorFungibility
    from kueue_tpu.models.constants import FlavorFungibilityPolicy as FFP

    rng = np.random.default_rng(seed)
    flavors = [f"fl-{i}" for i in range(3)]
    cqs, workloads = [], []
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            k = int(rng.integers(2, 4))
            fls = [
                (f, {"cpu": str(int(rng.integers(4, 14)))},
                 str(int(rng.integers(0, 10))) if rng.random() < 0.5 else None,
                 None)
                for f in flavors[:k]
            ]
            # index, not rng.choice: numpy truncates str-enum members
            # to fixed-width unicode scalars that equal neither member
            fung = FlavorFungibility(
                when_can_borrow=[FFP.BORROW, FFP.TRY_NEXT_FLAVOR][
                    int(rng.integers(0, 2))
                ],
                when_can_preempt=[FFP.TRY_NEXT_FLAVOR, FFP.PREEMPT][
                    int(rng.integers(0, 2))
                ],
            )
            cqs.append({
                "name": name,
                "cohort": f"cohort-{ci}",
                "groups": [{"resources": ["cpu"], "flavors": fls}],
                "preemption": None,
                "fungibility": fung,
            })
            for wi in range(workloads_per_cq):
                t += 1.0
                workloads.append({
                    "name": f"wl-{ci}-{qi}-{wi}",
                    "queue": f"lq-{name}",
                    "prio": int(rng.integers(0, 4)) * 10,
                    "t": t,
                    "pod_sets": [{
                        "name": "main",
                        "count": int(rng.integers(1, 4)),
                        "requests": {"cpu": str(int(rng.integers(1, 6)))},
                    }],
                })
    return {"flavors": flavors, "cqs": cqs, "workloads": workloads}


class TestDrainFungibilityPolicies:
    """Non-default flavorFungibility on device (previously host-only):
    TryNextFlavor borrowing (prefer a later non-borrowing flavor, fall
    back to the first borrowing fit) and Preempt stopping."""

    @pytest.mark.parametrize("seed", range(16))
    def test_randomized_parity(self, seed):
        spec = fungibility_spec(seed)
        host_admitted, host_parked = host_drain_trace(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked
        assert host_admitted

    def test_try_next_flavor_prefers_non_borrowing(self):
        # first flavor only fits by borrowing; whenCanBorrow=
        # TryNextFlavor must walk on and take the non-borrowing second
        from kueue_tpu.models import FlavorFungibility
        from kueue_tpu.models.constants import FlavorFungibilityPolicy as FFP

        spec = {
            "flavors": ["small", "big"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [{"resources": ["cpu"], "flavors": [
                        ("small", {"cpu": "2"}, "10", None),
                        ("big", {"cpu": "10"}, None, None),
                    ]}],
                    "preemption": None,
                    "fungibility": FlavorFungibility(
                        when_can_borrow=FFP.TRY_NEXT_FLAVOR,
                        when_can_preempt=FFP.TRY_NEXT_FLAVOR,
                    ),
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [{"resources": ["cpu"], "flavors": [
                        ("small", {"cpu": "10"}, None, None),
                    ]}],
                    "preemption": None,
                },
            ],
            "workloads": [
                {
                    "name": "w0", "queue": "lq-cq-a", "prio": 0, "t": 0.0,
                    "pod_sets": [{"name": "main", "count": 1,
                                  "requests": {"cpu": "4"}}],
                }
            ],
        }
        host_admitted, _ = host_drain_trace(spec)
        dev_admitted, _, outcome = device_drain_trace(spec)
        assert not outcome.fallback
        assert dev_admitted == host_admitted
        assert dev_admitted["w0"][0] == {"cpu": "big"}


class TestPreemptDrainFungibility:
    """Non-default fungibility through solve_drain_preempt: the policy
    bits must reach the preempt kernel's group walk alongside the
    victim-aware reclaim upgrade."""

    @pytest.mark.parametrize("seed", range(8))
    def test_randomized_parity(self, seed):
        from kueue_tpu.models import FlavorFungibility
        from kueue_tpu.models.constants import FlavorFungibilityPolicy as FFP

        rng = np.random.default_rng(1000 + seed)
        spec = multi_rg_preempt_spec(seed)
        for cq_spec in spec["cqs"]:
            cq_spec["fungibility"] = FlavorFungibility(
                when_can_borrow=[FFP.BORROW, FFP.TRY_NEXT_FLAVOR][
                    int(rng.integers(0, 2))
                ],
                when_can_preempt=[FFP.TRY_NEXT_FLAVOR, FFP.PREEMPT][
                    int(rng.integers(0, 2))
                ],
            )
        ha, he, hp = host_preempt_drain_trace(spec)
        da, de, dp, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert da == ha
        assert de == he
        assert dp == hp


def host_drain_trace_multi(spec):
    """Host truth with per-podset flavor maps: single-podset workloads
    keep the flat {resource: flavor}; multi-podset ones nest by podset
    name — the same shapes the device outcome mapping produces.

    Returns (admitted, parked, undecided): at quiescence heaps are
    empty, so heap leftovers exist only when the cycle cap was hit —
    a PendingFlavors retry loop that never converges (the reference's
    immediate-requeue machinery spins identically,
    cluster_queue.go:231); those entries are no-decision, which the
    device drain reports as fallback after ITS cycle cap."""
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    admitted = {}
    cycle = 0
    for _ in range(300):
        if not any(
            pq.pending_active() > 0 for pq in mgr.cluster_queues.values()
        ):
            break
        res = sched.schedule()
        for e in res.admitted:
            psas = e.workload.admission.pod_set_assignments
            if len(psas) == 1:
                fl = dict(psas[0].flavors)
            else:
                fl = {psa.name: dict(psa.flavors) for psa in psas}
            admitted[e.workload.name] = (fl, cycle)
        cycle += 1
    parked = {
        wl.name
        for pq in mgr.cluster_queues.values()
        for wl in pq.inadmissible.values()
    }
    undecided = {
        wl.name
        for pq in mgr.cluster_queues.values()
        for wl in pq.heap.items()
    }
    return admitted, parked, undecided


def multi_podset_spec(seed, n_cohorts=2, cqs_per_cohort=3, workloads_per_cq=5):
    """Driver+worker style workloads: 2-3 podsets per workload sharing
    (flavor, resource) cells, so podset nominations couple through
    assignment_usage exactly like the host's sequential walk."""
    rng = np.random.default_rng(seed)
    flavors = ["fa", "fb"]
    cqs, workloads = [], []
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            k = int(rng.integers(1, 3))
            fls = [
                (f, {"cpu": str(int(rng.integers(8, 20)))},
                 str(int(rng.integers(0, 10))) if rng.random() < 0.4 else None,
                 None)
                for f in flavors[:k]
            ]
            cqs.append({
                "name": name,
                "cohort": f"cohort-{ci}",
                "groups": [{"resources": ["cpu"], "flavors": fls}],
                "preemption": None,
            })
            for wi in range(workloads_per_cq):
                t += 1.0
                npods = int(rng.integers(1, 4))
                pod_sets = [
                    {
                        "name": ["driver", "worker", "aux"][pp],
                        "count": int(rng.integers(1, 3)),
                        "requests": {"cpu": str(int(rng.integers(1, 5)))},
                    }
                    for pp in range(npods)
                ]
                workloads.append({
                    "name": f"wl-{ci}-{qi}-{wi}",
                    "queue": f"lq-{name}",
                    "prio": int(rng.integers(0, 4)) * 10,
                    "t": t,
                    "pod_sets": pod_sets,
                })
    return {"flavors": flavors, "cqs": cqs, "workloads": workloads}


class TestDrainMultiPodset:
    """Multi-podset workloads on the device drain: podsets nominate
    sequentially with assignment_usage coupling at shared cells
    (previously every multi-podset head routed to the host fallback)."""

    @pytest.mark.parametrize("seed", range(16))
    def test_randomized_parity(self, seed):
        spec = multi_podset_spec(seed)
        host_admitted, host_parked, undecided = host_drain_trace_multi(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked
        # non-converging PendingFlavors retry loops spin forever on the
        # host (the reference's immediate-requeue does the same until
        # external events change state); the drain freezes the stuck
        # queue — the head keeps nominating so its reservations still
        # shape other queues — and reports its entries as no-decision
        assert {wl.name for wl, _ in outcome.fallback} == undecided
        assert host_admitted

    def test_podsets_share_cells(self):
        # driver 3cpu + workers 2x2cpu = 7 > fa's 8? fits; the SECOND
        # workload's driver alone would fit fa but the sum must spill:
        # assignment_usage coupling decides flavors per podset
        spec = {
            "flavors": ["fa", "fb"],
            "cqs": [{
                "name": "cq",
                "cohort": "co",
                "groups": [{"resources": ["cpu"], "flavors": [
                    ("fa", {"cpu": "8"}, None, None),
                    ("fb", {"cpu": "100"}, None, None),
                ]}],
                "preemption": None,
            }],
            "workloads": [
                {
                    "name": f"w{i}",
                    "queue": "lq-cq",
                    "prio": 0,
                    "t": float(i),
                    "pod_sets": [
                        {"name": "driver", "count": 1,
                         "requests": {"cpu": "3"}},
                        {"name": "worker", "count": 2,
                         "requests": {"cpu": "2"}},
                    ],
                }
                for i in range(3)
            ],
        }
        host_admitted, host_parked, undecided = host_drain_trace_multi(spec)
        dev_admitted, dev_parked, outcome = device_drain_trace(spec)
        assert not outcome.fallback and not undecided
        assert dev_admitted == host_admitted
        assert dev_parked == host_parked


class TestPreemptDrainMultiPodset:
    @pytest.mark.parametrize("seed", range(8))
    def test_randomized_parity(self, seed):
        # multi-podset pending workloads over saturated single-podset
        # victims (within-CQ preemption)
        rng = np.random.default_rng(seed)
        spec = multi_rg_preempt_spec(seed, n_cqs=3)
        for w in spec["workloads"]:
            if rng.random() < 0.6:
                w["pod_sets"].append({
                    "name": "worker",
                    "count": int(rng.integers(1, 3)),
                    "requests": {"cpu": str(int(rng.integers(1, 4)))},
                })
        ha, he, hp = host_preempt_drain_trace(spec)
        da, de, dp, outcome = device_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert da == ha
        assert de == he
        assert dp == hp


def host_fair_drain_trace(spec):
    """Host truth under fair-sharing admission ordering: scheduler
    cycles with fair_sharing enabled, to quiescence."""
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    sched.fair_sharing = True
    admitted = {}
    cycle = 0
    for _ in range(200):
        if not any(
            pq.pending_active() > 0 for pq in mgr.cluster_queues.values()
        ):
            break
        res = sched.schedule()
        for e in res.admitted:
            psa = e.workload.admission.pod_set_assignments[0]
            admitted[e.workload.name] = (dict(psa.flavors), cycle)
        cycle += 1
    parked = {
        wl.name
        for pq in mgr.cluster_queues.values()
        for wl in list(pq.inadmissible.values()) + list(pq.heap.items())
    }
    return admitted, parked


def device_fair_drain_trace(spec):
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    pending = []
    for cq_name, pq in mgr.cluster_queues.items():
        for wl in pq.snapshot_sorted():
            pending.append((wl, cq_name))
    snapshot = take_snapshot(cache)
    outcome = run_drain(
        snapshot,
        pending,
        cache.flavors,
        timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
        fair_sharing=True,
    )
    admitted = {
        wl.name: (flavors, cycle) for wl, _, flavors, cycle in outcome.admitted
    }
    parked = {wl.name for wl, _ in outcome.parked}
    return admitted, parked, outcome


def fair_drain_spec(seed, n_cohorts=2, cqs_per_cohort=4, workloads_per_cq=5):
    """Cohorts with shared borrowable capacity, unequal fairSharing
    weights and contending backlogs — admission ORDER is decided by the
    DRS tournament, not (priority, FIFO)."""
    rng = np.random.default_rng(seed + 47000)
    flavors = ["fl-0", "fl-1"]
    cqs, workloads = [], []
    t = 0.0
    weights = [500, 1000, 1000, 2000]
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            k = int(rng.integers(1, 3))
            fls = []
            for f in flavors[:k]:
                fls.append((f, {"cpu": str(int(rng.integers(2, 8)))}, None, None))
            cqs.append(
                {
                    "name": name,
                    "cohort": f"cohort-{ci}",
                    "groups": [{"resources": ["cpu"], "flavors": fls}],
                    "fair_weight": weights[int(rng.integers(0, len(weights)))],
                }
            )
            for wi in range(workloads_per_cq):
                t += 1.0
                workloads.append(
                    {
                        "name": f"wl-{ci}-{qi}-{wi}",
                        "queue": f"lq-{name}",
                        "prio": int(rng.integers(0, 3)) * 10,
                        "t": t,
                        "pod_sets": [
                            {
                                "name": "main",
                                "count": int(rng.integers(1, 3)),
                                "requests": {"cpu": str(int(rng.integers(1, 5)))},
                            }
                        ],
                    }
                )
    return {"flavors": flavors, "cqs": cqs, "workloads": workloads}


class TestDrainFairSharing:
    def test_tournament_orders_by_drs(self):
        # cq-a (weight 500) already borrows heavily; cq-b (weight 2000)
        # borrows little. Fair order admits cq-b's head first when only
        # one can fit — the opposite of the FIFO order.
        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "2"}, None, None)]}
                    ],
                    "fair_weight": 500,
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "2"}, None, None)]}
                    ],
                    "fair_weight": 2000,
                },
            ],
            "workloads": [
                # FIFO would admit wa first (earlier timestamp)
                {
                    "name": "wa", "queue": "lq-cq-a", "prio": 0, "t": 1.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "3"}}
                    ],
                },
                {
                    "name": "wb", "queue": "lq-cq-b", "prio": 0, "t": 2.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "3"}}
                    ],
                },
            ],
        }
        h_admitted, h_parked = host_fair_drain_trace(spec)
        d_admitted, d_parked, outcome = device_fair_drain_trace(spec)
        assert not outcome.fallback
        assert d_admitted == h_admitted
        assert d_parked == h_parked
        # both would borrow 1 above nominal 2; b's weight (2000) makes
        # its simulated share lower, so b wins the tournament, admits
        # in cycle 0, and a (no capacity left) parks
        assert d_admitted == {"wb": ({"cpu": "f"}, 0)}
        assert d_parked == {"wa"}
        # the NON-fair order decides the opposite way (wa is older), so
        # the tournament — not FIFO — made this call
        ff_admitted, ff_parked, _ = device_drain_trace(spec)
        assert "wa" in ff_admitted and ff_parked == {"wb"}

    def test_preempt_capable_cqs_fall_back_in_fair_mode(self):
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import PreemptionPolicy

        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"], "flavors": [("f", {"cpu": "4"}, None, None)]}
                    ],
                    "preemption": Preemption(
                        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
                    ),
                }
            ],
            "workloads": [
                {
                    "name": "w", "queue": "lq-cq", "prio": 0, "t": 1.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "2"}}
                    ],
                }
            ],
        }
        _, _, outcome = device_fair_drain_trace(spec)
        assert [wl.name for wl, _ in outcome.fallback] == ["w"]

    # tier-1 runtime headroom (ISSUE 14): 4 deterministic seeds stay
    # tier-1, the remainder of the historical sweep rides @slow
    @pytest.mark.parametrize("seed", range(4))
    def test_randomized(self, seed):
        spec = fair_drain_spec(seed)
        h_admitted, h_parked = host_fair_drain_trace(spec)
        d_admitted, d_parked, outcome = device_fair_drain_trace(spec)
        assert not outcome.fallback
        assert d_admitted == h_admitted
        assert d_parked == h_parked

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(4, 16))
    def test_randomized_wide(self, seed):
        self.test_randomized(seed)


def host_fair_preempt_drain_trace(spec, fs_strategies=None):
    """Host truth under fair-sharing ordering AND fair-sharing
    preemption: scheduler cycles with evictions applied between cycles
    (the reconciler round-trip compressed to the cycle boundary)."""
    sched, mgr, cache, _ = build_preempt_env(spec)
    sched.fair_sharing = True
    sched.preemptor.enable_fair_sharing = True
    if fs_strategies is not None:
        sched.preemptor.fs_strategies = list(fs_strategies)
    admitted, evicted = {}, set()
    for _ in range(300):
        progressed = False
        if any(pq.pending_active() > 0 for pq in mgr.cluster_queues.values()):
            progressed = True
        res = sched.schedule()
        for e in res.admitted:
            psa = e.workload.admission.pod_set_assignments[0]
            admitted[e.workload.name] = dict(psa.flavors)
        victims = []
        for e in res.preempting:
            for t in e.preemption_targets:
                victims.append(t.workload.workload)
        for wl in victims:
            if wl.name in evicted:
                continue
            evicted.add(wl.name)
            cq_name = wl.admission.cluster_queue
            cache.delete_workload(wl)
            mgr.queue_associated_inadmissible_workloads_after(cq_name)
            progressed = True
        if not progressed:
            break
    parked = {
        wl.name
        for pq in mgr.cluster_queues.values()
        for wl in list(pq.inadmissible.values()) + list(pq.heap.items())
    }
    return admitted, evicted, parked


def device_fair_preempt_drain_trace(spec, fs_strategies=None, **kw):
    from kueue_tpu.core.drain import run_drain_fair_preempt

    sched, mgr, cache, _ = build_preempt_env(spec)
    pending = []
    for cq_name, pq in mgr.cluster_queues.items():
        for wl in pq.snapshot_sorted():
            pending.append((wl, cq_name))
    snapshot = take_snapshot(cache)
    outcome = run_drain_fair_preempt(
        snapshot,
        pending,
        cache.flavors,
        timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
        fs_strategies=fs_strategies,
        **kw,
    )
    admitted = {wl.name: flavors for wl, _, flavors, _ in outcome.admitted}
    evicted = {wl.name for wl, _, _ in outcome.preempted}
    parked = {wl.name for wl, _ in outcome.parked}
    return admitted, evicted, parked, outcome


def fair_preempt_spec(
    seed, n_cohorts=2, cqs_per_cohort=3, victims_per_cq=3, workloads_per_cq=3
):
    """Random fair cohorts WITH preemption enabled — borrowing victims
    saturate shared capacity, pending backlogs need the fair victim
    tournament to start."""
    from kueue_tpu.models.cluster_queue import Preemption
    from kueue_tpu.models.constants import (
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
    )

    rng = np.random.default_rng(seed + 91000)
    flavors = ["fl-0"]
    cqs, workloads, victims = [], [], []
    weights = [500, 1000, 1000, 2000]
    wcq_opts = [
        PreemptionPolicy.NEVER,
        PreemptionPolicy.LOWER_PRIORITY,
        PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY,
    ]
    rwc_opts = [
        ReclaimWithinCohortPolicy.NEVER,
        ReclaimWithinCohortPolicy.LOWER_PRIORITY,
        ReclaimWithinCohortPolicy.ANY,
    ]
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            quota = int(rng.integers(4, 10))
            cqs.append(
                {
                    "name": name,
                    "cohort": f"cohort-{ci}",
                    "groups": [
                        {
                            "resources": ["cpu"],
                            "flavors": [("fl-0", {"cpu": str(quota)}, None, None)],
                        }
                    ],
                    "fair_weight": weights[int(rng.integers(0, len(weights)))],
                    "preemption": Preemption(
                        within_cluster_queue=wcq_opts[
                            int(rng.integers(0, len(wcq_opts)))
                        ],
                        reclaim_within_cohort=rwc_opts[
                            int(rng.integers(0, len(rwc_opts)))
                        ],
                    ),
                }
            )
            # admitted victims, some borrowing above nominal (DRS > 0)
            for vi in range(int(rng.integers(1, victims_per_cq + 1))):
                t += 1.0
                victims.append(
                    (
                        f"victim-{ci}-{qi}-{vi}", name, "fl-0",
                        str(int(rng.integers(2, 7))),
                        int(rng.integers(0, 3)) * 10, t,
                    )
                )
            for wi in range(workloads_per_cq):
                t += 1.0
                workloads.append(
                    {
                        "name": f"wl-{ci}-{qi}-{wi}",
                        "queue": f"lq-{name}",
                        "prio": int(rng.integers(0, 3)) * 10,
                        "t": t,
                        "pod_sets": [
                            {
                                "name": "main",
                                "count": 1,
                                "requests": {
                                    "cpu": str(int(rng.integers(1, 5)))
                                },
                            }
                        ],
                    }
                )
    return {
        "flavors": flavors, "cqs": cqs, "workloads": workloads,
        "victims": victims,
    }


class TestFairPreemptDrain:
    def test_fair_preemption_in_kernel(self):
        # cohort capacity saturated by a borrowing low-weight CQ; the
        # high-weight CQ's head can only start via the fair victim
        # tournament — no fallback, eviction decided in the drain
        from kueue_tpu.models.cluster_queue import Preemption
        from kueue_tpu.models.constants import (
            PreemptionPolicy,
            ReclaimWithinCohortPolicy,
        )
        from kueue_tpu.core.preemption import IN_COHORT_FAIR_SHARING

        prem = Preemption(
            within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
            reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
        )
        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq-a",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"],
                         "flavors": [("f", {"cpu": "4"}, None, None)]}
                    ],
                    "fair_weight": 1000,
                    "preemption": prem,
                },
                {
                    "name": "cq-b",
                    "cohort": "co",
                    "groups": [
                        {"resources": ["cpu"],
                         "flavors": [("f", {"cpu": "4"}, None, None)]}
                    ],
                    "fair_weight": 1000,
                    "preemption": prem,
                },
            ],
            # cq-a borrows the whole cohort (8 cpu over nominal 4)
            "victims": [
                ("va-0", "cq-a", "f", "4", 0, 1.0),
                ("va-1", "cq-a", "f", "4", 0, 2.0),
            ],
            "workloads": [
                {
                    "name": "wb", "queue": "lq-cq-b", "prio": 0, "t": 3.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "3"}}
                    ],
                }
            ],
        }
        ha, he, hp = host_fair_preempt_drain_trace(spec)
        da, de, dp, outcome = device_fair_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert da == ha
        assert de == he
        assert dp == hp
        assert "wb" in da and de  # preemption actually happened
        other_cq = [
            ev for ev in outcome.evictions if ev.victim_cq != ev.by_cq
        ]
        assert all(
            ev.reason == IN_COHORT_FAIR_SHARING for ev in other_cq
        ) and other_cq

    # tier-1 runtime headroom (ISSUE 14): 4 deterministic seeds stay
    # tier-1, the remainder of the historical sweep rides @slow
    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_parity(self, seed):
        spec = fair_preempt_spec(seed)
        ha, he, hp = host_fair_preempt_drain_trace(spec)
        da, de, dp, outcome = device_fair_preempt_drain_trace(spec)
        assert not outcome.fallback
        assert da == ha
        assert de == he
        assert dp == hp

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(4, 16))
    def test_randomized_parity_wide(self, seed):
        self.test_randomized_parity(seed)

    @pytest.mark.parametrize("seed", range(3))
    def test_randomized_parity_single_strategy(self, seed):
        # LessThanInitialShare alone (the other configurable strategy
        # list, config fairSharing.preemptionStrategies)
        from kueue_tpu.core.preemption import LESS_THAN_INITIAL_SHARE

        strategies = [LESS_THAN_INITIAL_SHARE]
        spec = fair_preempt_spec(seed + 300)
        ha, he, hp = host_fair_preempt_drain_trace(spec, strategies)
        da, de, dp, outcome = device_fair_preempt_drain_trace(
            spec, fs_strategies=strategies
        )
        assert not outcome.fallback
        assert da == ha
        assert de == he
        assert dp == hp

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(3, 8))
    def test_randomized_parity_single_strategy_wide(self, seed):
        self.test_randomized_parity_single_strategy(seed)


def test_retry_cap_scales_with_walk_odometer():
    """The stuck-detection budget must cover any CONVERGENT
    PendingFlavors sequence: prod over podsets and resource groups of
    (walk length + 1), not a flat multiple of K."""
    from kueue_tpu.core.drain import plan_drain
    from tests.test_solver_path import build_env

    spec = {
        "flavors": ["f0", "f1", "f2", "f3", "g0", "g1"],
        "cqs": [{
            "name": "cq",
            "cohort": "co",
            "groups": [
                {"resources": ["cpu"], "flavors": [
                    (f, {"cpu": "4"}, None, None) for f in ["f0", "f1", "f2", "f3"]
                ]},
                {"resources": ["gpu"], "flavors": [
                    (g, {"gpu": "2"}, None, None) for g in ["g0", "g1"]
                ]},
            ],
            "preemption": None,
        }],
        "workloads": [
            {
                "name": "w-multi", "queue": "lq-cq", "prio": 0, "t": 0.0,
                "pod_sets": [
                    {"name": "driver", "count": 1,
                     "requests": {"cpu": "1", "gpu": "1"}},
                    {"name": "worker", "count": 1,
                     "requests": {"cpu": "1"}},
                ],
            },
        ],
    }
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    pending = [
        (wl, cqn.replace("lq-", ""))
        for cqn, pq in mgr.cluster_queues.items()
        for wl in pq.snapshot_sorted()
    ]
    pending = [(wl, "cq") for wl, _ in pending]
    snap = take_snapshot(cache)
    plan = plan_drain(snap, pending, cache.flavors)
    # driver: (4+1)*(2+1)=15; worker: (4+1)=5 -> joint odometer 75 (+1)
    assert int(plan.queues_np["retry_cap"][0]) == 76
