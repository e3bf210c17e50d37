"""Production solver-path parity: Scheduler with use_solver=True must
make decisions identical to the host-only path (use_solver=False is the
decision oracle — reference semantics per pkg/scheduler/scheduler.go).

Scenarios are built twice from one spec (fresh objects per run) and
drained cycle-by-cycle; per-cycle admitted order, assigned flavors,
usage, skip/requeue outcomes and final cache state must match exactly.
"""

import numpy as np
import pytest

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
from kueue_tpu.core.cache import Cache
from kueue_tpu.core.preemption import Preemptor
from kueue_tpu.core.queue_manager import QueueManager
from kueue_tpu.core.scheduler import Scheduler
from kueue_tpu.utils.clock import FakeClock


def build_env(spec, use_solver):
    """spec: dict with cohorts, cqs, flavors, workloads (pure data, so
    both environments get independent-but-identical objects)."""
    clock = FakeClock(1000.0)
    cache = Cache()
    for fname in spec["flavors"]:
        cache.add_or_update_flavor(ResourceFlavor(name=fname))
    for c in spec.get("cohorts", []):
        from kueue_tpu.models.cohort import Cohort

        groups = tuple(
            ResourceGroup(
                tuple(rg["resources"]),
                tuple(
                    FlavorQuotas.build(f, {r: (v, bl, ll) for r, v in q.items()})
                    for f, q, bl, ll in rg["flavors"]
                ),
            )
            for rg in c.get("groups", [])
        )
        cache.add_or_update_cohort(
            Cohort(name=c["name"], parent=c.get("parent"), resource_groups=groups)
        )
    mgr = QueueManager(clock=clock)
    for cq_spec in spec["cqs"]:
        groups = []
        for rg in cq_spec["groups"]:
            groups.append(
                ResourceGroup(
                    tuple(rg["resources"]),
                    tuple(
                        FlavorQuotas.build(
                            f, {r: (v, bl, ll) for r, v in q.items()}
                        )
                        for f, q, bl, ll in rg["flavors"]
                    ),
                )
            )
        cq_kwargs = {}
        if cq_spec.get("fungibility") is not None:
            cq_kwargs["flavor_fungibility"] = cq_spec["fungibility"]
        if cq_spec.get("fair_weight") is not None:
            from kueue_tpu.models.cluster_queue import FairSharing

            cq_kwargs["fair_sharing"] = FairSharing(
                weight_milli=int(cq_spec["fair_weight"])
            )
        cq = ClusterQueue(
            name=cq_spec["name"],
            cohort=cq_spec.get("cohort"),
            namespace_selector={},
            resource_groups=tuple(groups),
            preemption=cq_spec.get("preemption") or Preemption(),
            **cq_kwargs,
        )
        cache.add_or_update_cluster_queue(cq)
        mgr.add_cluster_queue(cq)
        mgr.add_local_queue(
            LocalQueue(
                namespace="ns", name=f"lq-{cq.name}", cluster_queue=cq.name
            )
        )
    preemptor = Preemptor(clock)
    sched = Scheduler(
        queues=mgr,
        cache=cache,
        clock=clock,
        preemptor=preemptor,
        use_solver=use_solver,
        solver_threshold=1,
    )
    workloads = {}
    for w in spec["workloads"]:
        wl = Workload(
            namespace="ns",
            name=w["name"],
            queue_name=w["queue"],
            priority=w.get("prio", 0),
            creation_time=w["t"],
            pod_sets=tuple(
                PodSet.build(ps["name"], ps["count"], dict(ps["requests"]))
                for ps in w["pod_sets"]
            ),
        )
        workloads[w["name"]] = wl
        mgr.add_or_update_workload(wl)
    return sched, mgr, cache, workloads


def drain_and_trace(sched, mgr, cache, max_cycles=60):
    """Run cycles to quiescence; return the decision trace."""
    trace = []
    for _ in range(max_cycles):
        res = sched.schedule()
        cycle = {
            "admitted": [
                (
                    e.workload.name,
                    e.cq_name,
                    tuple(
                        sorted(
                            (psa.name, tuple(sorted(psa.flavors.items())), psa.count)
                            for psa in e.workload.admission.pod_set_assignments
                        )
                    ),
                )
                for e in res.admitted
            ],
            "preempting": sorted(e.workload.name for e in res.preempting),
            "skipped": sorted(
                e.workload.name
                for e in res.requeued
                if "no longer fits" in (e.inadmissible_msg or "")
            ),
        }
        trace.append(cycle)
        if not res.admitted and not res.preempting:
            # nothing moved; drain parked entries once then stop
            moved = False
            for cq_name in list(mgr.cluster_queues):
                moved = (
                    mgr.queue_associated_inadmissible_workloads_after(cq_name)
                    or moved
                )
            if not moved:
                break
    final = {
        name: sorted(cached.workloads) for name, cached in cache.cluster_queues.items()
    }
    return trace, final


def assert_parity(spec):
    s_host, m_host, c_host, _ = build_env(spec, use_solver=False)
    s_dev, m_dev, c_dev, _ = build_env(spec, use_solver=True)
    host_trace, host_final = drain_and_trace(s_host, m_host, c_host)
    dev_trace, dev_final = drain_and_trace(s_dev, m_dev, c_dev)
    assert dev_trace == host_trace
    assert dev_final == host_final
    return host_trace


def random_spec(seed, with_preemption=False, n_cohorts=2, cqs_per_cohort=3,
                n_flavors=3, workloads_per_cq=6):
    rng = np.random.default_rng(seed)
    flavors = [f"fl-{i}" for i in range(n_flavors)]
    cqs = []
    workloads = []
    t = 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            k = int(rng.integers(1, n_flavors + 1))
            fls = []
            for f in flavors[:k]:
                quota = {"cpu": str(int(rng.integers(4, 16)))}
                bl = (
                    str(int(rng.integers(0, 10)))
                    if rng.random() < 0.4
                    else None
                )
                ll = (
                    str(int(rng.integers(0, 6)))
                    if rng.random() < 0.3
                    else None
                )
                fls.append((f, quota, bl, ll))
            preemption = None
            if with_preemption and rng.random() < 0.5:
                preemption = Preemption(
                    within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                    reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
                )
            cqs.append(
                {
                    "name": name,
                    "cohort": f"cohort-{ci}",
                    "groups": [{"resources": ["cpu"], "flavors": fls}],
                    "preemption": preemption,
                }
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
                                "count": int(rng.integers(1, 4)),
                                "requests": {"cpu": str(int(rng.integers(1, 6)))},
                            }
                        ],
                    }
                )
    return {"flavors": flavors, "cqs": cqs, "workloads": workloads}


def try_next_spec(seed, n_cohorts=2, cqs_per_cohort=6, workloads_per_cq=8):
    """Two resource groups (cpu+memory | gpu) of 2-4 flavors each (at
    most 8 candidates a head), no borrowing limit, and whenCanBorrow
    TryNextFlavor on a random half of the ClusterQueues."""
    from kueue_tpu.models.cluster_queue import FlavorFungibility
    from kueue_tpu.models.constants import FlavorFungibilityPolicy as FF

    rng = np.random.default_rng(seed)
    n_a, n_b = [(2, 2), (2, 3), (3, 2), (2, 4), (4, 2)][int(rng.integers(0, 5))]
    cpu_fl = [f"c{i}" for i in range(n_a)]
    gpu_fl = [f"g{i}" for i in range(n_b)]
    cqs, workloads, t = [], [], 0.0
    for ci in range(n_cohorts):
        for qi in range(cqs_per_cohort):
            name = f"cq-{ci}-{qi}"
            cqs.append({
                "name": name,
                "cohort": f"cohort-{ci}",
                "groups": [
                    {"resources": ["cpu", "memory"], "flavors": [
                        (f, {"cpu": str(int(rng.integers(2, 10))),
                             "memory": f"{int(rng.integers(4, 40))}Gi"}, None, None)
                        for f in cpu_fl]},
                    {"resources": ["gpu"], "flavors": [
                        (f, {"gpu": str(int(rng.integers(1, 6)))}, None, None)
                        for f in gpu_fl]},
                ],
                "preemption": None,
                "fungibility": FlavorFungibility(
                    when_can_borrow=(FF.TRY_NEXT_FLAVOR if rng.random() < 0.5
                                     else FF.BORROW),
                    when_can_preempt=FF.TRY_NEXT_FLAVOR,
                ),
            })
            for wi in range(workloads_per_cq):
                t += 1.0
                req = {"cpu": str(int(rng.integers(1, 6))),
                       "memory": f"{int(rng.integers(1, 12))}Gi"}
                if rng.random() < 0.6:
                    req["gpu"] = str(int(rng.integers(1, 4)))
                workloads.append({
                    "name": f"wl-{ci}-{qi}-{wi}", "queue": f"lq-{name}",
                    "prio": int(rng.integers(0, 3)) * 10, "t": t,
                    "pod_sets": [{"name": "main", "count": 1, "requests": req}],
                })
    return {"flavors": cpu_fl + gpu_fl, "cqs": cqs, "workloads": workloads}


class TestResidentCycleState:
    def test_delta_updates_and_invalidation(self):
        """dispatch_lowered with device-resident tensors must decide
        identically to a fresh-ship dispatch across usage mutations
        (delta path) and quota edits (structure invalidation)."""
        from kueue_tpu.core.queue_manager import queue_order_timestamp
        from kueue_tpu.core.snapshot import take_snapshot
        from kueue_tpu.core.solver import (
            ResidentCycleState,
            dispatch_lowered,
            lower_heads,
        )
        from kueue_tpu.core.workload_info import make_admission
        from kueue_tpu.models import Workload, WorkloadConditionType
        from kueue_tpu.models.workload import PodSet

        spec = random_spec(21)
        sched, mgr, cache, _ = build_env(spec, use_solver=False)
        resident = ResidentCycleState()
        ts = lambda wl: queue_order_timestamp(wl, mgr._ts_policy)  # noqa: E731

        def compare():
            heads = [
                (wl, cq)
                for cq, pq in mgr.cluster_queues.items()
                for wl in pq.snapshot_sorted()
            ]
            snapshot = take_snapshot(cache)
            lowered = lower_heads(
                snapshot, heads, cache.flavors, timestamp_fn=ts
            )
            fresh = dispatch_lowered(snapshot, lowered)
            res = dispatch_lowered(snapshot, lowered, resident=resident)
            np.testing.assert_array_equal(fresh.chosen, res.chosen)
            np.testing.assert_array_equal(fresh.admitted, res.admitted)
            np.testing.assert_array_equal(fresh.reserved, res.reserved)

        compare()  # cold: full upload
        assert resident.full_uploads == 1

        # admit a workload -> a few changed usage rows ship as a delta
        cq_name = spec["cqs"][0]["name"]
        flavor = spec["cqs"][0]["groups"][0]["flavors"][0][0]
        wl = Workload(
            namespace="ns", name="resident-victim",
            queue_name=f"lq-{cq_name}", priority=0, creation_time=500.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "2"}),),
        )
        wl.admission = make_admission(cq_name, {"main": {"cpu": flavor}}, wl)
        wl.set_condition(
            WorkloadConditionType.QUOTA_RESERVED, True,
            reason="QuotaReserved", now=500.0,
        )
        cache.add_or_update_workload(wl)
        compare()
        assert resident.full_uploads == 1  # no re-upload
        assert resident.delta_rows >= 1

        # quota edit -> structure fingerprint changes -> full re-upload
        from kueue_tpu.models import ClusterQueue
        from kueue_tpu.models.cluster_queue import FlavorQuotas, ResourceGroup

        cq0 = cache.cluster_queues[cq_name].model
        new_groups = (
            ResourceGroup(
                ("cpu",),
                (FlavorQuotas.build(flavor, {"cpu": "99"}),),
            ),
        )
        cache.add_or_update_cluster_queue(
            ClusterQueue(
                name=cq_name, cohort=cq0.cohort,
                namespace_selector={}, resource_groups=new_groups,
            )
        )
        compare()
        assert resident.full_uploads == 2


class TestSolverPathParity:
    @pytest.mark.parametrize("seed", range(12))
    def test_randomized_fit_only(self, seed):
        trace = assert_parity(random_spec(seed))
        assert any(c["admitted"] for c in trace)

    @pytest.mark.parametrize("seed", range(12, 20))
    def test_randomized_with_preemption(self, seed):
        assert_parity(random_spec(seed, with_preemption=True))

    def test_multi_resource_groups(self):
        # two resource groups (cpu+memory | gpu) exercises the cartesian
        # candidate enumeration
        spec = {
            "flavors": ["fa", "fb", "ga"],
            "cqs": [
                {
                    "name": "cq-x",
                    "cohort": "co",
                    "groups": [
                        {
                            "resources": ["cpu", "memory"],
                            "flavors": [
                                ("fa", {"cpu": "4", "memory": "8Gi"}, None, None),
                                ("fb", {"cpu": "8", "memory": "16Gi"}, None, None),
                            ],
                        },
                        {
                            "resources": ["gpu"],
                            "flavors": [("ga", {"gpu": "2"}, None, None)],
                        },
                    ],
                    "preemption": None,
                },
                {
                    "name": "cq-y",
                    "cohort": "co",
                    "groups": [
                        {
                            "resources": ["cpu", "memory"],
                            "flavors": [
                                ("fa", {"cpu": "6", "memory": "12Gi"}, None, None)
                            ],
                        }
                    ],
                    "preemption": None,
                },
            ],
            "workloads": [
                {
                    "name": f"w{i}",
                    "queue": "lq-cq-x" if i % 2 == 0 else "lq-cq-y",
                    "prio": i % 3,
                    "t": float(i),
                    "pod_sets": [
                        {
                            "name": "main",
                            "count": 1 + i % 2,
                            "requests": (
                                {"cpu": "2", "memory": "4Gi", "gpu": "1"}
                                if i % 4 == 0
                                else {"cpu": "3", "memory": "2Gi"}
                            ),
                        }
                    ],
                }
                for i in range(10)
            ],
        }
        assert_parity(spec)


    @pytest.mark.parametrize("seed", range(20, 28))
    def test_randomized_try_next_flavor(self, seed):
        # whenCanBorrow TryNextFlavor heads are lowered onto the device
        # and decide as the host flavor walk does, cursors included
        spec = try_next_spec(seed)
        trace = assert_parity(spec)
        assert any(c["admitted"] for c in trace)
        sched, mgr, cache, _ = build_env(spec, use_solver=True)
        drain_and_trace(sched, mgr, cache)
        counts = [t.counts for t in sched.last_traces]
        offered = sum(c.get("fungible_heads", 0) for c in counts)
        assert offered > 0
        assert sum(c.get("fungible_lowered", 0) for c in counts) == offered
        assert sum(c.get("unrepresentable", 0) for c in counts) == 0

    @pytest.mark.parametrize("seed", range(28, 32))
    def test_try_next_flavor_host_mirror(self, seed):
        # the guard's numpy mirror (KERNEL_MIRRORS["assign_kernel"]) takes
        # the same candidate for a TryNextFlavor batch, skip flag included
        from kueue_tpu.core.guard import results_match, solve_lowered_host
        from kueue_tpu.core.snapshot import take_snapshot
        from kueue_tpu.core.solver import dispatch_lowered, lower_heads

        spec = try_next_spec(seed, workloads_per_cq=3)
        sched, mgr, cache, workloads = build_env(spec, use_solver=True)
        # admit one cycle so that later heads borrow
        sched.schedule()
        snapshot = take_snapshot(cache)
        heads = [
            (wl, f"cq-{wl.name.split('-')[1]}-{wl.name.split('-')[2]}")
            for wl in workloads.values() if not wl.has_quota_reservation
        ]
        lowered = lower_heads(snapshot, heads, cache.flavors)
        assert not lowered.fallback
        dev = dispatch_lowered(snapshot, lowered)
        host = solve_lowered_host(snapshot, lowered)
        assert results_match(dev, host) == []
        assert np.array_equal(np.asarray(dev.skipped), np.asarray(host.skipped))


class TestDeviceResolution:
    def test_pure_cycle_resolves_on_device(self):
        spec = random_spec(99)
        sched, mgr, cache, _ = build_env(spec, use_solver=True)
        res = sched.schedule()
        assert res.resolution == "device"
        assert res.admitted

    def test_host_resolution_when_preemption_possible(self):
        # one CQ full of low-prio work + a high-prio head that must
        # preempt: the cycle needs the host loop
        spec = {
            "flavors": ["f"],
            "cqs": [
                {
                    "name": "cq",
                    "cohort": None,
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
                    "name": "low",
                    "queue": "lq-cq",
                    "prio": 0,
                    "t": 0.0,
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "4"}}
                    ],
                }
            ],
        }
        sched, mgr, cache, wls = build_env(spec, use_solver=True)
        r = sched.schedule()
        assert [e.workload.name for e in r.admitted] == ["low"]
        # now a high-prio head that requires preemption
        high = Workload(
            namespace="ns", name="high", queue_name="lq-cq", priority=100,
            creation_time=5.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "4"}),),
        )
        mgr.add_or_update_workload(high)
        r2 = sched.schedule()
        assert r2.resolution == "host"
        assert [e.workload.name for e in r2.preempting] == ["high"]

    def test_solver_off_never_uses_device(self):
        spec = random_spec(7)
        sched, mgr, cache, _ = build_env(spec, use_solver=False)
        res = sched.schedule()
        assert res.resolution == "host"

    def test_auto_threshold(self):
        spec = random_spec(3, n_cohorts=1, cqs_per_cohort=2, workloads_per_cq=1)
        sched, mgr, cache, _ = build_env(spec, use_solver=None)
        sched.solver_threshold = 16  # 2 heads < 16 -> host
        res = sched.schedule()
        assert res.resolution == "host"

    def test_auto_mode_is_latency_aware(self):
        # Auto mode routes by measured cost: with a measured dispatch
        # far above the host estimate, the device stays off; once the
        # head count makes the host estimate exceed it, device turns on.
        spec = random_spec(3, n_cohorts=1, cqs_per_cohort=2, workloads_per_cq=1)
        sched, _, _, _ = build_env(spec, use_solver=None)
        sched.solver_threshold = 4
        sched._host_assign_ema = 1e-4
        sched._device_dispatch_est.observe(0.05)  # 50 ms device dispatch
        assert not sched._solver_enabled(10)  # 1 ms host < 50 ms device
        assert sched._solver_enabled(10_000)  # 1 s host > 50 ms device
        # no measurement yet -> probe the device once
        sched2, _, _, _ = build_env(spec, use_solver=None)
        sched2.solver_threshold = 4
        assert sched2._solver_enabled(4)

    def test_auto_mode_stale_estimate_erodes(self):
        # A pessimistic first sample (XLA compile included) must not
        # disable the device forever: each skip erodes the estimate.
        spec = random_spec(3, n_cohorts=1, cqs_per_cohort=2, workloads_per_cq=1)
        sched, _, _, _ = build_env(spec, use_solver=None)
        sched.solver_threshold = 4
        sched._host_assign_ema = 1e-4
        sched._device_dispatch_est.observe(30.0)  # cold compile sample
        for _ in range(5):
            assert not sched._solver_enabled(100)
        assert sched._device_dispatch_est.value < 30.0

    def test_auto_mode_probes_then_measures(self):
        # End to end: first eligible auto cycle dispatches (probe) and
        # records a measurement; the gate then has real data.
        spec = random_spec(11, n_cohorts=2, cqs_per_cohort=4, workloads_per_cq=4)
        sched, mgr, cache, _ = build_env(spec, use_solver=None)
        sched.solver_threshold = 1
        sched.schedule()
        assert sched._device_dispatch_est.value is not None

    def test_gate_recovers_when_device_slows(self):
        # Drift: erosion re-probes a stale estimate, and a slow re-probe
        # measurement RAISES the estimate back (windowed min, not a
        # running min), so the gate re-disables a genuinely slow device
        # instead of locking onto it forever.
        spec = random_spec(3, n_cohorts=1, cqs_per_cohort=2, workloads_per_cq=1)
        sched, _, _, _ = build_env(spec, use_solver=None)
        sched.solver_threshold = 4
        sched._host_assign_ema = 1e-4
        est = sched._device_dispatch_est
        est.observe(0.04)  # warm-era fast sample
        # host est for 100 heads = 10 ms < 40 ms -> skip; erode far past
        # the true dispatch cost (the old running-min bug's trigger)
        for _ in range(2000):
            if sched._solver_enabled(100):
                break
        assert sched._solver_enabled(100)  # eroded below 10 ms: re-probe
        # the re-probe measures the TRUE cost (50 ms, device got slower;
        # window fills with slow samples, the old fast one ages out)
        for _ in range(est._samples.maxlen):
            est.observe(0.05)
        assert est.value >= 0.05  # estimate rose: windowed, not min()
        assert not sched._solver_enabled(100)  # 10 ms host wins again

    def test_gate_converges_when_device_speeds_up(self):
        # Drift the other way: after a slow era the device gets fast
        # (e.g. recompile cached); one fast measurement immediately
        # lowers the windowed min and the gate re-enables.
        spec = random_spec(3, n_cohorts=1, cqs_per_cohort=2, workloads_per_cq=1)
        sched, _, _, _ = build_env(spec, use_solver=None)
        sched.solver_threshold = 4
        sched._host_assign_ema = 1e-4
        est = sched._device_dispatch_est
        est.observe(0.5)  # slow era
        assert not sched._solver_enabled(100)  # 10 ms host < 500 ms
        est.observe(0.005)  # fast sample lands (e.g. forced dispatch)
        assert sched._solver_enabled(100)  # 10 ms host > 5 ms device

    def test_erosion_resets_on_measurement(self):
        from kueue_tpu.core.scheduler import _LatencyEstimate

        est = _LatencyEstimate(window=3, erosion_rate=0.5)
        est.observe(1.0)
        est.erode()
        est.erode()
        assert est.value == 0.25
        est.observe(2.0)  # fresh measurement cancels accumulated erosion
        assert est.value == 1.0  # min(1.0, 2.0) * 1.0
        est.observe(3.0)
        est.observe(4.0)  # window now [2, 3, 4]: the 1.0 sample aged out
        assert est.value == 2.0


class TestCursorParity:
    def test_requeued_fit_head_keeps_host_cursor(self):
        # two CQs in a cohort with limited shared capacity; both heads
        # FIT at nominate time but only one survives phase 2 -> the
        # skipped one's LastAssignment cursor must match the host path
        spec = {
            "flavors": ["f1", "f2"],
            "cqs": [
                {
                    "name": f"cq-{i}",
                    "cohort": "co",
                    "groups": [
                        {
                            "resources": ["cpu"],
                            "flavors": [
                                ("f1", {"cpu": "2"}, None, None),
                                ("f2", {"cpu": "2"}, None, None),
                            ],
                        }
                    ],
                    "preemption": None,
                }
                for i in range(2)
            ],
            "workloads": [
                {
                    "name": f"w{i}",
                    "queue": f"lq-cq-{i}",
                    "prio": 0,
                    "t": float(i),
                    "pod_sets": [
                        {"name": "main", "count": 1, "requests": {"cpu": "4"}}
                    ],
                }
                for i in range(2)
            ],
        }
        s_host, m_host, c_host, wl_host = build_env(spec, use_solver=False)
        s_dev, m_dev, c_dev, wl_dev = build_env(spec, use_solver=True)
        s_host.schedule()
        s_dev.schedule()
        for name in wl_host:
            lh = wl_host[name].last_assignment
            ld = wl_dev[name].last_assignment
            if lh is None:
                assert ld is None
            else:
                assert ld is not None
                assert lh.last_tried_flavor_idx == ld.last_tried_flavor_idx
