"""Self-healing admission hot path (core/guard.py).

The chaos suite for the resilient solver executor: with faults injected
at every new named point — device raise, hang-past-deadline,
wrong-answer — across seeded admission/preemption traces, the loop must
keep admitting, the final admitted set must equal the fault-free
host-only run, ``check_invariants()`` must hold throughout, and no
cycle may abort. Plus units for the circuit breaker, poison bisection,
quarantine lifecycle + durability, the transactional apply (satellite
bugfix), /healthz degradation, and the fault-point registry lint.
"""

import json
import urllib.request

import numpy as np
import pytest

from kueue_tpu import serialization as ser
from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.core.guard import (
    CircuitBreaker,
    GuardConfig,
    QuarantineList,
    bisect_poison,
    solve_lowered_host,
)
from kueue_tpu.models import (
    ClusterQueue,
    LocalQueue,
    ResourceFlavor,
    Workload,
)
from kueue_tpu.models.constants import InadmissibleReason
from kueue_tpu.models.workload import PodSet
from kueue_tpu.storage import Journal, recover
from kueue_tpu.testing import faults
from kueue_tpu.utils.clock import FakeClock


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---- scenario: seeded admission/preemption traces ----
def build_rt(seed=0, mode="auto", k_div=16, use_solver=True,
             bulk_drain_threshold=None, ttl_s=300.0, threshold=3):
    rt = ClusterRuntime(
        clock=FakeClock(0.0),
        use_solver=use_solver,
        bulk_drain_threshold=bulk_drain_threshold,
        guard_config=GuardConfig(
            mode=mode,
            divergence_check_every=k_div,
            base_backoff_s=1.0,
            poison_threshold=threshold,
            quarantine_ttl_s=ttl_s,
        ),
    )
    # CREATION queue-order timestamps: clock-advancing faults (hang,
    # phase-deadline) must not reorder eviction requeues, or the
    # decisions-equal-host-run comparison would measure the clock, not
    # the guard (set before any CQ captures the policy)
    from kueue_tpu.core.queue_manager import RequeueTimestamp

    rt.queues._ts_policy = RequeueTimestamp.CREATION
    rt.add_flavor(ResourceFlavor(name="default"))
    rng = np.random.default_rng(seed)
    for i in range(4):
        quota = str(int(rng.integers(4, 10)))
        rt.add_cluster_queue(
            ser.cq_from_dict(
                {
                    "name": f"cq-{i}",
                    "cohort": "co",
                    "namespaceSelector": {},
                    "preemption": {
                        "withinClusterQueue": (
                            "LowerPriority" if i % 2 == 0 else "Never"
                        ),
                        "reclaimWithinCohort": "Never",
                        "borrowWithinCohort": {"policy": "Never"},
                    },
                    "resourceGroups": [
                        {
                            "coveredResources": ["cpu"],
                            "flavors": [
                                {
                                    "name": "default",
                                    "resources": [
                                        {"name": "cpu", "nominalQuota": quota}
                                    ],
                                }
                            ],
                        }
                    ],
                }
            )
        )
        rt.add_local_queue(
            LocalQueue(namespace="ns", name=f"lq-{i}", cluster_queue=f"cq-{i}")
        )
    return rt


def make_wl(name, cq_index=0, prio=0, cpu="1", t=0.0):
    return Workload(
        namespace="ns", name=name, queue_name=f"lq-{cq_index}",
        priority=prio, creation_time=t,
        pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),),
    )


def admitted_set(rt):
    return frozenset(k for k, wl in rt.workloads.items() if wl.is_admitted)


def run_trace(rt, seed=0, waves=3, wl_per_wave=12):
    """Seeded admission + preemption waves: each wave's priorities rise,
    so preempt-capable CQs evict earlier admissions. Invariants checked
    after every settle. Returns the invariant violations seen."""
    rng = np.random.default_rng(1000 + seed)
    violations = []
    k = 0
    for wave in range(waves):
        for _ in range(wl_per_wave):
            # priorities are UNIQUE: victim selection tiebreaks on
            # quota_reserved_time, which clock-advancing faults (hang)
            # legitimately shift — distinct priorities keep the
            # decisions a pure function of the inputs
            rt.add_workload(
                make_wl(
                    f"w{k}",
                    cq_index=int(rng.integers(0, 4)),
                    prio=wave * 100 + k,
                    cpu=str(int(rng.integers(1, 4))),
                    t=float(k),
                )
            )
            k += 1
        for _ in range(20):
            if rt.run_until_idle(max_iterations=30) < 30:
                break
        violations += rt.check_invariants()
    return violations


# ---- the chaos suite (acceptance criterion) ----
def _hang_action(rt, seconds):
    def advance():
        rt.clock.advance(seconds)

    return advance


def _corrupt_result(res):
    adm = np.asarray(res.admitted).copy()
    adm[:] = ~adm  # every decision wrong
    return res._replace(admitted=adm)


class TestChaosSuite:
    @pytest.mark.parametrize("seed", range(3))
    def test_fault_free_device_equals_host_only(self, seed):
        dev = build_rt(seed, mode="auto")
        run_trace(dev, seed)
        host = build_rt(seed, mode="host")
        run_trace(host, seed)
        assert admitted_set(dev) == admitted_set(host)
        assert dev.guard.contained_cycles == 0

    @pytest.mark.parametrize("skip", [0, 1, 3])
    @pytest.mark.parametrize("seed", range(2))
    def test_device_raise_fails_over(self, seed, skip):
        rt = build_rt(seed, mode="auto")

        def boom():
            raise RuntimeError("injected device fault")

        faults.arm("solver.device_raise", action=boom, skip=skip)
        violations = run_trace(rt, seed)
        faults.reset()
        assert not violations
        assert rt.guard.contained_cycles == 0  # no cycle aborted
        assert rt.guard.failovers > 0
        host = build_rt(seed, mode="host")
        run_trace(host, seed)
        assert admitted_set(rt) == admitted_set(host)
        # the breaker opened and the operator can see it
        assert rt.guard.breaker.state in ("open", "half_open")
        assert any(e.kind == "SolverFailover" for e in rt.events)

    @pytest.mark.parametrize("seed", range(2))
    def test_device_hang_past_deadline_fails_over(self, seed):
        rt = build_rt(seed, mode="auto")
        faults.arm(
            "solver.device_hang",
            action=_hang_action(rt, rt.guard.config.device_deadline_s + 1),
        )
        violations = run_trace(rt, seed)
        faults.reset()
        assert not violations
        assert rt.guard.contained_cycles == 0
        assert rt.guard.failovers > 0
        assert rt.guard.breaker.last_failure.startswith("cycle solve exceeded")
        host = build_rt(seed, mode="host")
        run_trace(host, seed)
        assert admitted_set(rt) == admitted_set(host)

    @pytest.mark.parametrize("seed", range(2))
    def test_device_wrong_answer_caught_and_quarantined(self, seed):
        # K=1: every device solve is differentially verified, so the
        # corrupted kernel is caught before any wrong decision applies
        rt = build_rt(seed, mode="auto", k_div=1)
        faults.arm("solver.device_wrong_answer", action=_corrupt_result)
        violations = run_trace(rt, seed)
        faults.reset()
        assert not violations
        assert rt.guard.divergences >= 1
        assert rt.guard.breaker.state == "quarantined"
        assert any(e.kind == "SolverDiverged" for e in rt.events)
        assert rt.metrics.solver_divergences_total.value() >= 1
        host = build_rt(seed, mode="host")
        run_trace(host, seed)
        assert admitted_set(rt) == admitted_set(host)

    def test_phase_deadline_breach_with_device_opens_breaker(self):
        rt = build_rt(0, mode="auto")
        faults.arm(
            "cycle.phase_deadline",
            action=_hang_action(rt, rt.guard.config.cycle_deadline_s + 1),
        )
        violations = run_trace(rt, 0)
        faults.reset()
        assert not violations
        assert rt.guard.deadline_breaches > 0
        assert rt.guard.contained_cycles == 0
        host = build_rt(0, mode="host")
        run_trace(host, 0)
        assert admitted_set(rt) == admitted_set(host)

    def test_recovery_after_outage_reprobes_device(self):
        rt = build_rt(0, mode="auto")

        def boom():
            raise RuntimeError("transient outage")

        faults.arm("solver.device_raise", action=boom)
        run_trace(rt, 0, waves=1)
        assert rt.guard.breaker.state in ("open", "half_open")
        faults.reset()
        # b * 2^(n-1) backoff elapses -> the next solve is the half-open
        # probe; it succeeds and the device path closes again
        rt.clock.advance(3600.0)
        run_trace(rt, 1, waves=1)
        assert rt.guard.breaker.state == "closed"
        assert any(e.kind == "SolverRecovered" for e in rt.events)
        assert rt.metrics.solver_path.value(path="device") == 1

    def test_bulk_drain_outage_falls_back_to_cycle_loop(self):
        rt = build_rt(0, mode="auto", bulk_drain_threshold=16)

        def boom():
            raise RuntimeError("drain launch died")

        faults.arm("solver.device_raise", action=boom)
        violations = run_trace(rt, 0, waves=2, wl_per_wave=24)
        faults.reset()
        assert not violations
        assert rt.guard.failovers > 0
        host = build_rt(0, mode="host", bulk_drain_threshold=16)
        run_trace(host, 0, waves=2, wl_per_wave=24)
        assert admitted_set(rt) == admitted_set(host)


# ---- host mirror parity (the failover authority) ----
class TestHostMirror:
    @pytest.mark.parametrize("seed", range(4))
    def test_mirror_matches_device_decisions(self, seed):
        from kueue_tpu.core.queue_manager import queue_order_timestamp
        from kueue_tpu.core.snapshot import take_snapshot
        from kueue_tpu.core.solver import dispatch_lowered, lower_heads

        rt = build_rt(seed, mode="host", use_solver=False)
        rng = np.random.default_rng(seed)
        for k in range(24):
            rt.add_workload(
                make_wl(
                    f"m{k}", cq_index=int(rng.integers(0, 4)),
                    prio=int(rng.integers(0, 3)),
                    cpu=str(int(rng.integers(1, 4))), t=float(k),
                )
            )
        snapshot = take_snapshot(rt.cache)
        heads = [
            (wl, rt.queues.cluster_queue_for_workload(wl) or "")
            for wl in sorted(rt.workloads.values(), key=lambda w: w.name)
        ]
        lowered = lower_heads(
            snapshot, heads, rt.cache.flavors,
            timestamp_fn=lambda wl: queue_order_timestamp(
                wl, rt.queues._ts_policy
            ),
        )
        dev = dispatch_lowered(snapshot, lowered)
        host = solve_lowered_host(snapshot, lowered)
        for field in ("chosen", "admitted", "borrows", "reserved"):
            assert np.array_equal(
                np.asarray(getattr(dev, field)),
                np.asarray(getattr(host, field)),
            ), field

    def test_host_mode_runs_no_device_solves(self):
        rt = build_rt(0, mode="host")
        run_trace(rt, 0, waves=1)
        assert rt.guard.device_solves == 0
        assert admitted_set(rt)  # still admitting
        assert rt.metrics.solver_path.value(path="host") == 1


# ---- circuit breaker units ----
class TestCircuitBreaker:
    def test_threshold_opens_and_backoff_doubles(self):
        clock = FakeClock(0.0)
        b = CircuitBreaker(clock, failure_threshold=3, base_backoff_s=2.0)
        assert b.state == "closed"
        b.record_failure("x")
        b.record_failure("x")
        assert b.state == "closed" and b.allow_device()
        assert b.record_failure("x")  # third opens
        assert b.state == "open" and not b.allow_device()
        assert b.next_probe_at == 2.0  # b * 2^0
        clock.advance(2.0)
        assert b.state == "half_open" and b.allow_device()
        # failed probe: re-opens with doubled backoff (b * 2^1)
        assert not b.record_failure("probe failed")  # already open
        assert b.next_probe_at == clock.now() + 4.0
        clock.advance(4.0)
        assert b.allow_device()
        assert b.record_success()  # closes
        assert b.state == "closed" and b.consecutive_failures == 0

    def test_backoff_capped(self):
        clock = FakeClock(0.0)
        b = CircuitBreaker(
            clock, failure_threshold=1, base_backoff_s=1.0, max_backoff_s=8.0
        )
        for _ in range(10):
            b.record_failure("x")
        assert b.next_probe_at - clock.now() == 8.0

    def test_quarantine_is_sticky(self):
        clock = FakeClock(0.0)
        b = CircuitBreaker(clock)
        b.quarantine("divergence")
        clock.advance(1e9)
        assert b.state == "quarantined" and not b.allow_device()
        b.reset()
        assert b.state == "closed" and b.allow_device()


# ---- poison bisection units ----
class TestBisectPoison:
    def _probe(self, poison):
        def probe(subset):
            if any(x in poison for x in subset):
                raise RuntimeError("boom")

        return probe

    def test_single_poison(self):
        assert bisect_poison(list(range(16)), self._probe({11})) == [11]

    def test_multiple_poison(self):
        out = bisect_poison(list(range(16)), self._probe({2, 13}))
        assert sorted(out) == [2, 13]

    def test_no_poison(self):
        assert bisect_poison(list(range(8)), self._probe(set())) == []

    def test_interaction_returns_group(self):
        def probe(subset):
            if 1 in subset and 2 in subset:
                raise RuntimeError("only together")

        out = bisect_poison([0, 1, 2, 3], probe)
        assert 1 in out and 2 in out

    def test_empty(self):
        assert bisect_poison([], self._probe({0})) == []


# ---- poison workloads: quarantine lifecycle ----
class _PoisonWorkload(Workload):
    """Raises during prevalidation — a malformed object the API layer
    let through. Serialization never calls is_active(), so the journal
    can still persist it."""

    poisoned = True

    def is_active(self):
        if self.poisoned:
            raise RuntimeError("poison workload")
        return super().is_active()


class TestPoisonQuarantine:
    def test_poison_head_is_bisected_struck_and_quarantined(self):
        rt = build_rt(0, mode="host", threshold=3, ttl_s=300.0)
        bad = _PoisonWorkload(
            namespace="ns", name="bad", queue_name="lq-0", priority=0,
            creation_time=0.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
        )
        rt.add_workload(bad)
        for k in range(6):
            rt.add_workload(make_wl(f"good{k}", cq_index=k % 4, t=1.0 + k))
        rt.run_until_idle()
        # the cluster is NOT wedged: good workloads admitted
        assert all(f"ns/good{k}" in admitted_set(rt) for k in range(6))
        assert rt.quarantine.active("ns/bad", rt.clock.now())
        assert rt.guard.contained_cycles >= rt.quarantine.threshold
        assert any(e.kind == "WorkloadQuarantined" for e in rt.events)
        assert rt.metrics.solver_quarantined_workloads.value() == 1
        qr = bad.conditions[
            __import__(
                "kueue_tpu.models.constants", fromlist=["x"]
            ).WorkloadConditionType.QUOTA_RESERVED
        ]
        assert qr.reason == InadmissibleReason.QUARANTINED.value
        # quarantined head is sidelined, not nominated, and check_invariants holds
        assert not rt.check_invariants()
        before = rt.scheduler.scheduling_cycle
        rt.run_until_idle()
        assert rt.guard.contained_cycles >= 3  # no NEW containment churn
        assert rt.scheduler.scheduling_cycle >= before

    def test_ttl_expiry_readmits_to_nomination(self):
        rt = build_rt(0, mode="host", threshold=2, ttl_s=60.0)
        bad = _PoisonWorkload(
            namespace="ns", name="bad", queue_name="lq-0", priority=0,
            creation_time=0.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
        )
        rt.add_workload(bad)
        rt.run_until_idle()
        assert rt.quarantine.active("ns/bad", rt.clock.now())
        # the workload gets fixed while sidelined; TTL lapses -> requeue
        bad.poisoned = False
        rt.clock.advance(61.0)
        rt.run_until_idle()
        assert not rt.quarantine.active("ns/bad", rt.clock.now())
        assert any(e.kind == "WorkloadUnquarantined" for e in rt.events)
        assert "ns/bad" in admitted_set(rt)

    def test_operator_clear_requeues_immediately(self):
        rt = build_rt(0, mode="host", threshold=2, ttl_s=1e6)
        bad = _PoisonWorkload(
            namespace="ns", name="bad", queue_name="lq-0", priority=0,
            creation_time=0.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
        )
        rt.add_workload(bad)
        rt.run_until_idle()
        assert rt.quarantine.active("ns/bad", rt.clock.now())
        bad.poisoned = False
        assert rt.clear_quarantine("ns/bad") == ["ns/bad"]
        rt.run_until_idle()
        assert "ns/bad" in admitted_set(rt)
        assert rt.metrics.solver_quarantined_workloads.value() == 0

    def test_quarantine_journaled_and_recovered(self, tmp_path):
        rt = build_rt(0, mode="host", threshold=2, ttl_s=1e6)
        journal = Journal(str(tmp_path / "j")).open()
        rt.attach_journal(journal)
        bad = _PoisonWorkload(
            namespace="ns", name="bad", queue_name="lq-0", priority=0,
            creation_time=0.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
        )
        rt.add_workload(bad)
        rt.add_workload(make_wl("good", cq_index=1, t=1.0))
        rt.run_until_idle()
        assert rt.quarantine.active("ns/bad", rt.clock.now())
        journal.close()
        # crash + recover: the quarantine survives via the journal
        res = recover(None, str(tmp_path / "j"),
                      runtime=build_rt(0, mode="host"), strict=True)
        rt2 = res.runtime
        assert rt2.quarantine.active("ns/bad", 0.0)
        entry = rt2.quarantine.get("ns/bad")
        assert entry.strikes >= 2 and "quarantined" in entry.message
        res.journal.close()
        # and via the checkpoint (compaction must not release poison)
        state = ser.runtime_to_state(rt)
        rt3 = ser.runtime_from_state(json.loads(json.dumps(state)))
        assert rt3.quarantine.active("ns/bad", 0.0)

    def test_quarantine_state_follows_deletion(self):
        rt = build_rt(0, mode="host", threshold=1, ttl_s=1e6)
        bad = _PoisonWorkload(
            namespace="ns", name="bad", queue_name="lq-0", priority=0,
            creation_time=0.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
        )
        rt.add_workload(bad)
        rt.run_until_idle()
        assert len(rt.quarantine) == 1
        rt.delete_workload(bad)
        assert len(rt.quarantine) == 0


# ---- transactional apply (satellite bugfix) ----
class TestTransactionalApply:
    def test_raising_apply_mid_cycle_leaves_usage_consistent(self):
        """A durable-write hook that RAISES on one head mid-apply (two
        heads already committed, one still to go) must cost that head a
        requeue, not the cycle — and cached usage must equal the sum
        over admitted workloads at every point."""
        rt = build_rt(0, mode="host")
        broken = {"t2"}

        def apply_admission(wl):
            if wl.name in broken:
                raise RuntimeError("API server went away")
            return True

        rt.scheduler.apply_admission = apply_admission
        # one head per CQ: t2's raise lands MID-apply, between t0/t1's
        # commits and t3's
        for k in range(4):
            rt.add_workload(make_wl(f"t{k}", cq_index=k, t=float(k)))
        res = rt.schedule_once()
        rt.run_until_idle()  # settle (clears inflight markers); t2
        # keeps failing its durable write and keeps being retried
        violations = rt.check_invariants()
        assert not violations, violations
        adm = admitted_set(rt)
        assert adm == {"ns/t0", "ns/t1", "ns/t3"}
        assert {e.workload.name for e in res.admitted} == {"t0", "t1", "t3"}
        # the failed head carries the canonical reason and is requeued
        rec = rt.audit.latest("ns/t2")
        assert rec is not None
        assert rec.reason == InadmissibleReason.DURABLE_WRITE_FAILED
        assert rt.guard.contained_cycles == 0  # contained per head
        # the API heals: the requeued head admits on the next cycle
        broken.clear()
        rt.run_until_idle()
        assert "ns/t2" in admitted_set(rt)
        assert not rt.check_invariants()

    def test_raising_apply_every_time_never_corrupts(self):
        rt = build_rt(0, mode="host")

        def apply_admission(wl):
            raise RuntimeError("always down")

        rt.scheduler.apply_admission = apply_admission
        for k in range(4):
            rt.add_workload(make_wl(f"t{k}", cq_index=k % 4, t=float(k)))
        rt.run_until_idle()
        assert not rt.check_invariants()
        assert not admitted_set(rt)
        # nothing charged: every CQ's usage is zero
        for cached in rt.cache.cluster_queues.values():
            assert all(q == 0 for q in cached.usage.values())


# ---- /healthz degradation (satellite bugfix) ----
class TestHealthz:
    def _get(self, port):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz") as r:
            return json.loads(r.read())

    def test_degraded_while_circuit_open_and_while_quarantined(self):
        from kueue_tpu.server import KueueServer

        rt = build_rt(0, mode="auto")
        srv = KueueServer(runtime=rt, auto_reconcile=False)
        port = srv.start()
        try:
            body = self._get(port)
            assert body["status"] == "ok"
            assert body["solver"]["path"] == "device"
            # circuit opens -> degraded
            for _ in range(rt.guard.config.failure_threshold):
                rt.guard._note_failure("test outage", "raise")
            body = self._get(port)
            assert body["status"] == "degraded"
            assert body["solver"]["breaker"] == "open"
            assert body["solver"]["path"] == "host"
            # recovery -> ok again
            rt.guard._note_success()
            body = self._get(port)
            assert body["status"] == "ok"
            # quarantined workload -> degraded, cleared -> ok
            rt.add_workload(make_wl("q0"))
            rt.scheduler._do_quarantine(rt.workloads["ns/q0"], "test")
            rt.scheduler.on_quarantine(rt.workloads["ns/q0"], "test")
            body = self._get(port)
            assert body["status"] == "degraded"
            assert body["solver"]["quarantinedWorkloads"] == 1
            rt.clear_quarantine()
            body = self._get(port)
            assert body["status"] == "ok"
        finally:
            srv.stop()

    def test_quarantine_routes_and_dashboard_badge(self):
        from kueue_tpu.server import KueueClient, KueueServer

        rt = build_rt(0, mode="host", threshold=1, ttl_s=1e6)
        bad = _PoisonWorkload(
            namespace="ns", name="bad", queue_name="lq-0", priority=0,
            creation_time=0.0,
            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
        )
        rt.add_workload(bad)
        rt.run_until_idle()
        srv = KueueServer(runtime=rt, auto_reconcile=False)
        port = srv.start()
        try:
            client = KueueClient(f"http://127.0.0.1:{port}")
            out = client.quarantine_list()
            assert [q["key"] for q in out["items"]] == ["ns/bad"]
            assert out["solver"]["mode"] == "host"
            from kueue_tpu.server.dashboard import dashboard_payload

            payload = dashboard_payload(rt)
            assert payload["solver"]["quarantined"][0]["key"] == "ns/bad"
            bad.poisoned = False
            cleared = client.quarantine_clear("ns/bad")
            assert cleared["cleared"] == ["ns/bad"]
            assert client.quarantine_list()["items"] == []
        finally:
            srv.stop()


# ---- kueuectl quarantine ----
class TestKueuectlQuarantine:
    def test_offline_list_and_clear(self, tmp_path, capsys):
        from kueue_tpu.cli.__main__ import main as kueuectl

        rt = build_rt(0, mode="host", threshold=1, ttl_s=1e6)
        rt.add_workload(make_wl("w0"))
        rt.scheduler._do_quarantine(rt.workloads["ns/w0"], "bad object")
        state_path = tmp_path / "state.json"
        state_path.write_text(json.dumps(ser.runtime_to_state(rt)))

        assert kueuectl(["--state", str(state_path), "quarantine", "list"]) == 0
        out = capsys.readouterr().out
        assert "ns/w0" in out and "bad object" in out

        assert kueuectl(
            ["--state", str(state_path), "quarantine", "clear", "ns/w0"]
        ) == 0
        assert "cleared 1" in capsys.readouterr().out
        data = json.loads(state_path.read_text())
        assert data.get("quarantine", []) == []

    def test_server_mode(self, tmp_path, capsys):
        from kueue_tpu.cli.__main__ import main as kueuectl
        from kueue_tpu.server import KueueServer

        rt = build_rt(0, mode="host", threshold=1, ttl_s=1e6)
        rt.add_workload(make_wl("w0"))
        rt.scheduler._do_quarantine(rt.workloads["ns/w0"], "bad object")
        rt.scheduler.on_quarantine(rt.workloads["ns/w0"], "bad object")
        srv = KueueServer(runtime=rt, auto_reconcile=False)
        port = srv.start()
        try:
            url = f"http://127.0.0.1:{port}"
            assert kueuectl(
                ["--state", str(tmp_path / "s.json"),
                 "quarantine", "list", "--server", url]
            ) == 0
            out = capsys.readouterr().out
            assert "ns/w0" in out and "solver path: host" in out
            assert kueuectl(
                ["--state", str(tmp_path / "s.json"),
                 "quarantine", "clear", "--server", url]
            ) == 0
            assert "ns/w0" in capsys.readouterr().out
            assert len(rt.quarantine) == 0
        finally:
            srv.stop()


# ---- divergence verdict durability ----
class TestDivergenceDurability:
    def test_verdict_journaled_and_requarantines_on_recovery(self, tmp_path):
        rt = build_rt(0, mode="auto", k_div=1)
        journal = Journal(str(tmp_path / "j")).open()
        rt.attach_journal(journal)
        faults.arm("solver.device_wrong_answer", action=_corrupt_result)
        run_trace(rt, 0, waves=1)
        faults.reset()
        assert rt.guard.breaker.state == "quarantined"
        assert rt.last_solver_verdict is not None
        assert rt.last_solver_verdict["authority"] == "host"
        journal.close()
        res = recover(None, str(tmp_path / "j"),
                      runtime=build_rt(0, mode="auto"), strict=True)
        rt2 = res.runtime
        assert rt2.last_solver_verdict is not None
        # a kernel that answered wrong is not trusted again on restart
        assert rt2.guard.breaker.state == "quarantined"
        assert rt2.guard.path == "host"
        res.journal.close()


# ---- fault-point registry lint (satellite) ----
class TestFaultPointRegistry:
    def test_every_call_site_is_registered(self):
        """Static lint over the tree: every literal fault-point name at
        a ``faults.fire("...")`` / ``faults.transform("...")`` /
        ``fault_point="..."`` call site must be registered in
        FAULT_POINTS (mirroring the PR-2 reason-enum lint), and every
        registered point must have at least one production call site.
        Thin wrapper over the kueuelint ``fault-point`` rule."""
        from kueue_tpu.analysis import lint

        offenders = lint(rules=["fault-point"])
        assert not offenders, (
            "fault-point registry violations:\n"
            + "\n".join(str(f) for f in offenders)
        )

    def test_list_fault_points_sorted_and_documented(self):
        pts = faults.list_fault_points()
        assert pts == sorted(pts)
        assert all(faults.FAULT_POINTS[p] for p in pts)

    def test_transform_hook(self):
        assert faults.transform("solver.device_wrong_answer", 41) == 41
        faults.arm("solver.device_wrong_answer", action=lambda v: v + 1)
        assert faults.transform("solver.device_wrong_answer", 41) == 42
        assert faults.fired("solver.device_wrong_answer") == 1
        faults.arm("solver.device_wrong_answer")  # "crash"
        with pytest.raises(faults.InjectedCrash):
            faults.transform("solver.device_wrong_answer", 41)


# ---- quarantine list units ----
class TestQuarantineList:
    def test_strike_threshold_and_ttl(self):
        q = QuarantineList(threshold=3, ttl_s=100.0)
        assert q.strike("a") == 1
        assert q.strike("a") == 2
        assert q.strike("a") == 3
        q.add("a", "bad", now=10.0)
        assert q.active("a", 50.0)
        assert not q.active("a", 110.0)  # TTL lapsed (read-side)
        assert [e.key for e in q.expired(110.0)] == ["a"]
        entry = q.release("a")
        assert entry is not None and q.strikes("a") == 0

    def test_restore_roundtrip(self):
        q = QuarantineList()
        q.add("a", "bad", now=5.0)
        d = q.get("a").to_dict()
        q2 = QuarantineList()
        q2.restore(**d)
        assert q2.get("a").until == q.get("a").until


# ---- XLA compile time and the device deadline ----
class TestCompileOutsideDeadline:
    """On a running clock the guard takes XLA compile seconds out of
    its device deadline, and nothing else: a cold launch whose compile
    alone outlasts the deadline is kept, a warm launch that runs past
    it is still a failure."""

    DEADLINE_S = 0.05

    @staticmethod
    def _lowered(n):
        """A jit lowered (outside the window) for a shape no other test
        compiles, slow enough to compile that it outlasts DEADLINE_S."""
        from jax import lax

        from kueue_tpu._jax import jax, jnp

        def f(x):
            def body(i, x):
                return jnp.sin(x @ x.T) * 0.5 + x

            return lax.fori_loop(0, 600, body, x, unroll=True)

        x = jnp.ones((n, n), jnp.float32)
        return jax.jit(f).lower(x), x

    @staticmethod
    def _guarded(guard, fn, path):
        if path == "call":
            return guard.device_call(fn, label="t")
        return guard.device_join(
            guard.device_launch(fn, label="t"), lambda h: np.asarray(h)
        )

    @pytest.mark.parametrize("path, n", [("call", 5), ("launch", 7)])
    def test_cold_compile_is_not_counted(self, path, n):
        from kueue_tpu.core.guard import SolverGuard, compile_seconds
        from kueue_tpu.utils.clock import Clock

        guard = SolverGuard(
            clock=Clock(), config=GuardConfig(device_deadline_s=self.DEADLINE_S)
        )
        lowered, x = self._lowered(n)
        c0 = compile_seconds()
        out = self._guarded(guard, lambda: lowered.compile()(x), path)
        compiled_s = compile_seconds() - c0
        assert compiled_s > self.DEADLINE_S  # counted, this would fail
        assert out.result is not None
        assert guard.failovers == 0 and guard.device_solves == 1

    @pytest.mark.parametrize("path, n", [("call", 6), ("launch", 9)])
    def test_slow_warm_launch_still_fails(self, path, n):
        import time

        from kueue_tpu.core.guard import SolverGuard
        from kueue_tpu.utils.clock import Clock

        guard = SolverGuard(
            clock=Clock(), config=GuardConfig(device_deadline_s=self.DEADLINE_S)
        )
        lowered, x = self._lowered(n)
        compiled = lowered.compile()

        def slow():
            time.sleep(3 * self.DEADLINE_S)
            return compiled(x)

        out = self._guarded(guard, slow, path)
        assert out.result is None
        assert guard.failovers == 1 and guard.device_solves == 0
