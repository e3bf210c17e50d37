"""Gray-failure immunity (PR 20): the latency-aware health plane
(federation/health.py), the network chaos layer (testing/chaos.py),
adaptive per-call deadlines, hedged dispatch, health-aware scheduling,
and the acceptance properties — a limping worker answering just under
the old fixed deadline cannot drag the federation down, and hedging x
asymmetric loss x crash recovery still converge to exactly one
admission per workload."""

import math
import random
import threading

import numpy as np
import pytest

from kueue_tpu.admissionchecks.multikueue import MultiKueueCluster
from kueue_tpu.admissionchecks.multikueue_transport import (
    ClusterUnreachable,
    InProcessTransport,
    RemoteClient,
    TransportError,
)
from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.federation import FederationDispatcher
from kueue_tpu.federation.health import (
    DEGRADED,
    HEALTHY,
    LOST,
    HealthPlane,
)
from kueue_tpu.models import (
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    ResourceFlavor,
    Workload,
)
from kueue_tpu.models.cluster_queue import ResourceGroup
from kueue_tpu.models.workload import PodSet
from kueue_tpu.ops.global_kernel import rescore_pairs
from kueue_tpu.ops.global_np import rescore_np
from kueue_tpu.storage.journal import Journal
from kueue_tpu.storage.recovery import recover
from kueue_tpu.testing import faults
from kueue_tpu.testing.chaos import (
    AsymmetricLossTransport,
    LatencyTransport,
    RecordingTransport,
    SlowDripTransport,
    flapping_schedule,
)
from kueue_tpu.utils.clock import FakeClock


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---- shared harness (mirrors tests/test_federation.py) ----
def build_worker(clock, cpu="10"):
    rt = ClusterRuntime(clock=clock)
    rt.add_flavor(ResourceFlavor(name="default"))
    rt.add_cluster_queue(
        ClusterQueue(
            name="cq",
            namespace_selector={},
            resource_groups=(
                ResourceGroup(
                    ("cpu",), (FlavorQuotas.build("default", {"cpu": cpu}),)
                ),
            ),
        )
    )
    rt.add_local_queue(
        LocalQueue(namespace="ns", name="lq", cluster_queue="cq")
    )
    return rt


def wl(name, cpu="1", **kw):
    return Workload(
        namespace="ns", name=name, queue_name="lq",
        pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),), **kw,
    )


def federation(
    tmp_path=None,
    n_workers=2,
    clock=None,
    worker_cpu="10",
    chaos=None,  # {worker_name: transport_wrapper(inner, clock)}
    **disp_kw,
):
    """Federation harness with a chaos hook: ``chaos`` wraps the named
    workers' in-process transports in the given chaos transports."""
    clock = clock or FakeClock(0.0)
    chaos = chaos or {}
    workers = {}
    clusters = {}
    for i in range(n_workers):
        name = f"w{i + 1}"
        rt = build_worker(clock, cpu=worker_cpu)
        workers[name] = rt
        transport = InProcessTransport(rt)
        if name in chaos:
            transport = chaos[name](transport, clock)
        clusters[name] = MultiKueueCluster(name=name, transport=transport)
    mgr = ClusterRuntime(clock=clock)
    journal = None
    if tmp_path is not None:
        journal = Journal(
            str(tmp_path / "mgr-journal"), fsync_policy="never"
        ).open()
        mgr.attach_journal(journal)
    disp_kw.setdefault("worker_lost_timeout", 20.0)
    disp_kw.setdefault("max_backoff_s", 8.0)
    disp_kw.setdefault("drive_inprocess", True)
    disp = FederationDispatcher(mgr, clusters=clusters, **disp_kw)
    return mgr, disp, workers, clock, journal


def drive(mgr, clock, passes=6, advance=10.0):
    for _ in range(passes):
        mgr.run_until_idle()
        clock.advance(advance)
    mgr.run_until_idle()


def holders(workers, key):
    return sorted(n for n, rt in workers.items() if key in rt.workloads)


def assert_converged(mgr, workers, keys):
    """Exactly one admission per workload, every plane sound."""
    admitted = {k for k, w in mgr.workloads.items() if w.is_admitted}
    assert admitted == set(keys), (
        f"federated admitted set {sorted(admitted)} != {sorted(keys)}"
    )
    for key in keys:
        hold = holders(workers, key)
        assert len(hold) == 1, f"{key}: copies on {hold} (expected one)"
        rwl = workers[hold[0]].workloads[key]
        assert rwl.has_quota_reservation, f"{key}: copy not reserving"
    assert mgr.check_invariants() == []
    for name, rt in workers.items():
        assert rt.check_invariants() == [], f"worker {name}"


# ---- health plane state machine ----
class TestHealthPlane:
    def plane(self, clock=None, **kw):
        return HealthPlane(clock or FakeClock(0.0), **kw)

    def test_healthy_until_min_samples(self):
        hp = self.plane()
        hp.observe_rtt("w1", 9.0)
        hp.observe_rtt("w1", 9.0)
        assert hp.state("w1") == HEALTHY  # 2 < degrade_min_samples
        hp.observe_rtt("w1", 9.0)
        assert hp.state("w1") == DEGRADED

    def test_degrade_on_error_rate(self):
        hp = self.plane()
        hp.observe_rtt("w1", 0.01)
        hp.observe_error("w1")
        hp.observe_error("w1")
        # 2/3 failures >= 0.5 threshold
        assert hp.state("w1") == DEGRADED

    def test_probation_clears_after_hold_with_clean_window(self):
        clock = FakeClock(0.0)
        hp = self.plane(clock, window=4, probation_hold_s=30.0)
        for _ in range(4):
            hp.observe_rtt("w1", 9.0)
        assert hp.state("w1") == DEGRADED
        # clean samples flush the window, but the hold still gates
        for _ in range(4):
            hp.observe_rtt("w1", 0.01)
        assert hp.state("w1") == DEGRADED
        clock.advance(31.0)
        hp.observe_rtt("w1", 0.01)
        assert hp.state("w1") == HEALTHY

    def test_lost_on_error_streak_recovers_via_probation(self):
        clock = FakeClock(0.0)
        hp = self.plane(clock, lost_error_streak=4)
        for _ in range(4):
            hp.observe_error("w1")
        assert hp.state("w1") == LOST
        # first success re-enters DEGRADED (probation), never HEALTHY
        hp.observe_rtt("w1", 0.01)
        assert hp.state("w1") == DEGRADED

    def test_heartbeat_slack_breach_degrades_idle_worker(self):
        clock = FakeClock(0.0)
        hp = self.plane(
            clock, heartbeat_interval_s=10.0, slack_factor=3.0
        )
        hp.observe_rtt("w1", 0.01)
        assert hp.state("w1") == HEALTHY
        clock.advance(31.0)  # > 3 * 10s without contact
        assert hp.state("w1") == DEGRADED

    def test_flapping_extends_probation_hold(self):
        def flap_once(hp, clock):
            # breach -> degraded, then clean window + hold -> healthy
            for _ in range(4):
                hp.observe_rtt("w1", 9.0)
            assert hp.state("w1") == DEGRADED
            for _ in range(4):
                hp.observe_rtt("w1", 0.01)
            clock.advance(11.0)
            hp.observe_rtt("w1", 0.01)
            assert hp.state("w1") == HEALTHY

        # each flap cycle costs two transitions (enter + leave
        # probation); threshold 5 lets two full cycles recover at the
        # base hold, and the THIRD degradation trips flap detection
        kw = dict(
            window=4, probation_hold_s=10.0, flap_window_s=10_000.0,
            flap_threshold=5, flap_extend_factor=4.0,
        )
        # worker A: one flap cycle, recovery at the base hold
        clock_a = FakeClock(0.0)
        a = self.plane(clock_a, **kw)
        flap_once(a, clock_a)

        # worker B: two flap cycles, then the third degradation holds
        # past the base hold (flap detection extended it 4x)
        clock_b = FakeClock(0.0)
        b = self.plane(clock_b, **kw)
        for _ in range(2):
            flap_once(b, clock_b)
        for _ in range(4):
            b.observe_rtt("w1", 9.0)
        assert b.state("w1") == DEGRADED
        for _ in range(4):
            b.observe_rtt("w1", 0.01)
        clock_b.advance(11.0)  # base hold elapsed — NOT enough now
        b.observe_rtt("w1", 0.01)
        assert b.state("w1") == DEGRADED
        clock_b.advance(40.0)  # the extended (4x) hold elapses
        b.observe_rtt("w1", 0.01)
        assert b.state("w1") == HEALTHY

    def test_adaptive_deadline_clamp(self):
        hp = self.plane(
            deadline_k=3.0, deadline_floor_s=1.0, deadline_cap_s=10.0
        )
        # no samples: the conservative full cap
        assert hp.deadline_s("w1") == 10.0
        for _ in range(8):
            hp.observe_rtt("w1", 0.05)
        # 3 * 0.05 < floor -> floor
        assert hp.deadline_s("w1") == 1.0
        for _ in range(64):
            hp.observe_rtt("w2", 1.0)
        # 3 * 1.0 in band -> k * p99
        assert hp.deadline_s("w2") == pytest.approx(3.0)
        for _ in range(8):
            hp.observe_rtt("w3", 9.0)
        assert hp.deadline_s("w3") == 10.0  # capped
        # per-call cap override (heartbeat probes)
        assert hp.deadline_s("w3", cap_s=2.0) == 2.0

    def test_hedge_delay_gated_on_samples_and_budget(self):
        hp = self.plane(hedge_min_samples=4, hedge_budget=0.05)
        assert hp.hedge_delay_s("w1") is None
        for _ in range(4):
            hp.observe_rtt("w1", 0.5)
        assert hp.hedge_delay_s("w1") == pytest.approx(0.5)
        # exhaust the fleet-wide budget: 5 hedges over 100 calls
        for _ in range(100):
            hp.record_call()
        for _ in range(5):
            hp.record_hedge()
        assert hp.hedge_delay_s("w1") is None
        assert hp.hedge_rate() == pytest.approx(0.05)

    def test_snapshot_zero_materialized(self):
        hp = self.plane()
        snap = hp.snapshot("never-seen")
        assert snap == {
            "state": HEALTHY, "ewmaRtt": 0.0, "rttP50": 0.0,
            "rttP95": 0.0, "rttP99": 0.0, "errorRate": 0.0,
            "samples": 0,
        }


# ---- chaos transports ----
class _StubInner:
    """Innermost transport stub: counts calls, returns a sentinel."""

    runtime = None
    deadline_s = None

    def __init__(self):
        self.calls = []

    def get_workload(self, key):
        self.calls.append(("get_workload", key))
        return "remote-copy"

    def delete_workload(self, key):
        self.calls.append(("delete_workload", key))


class TestChaosTransports:
    def test_latency_under_deadline_advances_clock_and_forwards(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = LatencyTransport(inner, clock, delay_s=3.0)
        assert t.get_workload("k") == "remote-copy"
        assert clock.now() == pytest.approx(3.0)
        assert inner.calls == [("get_workload", "k")]
        assert faults.fired("chaos.latency") == 0  # unarmed: free

    def test_latency_request_timeout_never_reaches_worker(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = LatencyTransport(inner, clock, delay_s=12.0)  # default 10s
        with pytest.raises(TransportError):
            t.get_workload("k")
        assert inner.calls == []  # dropped before the worker
        assert clock.now() == pytest.approx(10.0)  # full deadline burned
        assert t.timeouts == 1

    def test_latency_response_timeout_lands_then_raises(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = LatencyTransport(
            inner, clock, delay_s=12.0, direction="response"
        )
        with pytest.raises(TransportError):
            t.delete_workload("k")
        # the mutation LANDED; only the ack was lost
        assert inner.calls == [("delete_workload", "k")]

    def test_latency_tracks_threaded_deadline_fraction(self):
        clock = FakeClock(0.0)
        t = LatencyTransport(
            _StubInner(), clock, deadline_fraction=0.99
        )
        t.deadline_s = 4.0  # what RemoteClient._invoke does per-call
        t.get_workload("k")
        assert clock.now() == pytest.approx(3.96)
        t.deadline_s = None  # back to the constructor default
        t.get_workload("k")
        assert clock.now() == pytest.approx(3.96 + 9.9)

    def test_slow_drip_progresses_to_timeout(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = SlowDripTransport(
            inner, clock, step_s=4.0, default_deadline_s=10.0
        )
        t.get_workload("a")  # 0s
        t.get_workload("b")  # 4s
        t.get_workload("c")  # 8s
        assert clock.now() == pytest.approx(12.0)
        with pytest.raises(TransportError):
            t.get_workload("d")  # 12s >= 10s deadline
        assert len(inner.calls) == 3

    def test_slow_drip_max_caps_the_drip(self):
        clock = FakeClock(0.0)
        t = SlowDripTransport(_StubInner(), clock, step_s=4.0, max_s=6.0)
        for key in "abcdef":
            t.get_workload(key)
        assert t.timeouts == 0  # capped under the deadline forever

    def test_asymmetric_loss_response_lands_then_drops(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = AsymmetricLossTransport(inner, clock, direction="response")
        with pytest.raises(TransportError):
            t.delete_workload("k")
        assert inner.calls == [("delete_workload", "k")]
        assert t.dropped == 1
        assert clock.now() == pytest.approx(10.0)

    def test_asymmetric_loss_request_never_lands(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = AsymmetricLossTransport(inner, clock, direction="request")
        with pytest.raises(TransportError):
            t.get_workload("k")
        assert inner.calls == []

    def test_asymmetric_loss_probabilistic(self):
        clock = FakeClock(0.0)
        inner = _StubInner()
        t = AsymmetricLossTransport(
            inner, clock, p=0.5, rng=random.Random(7)
        )
        outcomes = []
        for i in range(20):
            try:
                t.get_workload(str(i))
                outcomes.append(True)
            except TransportError:
                outcomes.append(False)
        assert any(outcomes) and not all(outcomes)
        assert t.dropped == outcomes.count(False)

    def test_flapping_schedule_duty_cycle(self):
        sched = flapping_schedule(5.0, period_s=10.0, duty=0.3)
        assert sched(0.0) == 5.0
        assert sched(2.9) == 5.0
        assert sched(3.1) == 0.0
        assert sched(12.0) == 5.0  # next period's bad window

    def test_recording_transport_sees_injected_delay(self):
        clock = FakeClock(0.0)
        sink = []
        t = RecordingTransport(
            LatencyTransport(_StubInner(), clock, delay_s=2.5),
            clock,
            sink=sink,
        )
        t.get_workload("k")
        with pytest.raises(TransportError):
            # shrink the threaded deadline below the delay
            t.deadline_s = 1.0
            t.get_workload("k")
        # both the success (2.5s) and the timeout (1.0s) are recorded
        assert sink == [pytest.approx(2.5), pytest.approx(1.0)]

    def test_chaos_fault_points_armable(self):
        clock = FakeClock(0.0)
        t = AsymmetricLossTransport(
            _StubInner(), clock, direction="response"
        )
        faults.arm("chaos.drop_response", action="crash")
        with pytest.raises(faults.InjectedCrash):
            t.delete_workload("k")


# ---- adaptive deadline threading ----
class _DeadlineProbe:
    """Transport wrapper recording the threaded per-call deadline."""

    def __init__(self, inner, clock=None):
        self.inner = inner
        self.seen = []

    @property
    def runtime(self):
        return self.inner.runtime

    @property
    def deadline_s(self):
        return getattr(self.inner, "deadline_s", None)

    @deadline_s.setter
    def deadline_s(self, value):
        self.inner.deadline_s = value

    def __getattr__(self, name):
        fn = getattr(self.inner, name)

        def wrapped(*args):
            self.seen.append((name, self.deadline_s))
            return fn(*args)

        return wrapped


class TestAdaptiveDeadlines:
    def test_fixed_mode_threads_no_deadline(self):
        probe = {}

        def wrap(inner, clock):
            probe["t"] = _DeadlineProbe(inner)
            return probe["t"]

        mgr, disp, workers, clock, _ = federation(
            n_workers=1, chaos={"w1": wrap}, adaptive_deadlines=False,
            hedging=False,
        )
        mgr.add_workload(wl("fixed"))
        drive(mgr, clock, passes=2)
        assert probe["t"].seen, "no wire exchanges happened"
        assert all(d is None for _op, d in probe["t"].seen), (
            "fixed-timeout baseline must ride the transport default"
        )

    def test_adaptive_mode_threads_clamped_deadline(self):
        probe = {}

        def wrap(inner, clock):
            probe["t"] = _DeadlineProbe(inner)
            return probe["t"]

        mgr, disp, workers, clock, _ = federation(
            n_workers=1, chaos={"w1": wrap}, hedging=False,
        )
        # seed the health plane below the floor: deadline clamps there
        for _ in range(8):
            disp.worker_health.observe_rtt("w1", 0.01)
        mgr.add_workload(wl("adaptive"))
        drive(mgr, clock, passes=2)
        deadlines = [d for _op, d in probe["t"].seen if d is not None]
        assert deadlines, "adaptive deadlines never threaded"
        assert all(d <= 2.0 for d in deadlines), (
            f"expected floor/probe-cap deadlines, saw {deadlines}"
        )

    def test_heartbeat_probe_uses_probe_cap(self):
        probe = {}

        def wrap(inner, clock):
            probe["t"] = _DeadlineProbe(inner)
            return probe["t"]

        mgr, disp, workers, clock, _ = federation(
            n_workers=1, chaos={"w1": wrap}, hedging=False,
            probe_deadline_s=2.0,
        )
        # plenty of slow-but-healthy samples: full deadline would be 10
        for _ in range(8):
            disp.worker_health.observe_rtt("w1", 4.0)
        mgr.run_until_idle()
        clock.advance(31.0)  # past the heartbeat interval
        probe["t"].seen.clear()
        mgr.run_until_idle()
        beats = [
            d for op, d in probe["t"].seen if op == "list_workload_keys"
        ]
        assert beats and all(d == 2.0 for d in beats), (
            f"heartbeat probes must be capped at probe_deadline_s: {beats}"
        )


# ---- non-blocking heartbeats (satellite: step never stalls) ----
class TestHeartbeatBudget:
    def test_black_holed_worker_costs_at_most_probe_deadline(self):
        """Regression: a black-holed worker used to burn the full 10 s
        transport timeout inside EVERY step's heartbeat sweep. Probes
        are now capped at probe_deadline_s and budgeted per step."""
        mgr, disp, workers, clock, _ = federation(
            n_workers=3,
            chaos={
                "w3": lambda inner, clock: LatencyTransport(
                    inner, clock, delay_s=1e9
                )
            },
            probe_deadline_s=2.0,
            heartbeat_probe_budget=1,
        )
        w = wl("job-a")
        mgr.add_workload(w)
        drive(mgr, clock, passes=4)  # dispatch + detect the black hole
        assert not disp.clusters["w3"].client.active
        # steady state: one heartbeat sweep with the black hole in
        # backoff-elapsed state costs at most ONE probe deadline
        clock.advance(31.0)
        t0 = clock.now()
        mgr.run_until_idle()
        cost = clock.now() - t0
        assert cost <= 2.0 + 1e-9, (
            f"heartbeat sweep burned {cost:.1f}s of step time"
        )
        # the healthy workers still converged the dispatch
        assert w.is_admitted

    def test_probe_budget_zero_skips_lost_worker_probes(self):
        mgr, disp, workers, clock, _ = federation(
            n_workers=2,
            chaos={
                "w2": lambda inner, clock: LatencyTransport(
                    inner, clock, delay_s=1e9
                )
            },
            probe_deadline_s=2.0,
            heartbeat_probe_budget=0,
        )
        # probation keeps every dispatch (and so every retraction) off
        # w2; mark it lost so the only possible w2 wire exchange left
        # is a heartbeat reconnect probe
        for _ in range(8):
            disp.worker_health.observe_rtt("w2", 9.0)
        disp.clusters["w2"].mark_lost(clock.now())
        w = wl("job-a")
        mgr.add_workload(w)
        drive(mgr, clock, passes=3)
        assert w.is_admitted
        assert not disp.clusters["w2"].client.active
        clock.advance(31.0)
        t0 = clock.now()
        mgr.run_until_idle()
        assert clock.now() - t0 == pytest.approx(0.0), (
            "budget=0 must skip reconnect probes entirely"
        )


# ---- hedged dispatch ----
class _ScriptedTransport:
    """Succeeds iff the threaded deadline is >= ``needs_s``."""

    runtime = None
    deadline_s = None

    def __init__(self, needs_s):
        self.needs_s = needs_s
        self.attempts = []

    def get_workload(self, key):
        self.attempts.append(self.deadline_s)
        d = 10.0 if self.deadline_s is None else self.deadline_s
        if d < self.needs_s:
            raise TransportError(f"deadline {d} < needs {self.needs_s}")
        return "remote-copy"


class TestHedging:
    def client(self, transport):
        return RemoteClient(transport, FakeClock(0.0))

    def test_backup_wins_after_primary_misses_hedge_delay(self):
        t = _ScriptedTransport(needs_s=3.0)
        c = self.client(t)
        out = c.call("get_workload", "k", deadline_s=5.0, hedge_delay_s=1.0)
        assert out == "remote-copy"
        assert t.attempts == [1.0, 5.0]  # primary bounded, backup full
        assert c.last_hedge == "won"
        # the missed hedge delay is NOT charged to connectivity
        assert c.active and c.failed_attempts == 0
        assert faults.fired("multikueue.hedge") == 0  # unarmed: free

    def test_backup_failure_is_the_calls_verdict(self):
        t = _ScriptedTransport(needs_s=30.0)  # hopeless
        c = self.client(t)
        with pytest.raises(ClusterUnreachable):
            c.call("get_workload", "k", deadline_s=5.0, hedge_delay_s=1.0)
        assert c.last_hedge == "lost"
        assert c.failed_attempts == 1  # charged exactly once

    def test_no_hedge_delay_no_backup(self):
        t = _ScriptedTransport(needs_s=30.0)
        c = self.client(t)
        with pytest.raises(ClusterUnreachable):
            c.call("get_workload", "k", deadline_s=5.0)
        assert t.attempts == [5.0]
        assert c.last_hedge is None

    def test_dispatcher_hedges_and_stays_in_budget(self):
        """End-to-end: a worker whose exchanges run just past the p95
        hedge delay (but inside the adaptive deadline) triggers hedges
        through the dispatcher; the accounting lands in the health
        plane and stays within the budget assertion's reach."""
        mgr, disp, workers, clock, _ = federation(
            n_workers=1,
            chaos={
                "w1": lambda inner, clock: LatencyTransport(
                    inner, clock, delay_s=1.0
                )
            },
        )
        # seed: p95=0.5 -> hedge delay 0.5 (missed by the 1.0s limp),
        # p99=0.5 -> deadline clamp(1.5, 1, 10)=1.5 (backup succeeds)
        for _ in range(8):
            disp.worker_health.observe_rtt("w1", 0.5)
        w = wl("hedged")
        mgr.add_workload(w)
        drive(mgr, clock, passes=3, advance=0.0)
        assert w.is_admitted
        assert disp.worker_health.hedges_total > 0
        assert disp.worker_health.hedge_rate() <= 0.5  # sane accounting


# ---- health-aware scheduling ----
class TestHealthAwareScheduling:
    def test_probation_excludes_worker_from_new_dispatches(self):
        mgr, disp, workers, clock, _ = federation(n_workers=3)
        for _ in range(8):
            disp.worker_health.observe_rtt("w2", 9.0)
        assert disp.worker_health.state("w2") == DEGRADED
        keys = []
        for i in range(4):
            w = wl(f"job-{i}")
            keys.append(w.key)
            mgr.add_workload(w)
        drive(mgr, clock, passes=4)
        assert_converged(mgr, workers, keys)
        assert not workers["w2"].workloads, (
            "probation worker received new dispatches"
        )

    def test_all_degraded_falls_back_to_dispatching(self):
        """A slow federation beats a stalled one: when probation would
        empty the fleet, degraded workers stay in rotation."""
        mgr, disp, workers, clock, _ = federation(n_workers=2)
        for name in workers:
            for _ in range(8):
                disp.worker_health.observe_rtt(name, 9.0)
        w = wl("still-runs")
        mgr.add_workload(w)
        drive(mgr, clock, passes=3)
        assert w.is_admitted

    def test_probation_keeps_syncing_existing_placements(self):
        mgr, disp, workers, clock, _ = federation(n_workers=2)
        w = wl("placed-then-gray")
        mgr.add_workload(w)
        drive(mgr, clock, passes=3)
        winner = disp.states[w.key].winner
        # the winner limps AFTER placement: probation, not retraction
        for _ in range(8):
            disp.worker_health.observe_rtt(winner, 9.0)
        drive(mgr, clock, passes=2)
        assert_converged(mgr, workers, [w.key])
        assert holders(workers, w.key) == [winner], (
            "probation must keep existing placements, not retract them"
        )

    def test_rescore_degraded_penalty_device_matches_numpy(self):
        rng = np.random.default_rng(20)
        for _ in range(10):
            w = int(rng.integers(1, 12))
            c = int(rng.integers(2, 9))
            tta = rng.integers(0, 10_000, size=(w, c)).astype(np.int64)
            score = rng.integers(0, 100, size=(w, c)).astype(np.int64)
            valid = rng.random((w, c)) > 0.2
            current = rng.integers(-1, c, size=w).astype(np.int32)
            rotation = rng.integers(0, c, size=w).astype(np.int32)
            degraded = rng.random(c) > 0.5
            dev = rescore_pairs(
                tta, score, valid, current, rotation, 500,
                degraded=degraded, degraded_penalty_ms=120_000,
            )
            ref = rescore_np(
                tta, score, valid, current, rotation, 500,
                degraded=degraded, degraded_penalty_ms=120_000,
            )
            for field in ("best", "best_key", "gain_ms", "rebalance"):
                assert np.array_equal(
                    getattr(dev, field), getattr(ref, field)
                ), field

    def test_rescore_penalty_moves_wins_off_degraded_clusters(self):
        # two clusters, equal forecasts: without the penalty cluster 0
        # wins on rotation; with cluster 0 degraded, cluster 1 wins
        tta = np.array([[100, 100]], dtype=np.int64)
        score = np.zeros((1, 2), dtype=np.int64)
        valid = np.ones((1, 2), dtype=bool)
        current = np.array([-1], dtype=np.int32)
        rotation = np.zeros(1, dtype=np.int32)
        base = rescore_np(tta, score, valid, current, rotation, 0)
        assert base.best[0] == 0
        shifted = rescore_np(
            tta, score, valid, current, rotation, 0,
            degraded=np.array([True, False]),
            degraded_penalty_ms=120_000,
        )
        assert shifted.best[0] == 1

    def test_rescore_penalty_omitted_is_all_healthy(self):
        rng = np.random.default_rng(7)
        tta = rng.integers(0, 1000, size=(4, 3)).astype(np.int64)
        score = rng.integers(0, 10, size=(4, 3)).astype(np.int64)
        valid = np.ones((4, 3), dtype=bool)
        current = np.array([-1, 0, 1, 2], dtype=np.int32)
        rotation = np.zeros(4, dtype=np.int32)
        a = rescore_np(tta, score, valid, current, rotation, 100)
        b = rescore_np(
            tta, score, valid, current, rotation, 100,
            degraded=np.zeros(3, dtype=bool), degraded_penalty_ms=120_000,
        )
        for field in ("best", "best_key", "gain_ms", "rebalance"):
            assert np.array_equal(getattr(a, field), getattr(b, field))


# ---- backoff jitter + probe cap (satellite: property tests) ----
class TestBackoffProperties:
    def test_backoff_windows_respect_jitter_bounds(self):
        """Property: after the n-th consecutive failure the wait is in
        [min(cap, b*2^(n-1)), min(cap, b*2^(n-1)) * (1 + jitter))."""
        for seed in range(40):
            clock = FakeClock(1000.0)
            c = RemoteClient(
                _ScriptedTransport(needs_s=0.0), clock,
                base_backoff_s=1.0, max_backoff_s=300.0, jitter=0.1,
                rng=random.Random(seed),
            )
            for n in range(1, 13):
                c._record_failure()
                delay = c.next_retry_at - clock.now()
                lo = min(300.0, 1.0 * 2 ** (n - 1))
                hi = lo * 1.1
                assert lo <= delay < hi, (
                    f"seed={seed} n={n}: {delay} not in [{lo}, {hi})"
                )

    def test_zero_jitter_is_exact_exponential(self):
        clock = FakeClock(0.0)
        c = RemoteClient(
            _ScriptedTransport(needs_s=0.0), clock,
            base_backoff_s=2.0, max_backoff_s=100.0, jitter=0.0,
        )
        expected = [2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 100.0, 100.0]
        for want in expected:
            c._record_failure()
            assert c.next_retry_at - clock.now() == pytest.approx(want)

    def test_single_reconnect_probe_under_concurrent_callers(self):
        clock = FakeClock(0.0)
        entered = threading.Event()
        release = threading.Event()

        class _Blocking:
            runtime = None
            deadline_s = None

            def get_workload(self, key):
                entered.set()
                assert release.wait(timeout=10.0)
                return "ok"

        c = RemoteClient(_Blocking(), clock, max_inflight_probes=1)
        c._record_failure()  # lost; backoff from t=0
        clock.advance(100.0)  # backoff elapsed: next call is the probe
        results = []
        t = threading.Thread(
            target=lambda: results.append(c.call("get_workload", "k"))
        )
        t.start()
        assert entered.wait(timeout=10.0)
        # the probe slot is held: every concurrent caller is refused
        for _ in range(3):
            with pytest.raises(ClusterUnreachable) as ei:
                c.call("get_workload", "k")
            assert "probe already in flight" in str(ei.value)
        release.set()
        t.join(timeout=10.0)
        assert results == ["ok"]
        assert c.active  # probe success restored the cluster
        # slot released: a fresh loss allows a fresh probe
        c._record_failure()
        clock.advance(100.0)
        release.set()
        assert c.call("get_workload", "k") == "ok"

    def test_probe_cap_scales_with_max_inflight(self):
        clock = FakeClock(0.0)
        gate = threading.Event()
        entered = threading.Semaphore(0)

        class _Blocking:
            runtime = None
            deadline_s = None

            def get_workload(self, key):
                entered.release()
                assert gate.wait(timeout=10.0)
                return "ok"

        c = RemoteClient(_Blocking(), clock, max_inflight_probes=2)
        c._record_failure()
        clock.advance(100.0)
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(c.call("get_workload", "k"))
            )
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        assert entered.acquire(timeout=10.0)
        assert entered.acquire(timeout=10.0)
        with pytest.raises(ClusterUnreachable):
            c.call("get_workload", "k")  # third concurrent probe refused
        gate.set()
        for t in threads:
            t.join(timeout=10.0)
        assert results == ["ok", "ok"]


# ---- acceptance: the limping worker ----
class TestLimpingWorkerAcceptance:
    def _run(self, limping, adaptive, n_workers=4, n_wl=12):
        chaos = {}
        if limping:
            chaos["w1"] = lambda inner, clock: LatencyTransport(
                inner, clock, deadline_fraction=0.99
            )
        mgr, disp, workers, clock, _ = federation(
            n_workers=n_workers,
            worker_cpu="20",
            chaos=chaos,
            adaptive_deadlines=adaptive,
            hedging=adaptive,
            health_plane_kw=(
                None if adaptive else {"degrade_min_samples": 10**9}
            ),
        )
        keys = []
        for i in range(n_wl):
            w = wl(f"limp-{i:02d}")
            keys.append(w.key)
            mgr.add_workload(w)
        passes = 0
        admitted = set()
        t0 = clock.now()
        for _ in range(30):
            mgr.run_until_idle()
            passes += 1
            admitted = {
                k for k, w in mgr.workloads.items() if w.is_admitted
            }
            if admitted == set(keys):
                break
            clock.advance(5.0)
        assert admitted == set(keys)
        assert_converged(mgr, workers, keys)
        return passes, clock.now() - t0, admitted

    def test_limping_worker_sustains_70pct_of_healthy_rate(self):
        """Acceptance: one worker limping at 0.99x the old fixed
        deadline; with the health plane + adaptive deadlines + hedging
        the federation still admits at >= 70% of the healthy fleet's
        per-pass rate, on the identical admitted set."""
        h_passes, _h_sim, h_admitted = self._run(
            limping=False, adaptive=True
        )
        l_passes, _l_sim, l_admitted = self._run(
            limping=True, adaptive=True
        )
        assert l_admitted == h_admitted
        healthy_rate = len(h_admitted) / h_passes
        limping_rate = len(l_admitted) / l_passes
        assert limping_rate >= 0.7 * healthy_rate, (
            f"limping fleet admitted at {limping_rate:.2f}/pass vs "
            f"healthy {healthy_rate:.2f}/pass"
        )

    def test_immunity_beats_fixed_timeouts_on_wall_cost(self):
        """The fixed vs adaptive A/B, at test scale: the fixed
        10 s-timeout configuration burns far more simulated time on
        the limping wire than the adaptive+probation configuration."""
        _passes_f, sim_fixed, a_fixed = self._run(
            limping=True, adaptive=False
        )
        _passes_a, sim_adaptive, a_adaptive = self._run(
            limping=True, adaptive=True
        )
        assert a_fixed == a_adaptive  # immunity never costs correctness
        assert sim_adaptive < sim_fixed, (
            f"adaptive {sim_adaptive:.1f}s vs fixed {sim_fixed:.1f}s"
        )


# ---- acceptance: exactly-once under hedging x loss x crash ----
def crash_recover_manager(journal, tmp_path, clusters, clock, **disp_kw):
    journal.close()
    mgr2 = ClusterRuntime(clock=clock)
    res = recover(
        None, str(tmp_path / "mgr-journal"), runtime=mgr2, strict=True
    )
    mgr2.attach_journal(res.journal)
    disp_kw.setdefault("worker_lost_timeout", 20.0)
    disp_kw.setdefault("max_backoff_s", 8.0)
    disp_kw.setdefault("drive_inprocess", True)
    disp2 = FederationDispatcher(mgr2, clusters=clusters, **disp_kw)
    return mgr2, disp2, res.journal


class TestExactlyOnceUnderChaos:
    @pytest.mark.parametrize("seed", range(4))
    def test_asymmetric_response_loss_converges_exactly_once(self, seed):
        """Responses from w1 drop 40% of the time: every landed-but-
        unacked mutation must be deduplicated by name+fence (and
        404==ack for retractions) on the retry path."""
        mgr, disp, workers, clock, _ = federation(
            n_workers=2,
            chaos={
                "w1": lambda inner, clock: AsymmetricLossTransport(
                    inner, clock, p=0.4, rng=random.Random(seed)
                )
            },
        )
        keys = []
        for i in range(4):
            w = wl(f"lossy-{i}")
            keys.append(w.key)
            mgr.add_workload(w)
        drive(mgr, clock, passes=12)
        assert_converged(mgr, workers, keys)

    def test_crash_at_hedge_point_recovers_exactly_once(self, tmp_path):
        """The dispatcher dies at the instant a hedge fires (primary
        timed out, backup about to go): recovery must re-dispatch and
        converge to exactly one admission."""
        mgr, disp, workers, clock, journal = federation(
            tmp_path=tmp_path,
            n_workers=2,
            chaos={
                "w1": lambda inner, clock: LatencyTransport(
                    inner, clock, delay_s=1.0
                )
            },
        )
        for _ in range(8):
            disp.worker_health.observe_rtt("w1", 0.5)
        w = wl("hedge-crash")
        mgr.add_workload(w)
        faults.arm("multikueue.hedge", action="crash")
        with pytest.raises(faults.InjectedCrash):
            drive(mgr, clock, passes=3, advance=0.0)
        faults.reset()
        mgr2, disp2, j2 = crash_recover_manager(
            journal, tmp_path, disp.clusters, clock
        )
        drive(mgr2, clock, passes=6)
        assert_converged(mgr2, workers, [w.key])
        j2.close()

    def test_crash_at_drop_response_recovers_exactly_once(self, tmp_path):
        """The hardest window: the mutation LANDED on w1, the response
        was dropped, and the dispatcher crashed before journaling any
        of it. Recovery + (healed network) must converge to exactly
        one admission with no duplicate copy left anywhere."""
        mgr, disp, workers, clock, journal = federation(
            tmp_path=tmp_path,
            n_workers=2,
            chaos={
                "w1": lambda inner, clock: AsymmetricLossTransport(
                    inner, clock, p=1.0
                )
            },
        )
        w = wl("landed-unacked")
        mgr.add_workload(w)
        faults.arm("chaos.drop_response", action="crash")
        with pytest.raises(faults.InjectedCrash):
            drive(mgr, clock, passes=3)
        faults.reset()
        # the network heals across the restart
        chaos_t = disp.clusters["w1"].client.transport
        chaos_t.p = 0.0
        mgr2, disp2, j2 = crash_recover_manager(
            journal, tmp_path, disp.clusters, clock
        )
        drive(mgr2, clock, passes=8)
        assert_converged(mgr2, workers, [w.key])
        j2.close()

    @pytest.mark.parametrize("seed", range(3))
    def test_hedging_under_flap_converges_exactly_once(self, seed):
        """Hedged dispatch against a flapping limper (bad half of every
        window) across seeds: convergence, exactly-once, and the
        fleet-wide hedge accounting stays coherent."""
        def flappy(inner, clock):
            return LatencyTransport(
                inner, clock,
                schedule=flapping_schedule(3.0, period_s=40.0, duty=0.5),
            )

        mgr, disp, workers, clock, _ = federation(
            n_workers=3, chaos={"w1": flappy},
        )
        rng = random.Random(seed)
        keys = []
        for i in range(5):
            w = wl(f"flap-{seed}-{i}", priority=rng.randrange(5))
            keys.append(w.key)
            mgr.add_workload(w)
        drive(mgr, clock, passes=10, advance=7.0)
        assert_converged(mgr, workers, keys)
        hp = disp.worker_health
        assert 0.0 <= hp.hedge_rate() <= 1.0
        assert hp.hedges_total <= hp.calls_total
