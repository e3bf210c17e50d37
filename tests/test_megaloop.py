"""Device-resident admission megaloop — ops/megaloop_kernel +
core/drain.launch_drain_megaloop + ClusterRuntime._megaloop_bulk_drain.

Three layers of the serial==megaloop property, mirroring
tests/test_pipeline.py:

1. KERNEL: one fused K-round launch decides bit-for-bit what K chained
   serial ``launch_drain(max_cycles=chunk)`` rounds decide, per-round
   stamps, cursors, stuck sets and final usage included — checked
   against both the serial chain and the numpy mirror
   ops/megaloop_np.solve_megaloop_np (which literally IS the serial
   loop over suffix-trimmed queues; KERNEL_MIRRORS entry).
2. RUNTIME: the megaloop drain loop produces the BIT-FOR-BIT same
   admitted set, journal record sequence and audit records as the
   serial chunked loop on the same seeded traces, and the per-round
   conflict check truncates the batch under interference instead of
   shipping stale decisions.
3. CHAOS: a crash at either new fault point
   (``cycle.megaloop_launched``, ``cycle.megaloop_commit_round``),
   followed by journal recovery and a rerun, converges to the serial
   loop's admitted set.
"""

import numpy as np
import pytest

from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.core.drain import (
    launch_drain,
    launch_drain_megaloop,
    run_drain_megaloop_host,
)
from kueue_tpu.core.guard import RoundsTuner, SolverGuard
from kueue_tpu.core.pipeline import outcome_signature, speculative_snapshot
from kueue_tpu.core.queue_manager import queue_order_timestamp
from kueue_tpu.core.snapshot import take_snapshot
from kueue_tpu.storage import Journal, recover
from kueue_tpu.testing import faults
from kueue_tpu.utils.clock import FakeClock

from tests.test_pipeline import (
    CHUNK,
    THRESHOLD,
    _OpenGate,
    admitted,
    audit_dump,
    build_rt,
    journal_sequence,
    parked,
)
from tests.test_solver_path import build_env, random_spec


def build_ml_rt(seed, megaloop, journal_dir=None, pipeline="on",
                chunk=CHUNK):
    """The tests/test_pipeline seeded environment with the megaloop
    knob exposed (same CQs/workloads per seed by construction)."""
    rt, journal = build_rt(seed, pipeline, journal_dir, chunk)
    rt.set_megaloop(megaloop)
    return rt, journal


# ---- layer 1: kernel vs serial chain vs numpy mirror ----


def _kernel_env(spec):
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    pending = []
    for cq_name, pq in mgr.cluster_queues.items():
        for wl in pq.snapshot_sorted():
            pending.append((wl, cq_name))
    snapshot = take_snapshot(cache)
    ts_fn = lambda wl: queue_order_timestamp(wl, mgr._ts_policy)  # noqa: E731
    return snapshot, pending, cache.flavors, ts_fn


def _round_view(outcome):
    sig = outcome_signature(outcome)
    sig["undecided"] = [(wl.key, cq) for wl, cq in outcome.undecided]
    sig["resume"] = [(wl.key, g.tolist()) for wl, _, g in outcome.resume]
    return sig


def _resume_walks(snapshot, outcome):
    """What the runtime does between serial rounds: each nominated,
    undecided head keeps its flavor walk as its LastAssignment."""
    from kueue_tpu.core.solver import resumed_assignment

    for wl, cq, walks in outcome.resume:
        wl.last_assignment = (
            resumed_assignment(
                snapshot.cq_models[cq], wl, walks,
                snapshot.generations.get(cq, 0),
            )
            if walks.max() > 0
            else None
        )


class TestKernelSerialEquivalence:
    """One fused launch == the chained serial rounds, bit-for-bit."""

    @pytest.mark.parametrize("seed", range(3))
    def test_fused_equals_serial_chain(self, seed):
        snapshot, pending, flavors, ts_fn = _kernel_env(
            random_spec(seed, workloads_per_cq=8)
        )
        log = launch_drain_megaloop(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=2, max_rounds=16,
        ).fetch()
        assert log.n_rounds >= 2, "trace too shallow to exercise fusion"
        s, p = snapshot, pending
        for r, round_out in enumerate(log.rounds):
            serial = launch_drain(
                s, p, flavors, timestamp_fn=ts_fn, max_cycles=2
            ).fetch()
            assert _round_view(serial) == _round_view(round_out), r
            assert np.array_equal(
                serial.final_usage, round_out.final_usage
            ), r
            if not serial.undecided:
                break
            _resume_walks(s, serial)
            s = speculative_snapshot(s, serial.final_usage)
            p = serial.undecided

    @pytest.mark.parametrize("seed", range(3))
    def test_fused_equals_numpy_mirror(self, seed):
        """KERNEL_MIRRORS parity: the device log equals the numpy
        mirror's — and the mirror IS the serial loop over trimmed
        tensors, so this is the serial==megaloop proof at the tensor
        level (multi-flavor specs exercise the flavor walks carried
        across rounds and the per-round retry-budget resets)."""
        snapshot, pending, flavors, ts_fn = _kernel_env(
            random_spec(seed, workloads_per_cq=8)
        )
        dev = launch_drain_megaloop(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=3, max_rounds=8,
        ).fetch()
        host = run_drain_megaloop_host(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=3, max_rounds=8,
        )
        assert dev.n_rounds == host.n_rounds
        assert dev.cycles == host.cycles
        assert dev.truncated == host.truncated
        for r, (a, b) in enumerate(zip(dev.rounds, host.rounds)):
            assert _round_view(a) == _round_view(b), r
            assert np.array_equal(a.final_usage, b.final_usage), r

    def test_round_budget_truncates_log(self):
        """max_rounds caps the batch: the final round reports the
        remaining backlog undecided and the log says truncated."""
        snapshot, pending, flavors, ts_fn = _kernel_env(
            random_spec(0, workloads_per_cq=8)
        )
        log = launch_drain_megaloop(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=1, max_rounds=2,
        ).fetch()
        assert log.n_rounds == 2
        assert log.truncated
        assert log.rounds[-1].undecided

    def test_policy_scores_flow_through(self):
        """Policy-complete: a gavel-scored megaloop decides exactly
        what gavel-scored serial rounds decide (score tensors ride
        plan_drain into the fused kernel unchanged)."""
        from kueue_tpu.policy import resolve_policy

        policy = resolve_policy("gavel")
        snapshot, pending, flavors, ts_fn = _kernel_env(
            random_spec(2, workloads_per_cq=8)
        )
        log = launch_drain_megaloop(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=2, max_rounds=16, policy=policy, now=5.0,
        ).fetch()
        s, p = snapshot, pending
        for r, round_out in enumerate(log.rounds):
            serial = launch_drain(
                s, p, flavors, timestamp_fn=ts_fn, max_cycles=2,
                policy=policy, now=5.0,
            ).fetch()
            assert _round_view(serial) == _round_view(round_out), r
            if not serial.undecided:
                break
            _resume_walks(s, serial)
            s = speculative_snapshot(s, serial.final_usage)
            p = serial.undecided

    def test_resident_mesh_rejected_loudly(self):
        """launch_drain / launch_drain_megaloop are documented
        single-device-only with a resident: a mesh + resident call must
        raise, not silently ignore the resident buffers."""
        import types

        from kueue_tpu.core.encode import ResidentEncoder

        snapshot, pending, flavors, ts_fn = _kernel_env(
            random_spec(0, workloads_per_cq=4)
        )
        fake_mesh = types.SimpleNamespace(shape={"wl": 2})
        with pytest.raises(ValueError, match="single-device"):
            launch_drain(
                snapshot, pending, flavors, timestamp_fn=ts_fn,
                mesh=fake_mesh, resident=ResidentEncoder(),
            )
        with pytest.raises(ValueError, match="single-device"):
            launch_drain_megaloop(
                snapshot, pending, flavors, timestamp_fn=ts_fn,
                mesh=fake_mesh, resident=ResidentEncoder(),
            )


class TestMeshComposition:
    """--megaloop composes with --mesh: the fused launch shards its
    queue tensors (and suffix budgets) along wl and decides bit-for-bit
    the single-device log (8 virtual CPU devices via conftest)."""

    @pytest.fixture(scope="class")
    def mesh(self):
        from kueue_tpu.parallel import make_mesh

        return make_mesh(8)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_sharded_log_parity(self, mesh, seed):
        snapshot, pending, flavors, ts_fn = _kernel_env(
            random_spec(seed, workloads_per_cq=6)
        )
        single = launch_drain_megaloop(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=2, max_rounds=8,
        ).fetch()
        sharded = launch_drain_megaloop(
            snapshot, pending, flavors, timestamp_fn=ts_fn,
            chunk_cycles=2, max_rounds=8, mesh=mesh,
        ).fetch()
        assert single.n_rounds == sharded.n_rounds
        for r, (a, b) in enumerate(zip(single.rounds, sharded.rounds)):
            assert _round_view(a) == _round_view(b), r
            assert np.array_equal(a.final_usage, b.final_usage), r


# ---- layer 2: runtime equivalence + truncation ----


class TestMegaloopEqualsSerial:
    """The bit-for-bit property over seeded traces: decisions, journal
    record sequence and audit trail identical with the megaloop on."""

    @pytest.mark.parametrize("seed", range(3))
    def test_decisions_journal_audit_identical(self, tmp_path, seed):
        rt_s, j_s = build_rt(seed, "serial", tmp_path / "s")
        rt_m, j_m = build_ml_rt(seed, "on", tmp_path / "m")
        rt_s.run_until_idle(max_iterations=60)
        rt_m.run_until_idle(max_iterations=60)
        assert admitted(rt_s) == admitted(rt_m)
        assert parked(rt_s) == parked(rt_m)
        assert admitted(rt_m), "vacuous trace: nothing admitted"
        # the fusion actually engaged and amortized dispatches
        ml = rt_m.megaloop
        assert ml.launches >= 1
        assert ml.rounds > ml.launches, ml.to_dict()
        assert rt_s.megaloop.launches == 0
        assert not rt_s.check_invariants() and not rt_m.check_invariants()
        j_s.close()
        j_m.close()
        assert journal_sequence(tmp_path / "s") == journal_sequence(
            tmp_path / "m"
        )
        assert audit_dump(rt_s) == audit_dump(rt_m)

    def test_pinned_k_forces_multiple_launches(self):
        """--megaloop K pins the rounds-per-launch: a deep backlog
        takes ceil(rounds / K) launches, decisions unchanged."""
        rt_auto, _ = build_ml_rt(3, "on")
        rt_auto.run_until_idle(max_iterations=60)
        rt_k, _ = build_ml_rt(3, "2")
        rt_k.run_until_idle(max_iterations=60)
        assert admitted(rt_auto) == admitted(rt_k)
        assert rt_k.megaloop_rounds == 2
        assert rt_k.megaloop.launches > rt_auto.megaloop.launches

    def test_megaloop_off_by_default(self):
        rt = ClusterRuntime(clock=FakeClock(0.0))
        assert rt.drain_megaloop == "off"
        assert rt.megaloop_rounds == 0

    def test_knob_parsing(self):
        rt = ClusterRuntime(clock=FakeClock(0.0))
        for spec, want in [
            ("on", ("on", 0)), ("off", ("off", 0)), (4, ("on", 4)),
            ("8", ("on", 8)), (0, ("off", 0)), (None, ("off", 0)),
        ]:
            rt.set_megaloop(spec)
            assert (rt.drain_megaloop, rt.megaloop_rounds) == want, spec
        with pytest.raises(ValueError):
            rt.set_megaloop("sideways")

    def test_observability_surfaces(self):
        rt, _ = build_ml_rt(3, "on")
        rt.run_until_idle(max_iterations=60)
        # per-launch cycle.megaloop span on the drain cycle trees
        tracer = rt.scheduler.tracer
        names = {
            s.name
            for t in tracer.traces_summary(limit=256)
            for s in tracer.trace(t["traceId"])
        }
        assert "cycle.megaloop" in names
        # metrics exposed (materialized-at-zero contract checked by the
        # metrics lint; here: live values flow)
        text = rt.metrics.registry.expose()
        assert "kueue_megaloop_rounds_per_launch" in text
        assert "kueue_megaloop_launches_total" in text
        assert "kueue_megaloop_truncations_total" in text
        # SIGUSR2 dump section
        from kueue_tpu.debugger import dump

        out = dump(rt)
        assert "-- megaloop --" in out
        assert "roundsPerLaunch" in out
        # dashboard payload
        from kueue_tpu.server.dashboard import dashboard_payload

        state = dashboard_payload(rt)
        assert state["megaloop"]["mode"] == "on"
        assert state["megaloop"]["launches"] >= 1

    def test_resident_usage_carry(self):
        """After a fully-committed launch the ResidentEncoder adopts
        the kernel's final usage device slice: the next launch ships
        zero delta rows for everything the batch itself changed."""
        rt, _ = build_ml_rt(0, "on")
        rt.run_until_idle(max_iterations=60)
        res = rt._drain_resident
        assert res is not None
        assert res.adopts >= 1, res.stats()


class TestConflictTruncation:
    def test_interference_truncates_batch_not_decisions(self):
        """Mutating queue state during a round's apply invalidates the
        rest of the fused batch: the megaloop truncates there,
        re-solves from the real state, and the final decisions match
        the serial loop run against the same interference."""

        def run(megaloop):
            rt, _ = build_ml_rt(5, megaloop)
            if megaloop == "off":
                rt.drain_pipeline = "serial"
            orig = rt._apply_drain_outcome
            state = {"fired": False}

            def interfering_apply(outcome, snapshot):
                res = orig(outcome, snapshot)
                if not state["fired"] and outcome.undecided:
                    state["fired"] = True
                    wl, _cq = outcome.undecided[0]
                    rt.delete_workload(wl)
                return res

            rt._apply_drain_outcome = interfering_apply
            rt.run_until_idle(max_iterations=60)
            assert state["fired"], "interference never triggered"
            return rt

        rt_m = run("on")
        rt_s = run("off")
        assert rt_m.megaloop.truncations >= 1, rt_m.megaloop.to_dict()
        assert admitted(rt_m) == admitted(rt_s)
        assert not rt_m.check_invariants()


# ---- layer 3: chaos at the new fault points ----


class TestMegaloopChaos:
    """Crash-at-every-new-fault-point x occurrence sweep: recovery from
    the journal plus a rerun converges to the fault-free serial
    admitted set (the tests/test_pipeline chaos pattern)."""

    POINTS = ("cycle.megaloop_launched", "cycle.megaloop_commit_round")

    @pytest.mark.parametrize("point", POINTS)
    @pytest.mark.parametrize("occurrence", [0, 1, 2])
    def test_crash_recover_converge(self, tmp_path, point, occurrence):
        ref, j_ref = build_rt(0, "serial", tmp_path / "ref")
        ref.run_until_idle(max_iterations=60)
        ref_admitted = admitted(ref)
        j_ref.close()

        # pin K=2 so a deep trace takes several fused launches and
        # every (point, occurrence) pair genuinely fires
        rt, j = build_ml_rt(0, "2", tmp_path / "j")
        faults.arm(point, "crash", skip=occurrence)
        crashed = False
        try:
            rt.run_until_idle(max_iterations=60)
        except faults.InjectedCrash:
            crashed = True
        finally:
            faults.reset()
        j.close()
        if not crashed:
            pytest.fail(f"{point} occurrence {occurrence} never fired")

        rt2, _ = build_ml_rt(0, "2")
        res = recover(None, str(tmp_path / "j"), runtime=rt2, strict=True)
        rt2.attach_journal(res.journal)
        rt2.run_until_idle(max_iterations=60)
        assert admitted(rt2) == ref_admitted
        assert parked(rt2) == parked(ref)
        assert not rt2.check_invariants()

    def test_points_registered(self):
        for p in self.POINTS:
            assert p in faults.FAULT_POINTS


# ---- guard coverage: tuner, deadline, sampled replay ----


class TestGuardMegaloop:
    def test_rounds_tuner_shrinks_on_truncation(self):
        t = RoundsTuner(default_k=8)
        assert t.k_for(1000) == 8
        t.observe(1000, committed=1, truncated=True)
        assert t.k_for(1000) == 4
        t.observe(1000, committed=1, truncated=True)
        t.observe(1000, committed=1, truncated=True)
        assert t.k_for(1000) == 2  # floor of the ladder
        assert t.truncations == 3

    def test_rounds_tuner_grows_on_clean_exhaustion(self):
        t = RoundsTuner(default_k=8, grow_after=2)
        t.observe(1000, committed=8, truncated=False)
        assert t.k_for(1000) == 8  # one clean launch is not enough
        t.observe(1000, committed=8, truncated=False)
        assert t.k_for(1000) == 16
        # a quiesced (non-exhausted) launch resets the streak
        t.observe(1000, committed=3, truncated=False)
        t.observe(1000, committed=16, truncated=False)
        assert t.k_for(1000) == 16

    def test_rounds_tuner_is_per_backlog_bucket(self):
        t = RoundsTuner(default_k=8)
        t.observe(100, committed=1, truncated=True)
        assert t.k_for(100) == 4
        assert t.k_for(100000) == 8  # other mixes untouched

    def test_pick_replay_round_deterministic_and_in_range(self):
        g = SolverGuard(clock=FakeClock(0.0))
        picks = set()
        for n in range(1, 40):
            g.divergence_checks = n
            r = g.pick_replay_round(7)
            assert 0 <= r < 7
            picks.add(r)
        assert len(picks) > 1, "degenerate replay schedule"
        g.divergence_checks = 5
        assert g.pick_replay_round(7) == g.pick_replay_round(7)

    def test_launch_deadline_override(self):
        """The megaloop's K-scaled deadline: a launch that would breach
        the per-round budget passes under its scaled override, and
        still breaches past it."""
        clock = FakeClock(0.0)
        guard = SolverGuard(clock=clock)
        guard.config.device_deadline_s = 5.0
        launch = guard.device_launch(
            lambda: "h", label="megaloop", deadline_s=40.0
        )
        clock.advance(30.0)  # past per-round budget, inside the batch's
        out = guard.device_join(launch, lambda h: h)
        assert out.result == "h"
        launch = guard.device_launch(
            lambda: "h", label="megaloop", deadline_s=40.0
        )
        clock.advance(41.0)
        out = guard.device_join(launch, lambda h: h)
        assert out.result is None
        assert guard.breaker.consecutive_failures == 1

    def test_sampled_round_replay_in_loop(self):
        """divergence_check_every=1: every fused launch replays one of
        its rounds on the numpy mirror BEFORE applying it; agreement
        keeps the device path trusted and decisions match serial."""
        rt, _ = build_ml_rt(2, "on")
        rt.guard.config.divergence_check_every = 1
        rt.run_until_idle(max_iterations=60)
        assert rt.megaloop.launches >= 1
        assert rt.guard.divergence_checks >= 1
        assert rt.guard.divergences == 0
        assert not rt.guard.breaker.quarantined
        ref, _ = build_rt(2, "serial")
        ref.run_until_idle(max_iterations=60)
        assert admitted(rt) == admitted(ref)

    def test_divergence_surface_label(self):
        guard = SolverGuard(clock=FakeClock(0.0))
        host = guard.check_drain_divergence(
            {"admitted": ["a"]},
            lambda: ("HOST", {"admitted": ["b"]}),
            heads=3,
            surface="drain-megaloop",
        )
        assert host == "HOST"
        assert guard.last_divergence["surface"] == "drain-megaloop"
        assert guard.breaker.quarantined
