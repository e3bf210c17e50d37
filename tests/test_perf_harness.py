"""Perf harness tests (scaled-down default scenario)."""

from kueue_tpu.perf import DEFAULT_GENERATOR_CONFIG, RangeSpec, check, run
from kueue_tpu.perf.generator import generate


class TestGenerator:
    def test_default_config_shape(self):
        scenario = generate(DEFAULT_GENERATOR_CONFIG)
        assert len(scenario.cluster_queues) == 30  # 5 cohorts x 6 CQs
        assert len(scenario.local_queues) == 30
        assert len(scenario.workloads) == 2500  # 5 x (350+100+50)
        classes = {}
        for gw in scenario.workloads:
            classes[gw.class_name] = classes.get(gw.class_name, 0) + 1
        assert classes == {"small": 1750, "medium": 500, "large": 250}
        # borrowing limits present
        cq = scenario.cluster_queues[0]
        rq = cq.resource_groups[0].flavors[0].resources["cpu"]
        assert rq.nominal == 20_000 and rq.borrowing_limit == 100_000

    def test_scaled(self):
        cfg = DEFAULT_GENERATOR_CONFIG.scaled(0.1)
        scenario = generate(cfg)
        assert len(scenario.workloads) == 5 * (35 + 10 + 5)


class TestArrivalProcess:
    """The open-loop arrival-stream config."""

    def test_uniform_spacing(self):
        import numpy as np

        from kueue_tpu.perf.generator import ArrivalProcess

        proc = ArrivalProcess(
            rate_per_s=10.0, duration_s=2.0, process="uniform"
        )
        times = proc.arrival_times(np.random.default_rng(0))
        assert len(times) == 20
        gaps = {round(b - a, 6) for a, b in zip(times, times[1:])}
        assert gaps == {0.1}

    def test_poisson_is_seeded_and_rate_correct(self):
        import numpy as np

        from kueue_tpu.perf.generator import ArrivalProcess

        proc = ArrivalProcess(rate_per_s=100.0, duration_s=20.0)
        a = proc.arrival_times(np.random.default_rng(7))
        b = proc.arrival_times(np.random.default_rng(7))
        assert a == b, "same seed must reproduce the same stream"
        # law of large numbers: ~2000 arrivals within 10%
        assert 1800 <= len(a) <= 2200
        assert all(0.0 <= t < 20.0 for t in a)
        assert a == sorted(a)

    def test_arrival_stream_round_robins_queues_and_classes(self):
        import numpy as np
        import pytest

        from kueue_tpu.perf.generator import (
            ArrivalProcess,
            arrival_stream,
        )

        proc = ArrivalProcess(
            rate_per_s=5.0, duration_s=2.0, process="uniform"
        )
        stream = arrival_stream(
            proc, ["lq-0", "lq-1"], np.random.default_rng(0)
        )
        assert len(stream) == 10
        assert {gw.workload.queue_name for gw in stream} == {"lq-0", "lq-1"}
        assert {gw.class_name for gw in stream} == {"small", "medium"}
        for gw in stream:
            assert gw.workload.creation_time == gw.creation_s
            assert gw.runtime_s > 0
        with pytest.raises(ValueError):
            ArrivalProcess(process="bursty").arrival_times(
                np.random.default_rng(0)
            )


class TestRunner:
    def test_scaled_run_admits_everything(self):
        result = run(DEFAULT_GENERATOR_CONFIG.scaled(0.04))
        assert result.admitted == result.total == 100
        assert result.virtual_s > 0
        assert set(result.time_to_admission) == {"small", "medium", "large"}
        violations = check(
            result,
            RangeSpec(
                wl_classes_max_avg_tta_s={"large": 11.0, "medium": 90.0, "small": 233.0},
            ),
        )
        assert violations == []

    def test_contention_produces_queueing(self):
        # 10x the load on the same quota: small workloads must wait
        cfg = DEFAULT_GENERATOR_CONFIG.scaled(0.2)
        result = run(cfg)
        assert result.admitted == result.total
        # higher-priority large workloads admit faster than small ones
        assert result.avg_tta("large") <= result.avg_tta("small") + 1e-9

    def test_checker_flags_violations(self):
        result = run(DEFAULT_GENERATOR_CONFIG.scaled(0.04))
        errs = check(
            result, RangeSpec(wl_classes_max_avg_tta_s={"small": -1.0})
        )
        assert errs and "small" in errs[0]


class TestSolverRunnerParity:
    def test_scaled_run_solver_matches_host(self):
        cfg = DEFAULT_GENERATOR_CONFIG.scaled(0.08)
        host = run(cfg, use_solver=False)
        dev = run(cfg, use_solver=True)
        assert dev.admitted == host.admitted == dev.total
        # identical admission decisions: per-class TTA lists match exactly
        assert dev.time_to_admission == host.time_to_admission
        assert dev.cq_avg_utilization == host.cq_avg_utilization
        assert dev.backlog_fraction == host.backlog_fraction
        assert dev.cq_backlogged_utilization == host.cq_backlogged_utilization


class TestContendedScenario:
    def test_floors_hold_under_sustained_backlog(self):
        # the contended variant (runtimes x100) sustains a backlog so
        # the no-idle-capacity-under-backlog floor and nonzero TTA
        # ceilings are REAL assertions (round-3 verdict weak #2);
        # scaled down for CI, the structural floors still hold
        from kueue_tpu.perf import (
            CONTENDED_GENERATOR_CONFIG,
            RangeSpec,
            check,
            run,
        )

        result = run(CONTENDED_GENERATOR_CONFIG.scaled(0.2), use_solver=False)
        assert result.admitted == result.total
        assert result.backlog_fraction > 0.5
        assert min(result.cq_backlogged_utilization.values()) >= 0.55
        # queueing is real: every class waited
        for cls in ("small", "medium", "large"):
            assert result.avg_tta(cls) > 1.0
        # the priority ladder: prio-200 gangs wait least
        assert result.avg_tta("large") < result.avg_tta("small")
        errs = check(
            result,
            RangeSpec(
                wl_classes_min_avg_tta_s={"small": 1.0, "large": 1.0},
                cq_min_avg_utilization=0.55,
                cq_min_backlogged_utilization=0.55,
                min_backlog_fraction=0.5,
            ),
        )
        assert errs == []

    def test_checker_flags_vacuous_scenario(self):
        # the DEFAULT scenario admits instantly: the contended floors
        # must FLAG it (that is the point of the floors)
        from kueue_tpu.perf import (
            CONTENDED_RANGE_SPEC,
            DEFAULT_GENERATOR_CONFIG,
            check,
            run,
        )

        result = run(DEFAULT_GENERATOR_CONFIG.scaled(0.04), use_solver=False)
        errs = check(result, CONTENDED_RANGE_SPEC)
        assert errs  # no backlog, zero TTAs -> floors flag it


class TestMultiKueueAtScale:
    """BASELINE config #5 at test scale: worker clusters x workloads
    through batched dispatch, full lifecycle to completion
    (workload.go:298-425 behaviors at fleet granularity)."""

    def test_dispatch_lifecycle_floors(self):
        from kueue_tpu.perf.multikueue import (
            MULTIKUEUE_RANGE_SPEC,
            check_mk,
            run_multikueue,
        )

        # 320 workloads over 4 workers; capacity forces ~2 dispatch
        # waves; backlog (320) clears the 256 bulk-drain threshold so
        # the device drain and the batched dispatch compose
        r = run_multikueue(
            n_workers=4,
            n_workloads=320,
            worker_cpu_each=40,
            n_queues=8,
        )
        assert check_mk(r, MULTIKUEUE_RANGE_SPEC) == []
        assert r.finished == r.total == 320
        # wire efficiency: every create rode a batched exchange, and
        # batches were real (≥ tens of creates per exchange on average)
        assert r.unbatched_creates == 0
        assert r.total_batched_creates >= 4 * 320  # a copy per cluster
        assert r.avg_batch >= 10.0
        # the first-reserving race path genuinely ran and resolved
        assert r.first_reserving_races > 0
        # the load spread across ALL workers (scan-order rotation)
        assert set(r.winner_counts) == {f"worker{i}" for i in range(4)}
        assert min(r.winner_counts.values()) >= 0.05 * r.total
        assert sum(r.winner_counts.values()) == r.total
        # hygiene: no origin-labeled remote survives the final GC
        assert r.remote_leftovers == 0

    def test_checker_flags_unbatched_and_orphans(self):
        from kueue_tpu.perf.multikueue import (
            MKRangeSpec,
            MKRunResult,
            check_mk,
        )

        bad = MKRunResult(
            wall_s=1.0, virtual_s=1.0, n_workers=4, total=10, dispatched=10,
            finished=9, driver_iterations=1, unbatched_creates=3,
            batched_exchanges=2, total_batched_creates=4, max_batch=2,
            avg_batch=1.5, first_reserving_races=0,
            winner_counts={"worker0": 10},
            orphans_gced=0, remote_leftovers=2,
        )
        errs = check_mk(bad, MKRangeSpec())
        joined = "\n".join(errs)
        assert "finished 9/10" in joined
        assert "bypassed the batched exchange" in joined
        assert "races" in joined
        assert "survived GC" in joined
        # a worker that never won is a spread violation even though the
        # per-worker share loop can only see workers that DID win
        assert "only 1/4 workers" in joined
