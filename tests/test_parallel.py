"""Sharded-solver tests on the 8-device virtual CPU mesh."""

import numpy as np
import pytest

import jax

from kueue_tpu.parallel import ShardedSolver, make_mesh


def build_problem(seed=0, n_cq=24, n_cohort=4, fr=8, w=20, k=3, c=3):
    import __graft_entry__

    return __graft_entry__._synthetic_problem(
        n_cq=n_cq, n_cohort=n_cohort, fr=fr, w=w, k=k, c=c
    )


@pytest.mark.parametrize("fr_parallel", [False, True])
def test_sharded_matches_single_device(fr_parallel):
    from kueue_tpu.ops.assign_kernel import solve_cycle_jit

    tree, usage, heads, paths = build_problem(w=24)
    expected = solve_cycle_jit(tree, usage, heads, paths)

    mesh = make_mesh(8, fr_parallel=fr_parallel)
    solver = ShardedSolver(mesh)
    got = solver(tree, usage, heads, paths)

    np.testing.assert_array_equal(np.asarray(got.chosen), np.asarray(expected.chosen))
    np.testing.assert_array_equal(np.asarray(got.admitted), np.asarray(expected.admitted))
    np.testing.assert_array_equal(np.asarray(got.usage), np.asarray(expected.usage))


def test_padding_to_axis_multiple():
    tree, usage, heads, paths = build_problem(w=13)  # not divisible by 8
    from kueue_tpu.ops.assign_kernel import solve_cycle_jit

    expected = solve_cycle_jit(tree, usage, heads, paths)
    solver = ShardedSolver(make_mesh(8))
    got = solver(tree, usage, heads, paths)
    assert got.admitted.shape[0] == 16  # padded
    np.testing.assert_array_equal(
        np.asarray(got.admitted)[:13], np.asarray(expected.admitted)
    )
    assert not np.asarray(got.admitted)[13:].any()


def test_resolve_mesh_refuses_devices_the_host_lacks():
    from kueue_tpu.parallel import resolve_mesh

    assert resolve_mesh("auto").size == jax.device_count() == 8
    with pytest.raises(ValueError, match="16 devices requested"):
        resolve_mesh(16)
    with pytest.raises(ValueError, match="16 devices requested"):
        resolve_mesh("16")


def test_mesh_shapes():
    assert make_mesh(8).axis_names == ("wl",)
    assert make_mesh(8, fr_parallel=True).axis_names == ("wl", "fr")
    assert make_mesh(3, fr_parallel=True).axis_names == ("wl",)  # odd: 1-D


def test_sharded_drain_matches_unsharded():
    """run_drain with a mesh (Q axis sharded over 8 devices) must make
    identical decisions to the unsharded dispatch."""
    from kueue_tpu.core.drain import run_drain
    from kueue_tpu.core.queue_manager import queue_order_timestamp
    from kueue_tpu.core.snapshot import take_snapshot
    from kueue_tpu.parallel import make_mesh

    from tests.test_solver_path import build_env, random_spec

    spec = random_spec(3, workloads_per_cq=6)
    outcomes = {}
    for label, mesh in (("plain", None), ("mesh", make_mesh(8))):
        sched, mgr, cache, _ = build_env(spec, use_solver=False)
        pending = []
        for cq_name, pq in mgr.cluster_queues.items():
            for wl in pq.snapshot_sorted():
                pending.append((wl, cq_name))
        out = run_drain(
            take_snapshot(cache), pending, cache.flavors,
            timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
            mesh=mesh,
        )
        outcomes[label] = (
            {(wl.name, tuple(sorted(fl.items())), cyc) for wl, _, fl, cyc in out.admitted},
            {wl.name for wl, _ in out.parked},
        )
    assert outcomes["plain"] == outcomes["mesh"]


def test_sharded_dispatch_lowered_matches_unsharded():
    """dispatch_lowered with a mesh shards heads along wl; decisions
    must match the unsharded path."""
    import numpy as np

    from kueue_tpu.core.solver import dispatch_lowered, lower_heads
    from kueue_tpu.core.queue_manager import queue_order_timestamp
    from kueue_tpu.core.snapshot import take_snapshot
    from kueue_tpu.parallel import make_mesh

    from tests.test_solver_path import build_env, random_spec

    spec = random_spec(5, workloads_per_cq=4)
    sched, mgr, cache, _ = build_env(spec, use_solver=False)
    heads = []
    for cq_name, pq in mgr.cluster_queues.items():
        for wl in pq.snapshot_sorted():
            heads.append((wl, cq_name))
    snapshot = take_snapshot(cache)
    ts = lambda wl: queue_order_timestamp(wl, mgr._ts_policy)  # noqa: E731
    lowered = lower_heads(snapshot, heads, cache.flavors, timestamp_fn=ts)
    plain = dispatch_lowered(snapshot, lowered)
    sharded = dispatch_lowered(snapshot, lowered, mesh=make_mesh(8))
    np.testing.assert_array_equal(plain.chosen, sharded.chosen)
    np.testing.assert_array_equal(plain.admitted, sharded.admitted)
    np.testing.assert_array_equal(plain.reserved, sharded.reserved)


def test_sharded_preempt_drain_matches_unsharded():
    """run_drain_preempt with a mesh (queues + per-queue victim config
    sharded along wl, segment pools replicated) must decide identically
    to the unsharded dispatch — cohort reclaim included."""
    from kueue_tpu.core.queue_manager import queue_order_timestamp
    from kueue_tpu.core.snapshot import take_snapshot
    from kueue_tpu.parallel import make_mesh

    from tests.test_drain import build_preempt_env, cohort_reclaim_spec

    spec = cohort_reclaim_spec(2)
    outcomes = {}
    for label, mesh in (("plain", None), ("mesh", make_mesh(8))):
        from kueue_tpu.core.drain import run_drain_preempt

        sched, mgr, cache, _ = build_preempt_env(spec)
        pending = []
        for cq_name, pq in mgr.cluster_queues.items():
            for wl in pq.snapshot_sorted():
                pending.append((wl, cq_name))
        out = run_drain_preempt(
            take_snapshot(cache), pending, cache.flavors,
            timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
            mesh=mesh,
        )
        outcomes[label] = (
            {(wl.name, cyc) for wl, _, _, cyc in out.admitted},
            {wl.name for wl, _, _ in out.preempted},
            {wl.name for wl, _ in out.parked},
        )
    assert outcomes["plain"] == outcomes["mesh"]


def test_sharded_fair_drain_matches_unsharded():
    """run_drain(fair_sharing=True) with a mesh (per-queue tensors +
    DRS chain work sharded along wl, node space replicated) must make
    identical decisions — separate root cohorts are independent
    subproblems the tournament shards over."""
    from kueue_tpu.core.drain import run_drain
    from kueue_tpu.core.queue_manager import queue_order_timestamp
    from kueue_tpu.core.snapshot import take_snapshot
    from kueue_tpu.parallel import make_mesh

    from tests.test_drain import fair_drain_spec
    from tests.test_solver_path import build_env

    spec = fair_drain_spec(7, n_cohorts=3, cqs_per_cohort=3)
    outcomes = {}
    for label, mesh in (("plain", None), ("mesh", make_mesh(8))):
        sched, mgr, cache, _ = build_env(spec, use_solver=False)
        pending = []
        for cq_name, pq in mgr.cluster_queues.items():
            for wl in pq.snapshot_sorted():
                pending.append((wl, cq_name))
        out = run_drain(
            take_snapshot(cache), pending, cache.flavors,
            timestamp_fn=lambda wl: queue_order_timestamp(wl, mgr._ts_policy),
            fair_sharing=True,
            mesh=mesh,
        )
        assert not out.fallback
        outcomes[label] = (
            {
                (wl.name, tuple(sorted(fl.items())), cyc)
                for wl, _, fl, cyc in out.admitted
            },
            {wl.name for wl, _ in out.parked},
        )
    assert outcomes["plain"] == outcomes["mesh"]


def test_sharded_fair_search_matches_unsharded():
    """batched_fair_get_targets with a mesh (FairProblem rows sharded
    along wl) must return the same victim sets."""
    import pytest

    from kueue_tpu.core.preempt_batch import batched_fair_get_targets
    from kueue_tpu.core.preemption import Preemptor
    from kueue_tpu.parallel import make_mesh
    from kueue_tpu.utils.clock import FakeClock

    from tests.test_fair_preempt import build_fair_cluster, fair_items

    cache, cq_names = build_fair_cluster(3)
    snapshot, items = fair_items(cache, cq_names, 3)
    if not items:
        pytest.skip("no preempt-mode heads generated")
    preemptor = Preemptor(FakeClock(0.0), enable_fair_sharing=True)
    plain = batched_fair_get_targets(snapshot, items, preemptor)
    sharded = batched_fair_get_targets(
        snapshot, items, preemptor, mesh=make_mesh(8)
    )
    names = lambda rs: [  # noqa: E731
        sorted(t.workload.workload.name for t in r) for r in rs
    ]
    assert names(plain) == names(sharded)
