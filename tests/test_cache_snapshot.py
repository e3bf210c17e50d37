"""Cache + snapshot behavior (pkg/cache parity) and np/JAX kernel parity."""

import dataclasses
import random

import numpy as np
import pytest

from kueue_tpu.models import (
    AdmissionCheck,
    ClusterQueue,
    Cohort,
    FlavorQuotas,
    ResourceFlavor,
    ResourceGroup,
    Topology,
    TopologyLevel,
    Workload,
)
from kueue_tpu.models.constants import StopPolicy
from kueue_tpu.models.workload import PodSet
from kueue_tpu.core.cache import Cache
from kueue_tpu.core.hierarchy import CohortForest
from kueue_tpu.core.snapshot import take_snapshot
from kueue_tpu.core.workload_info import admission_usage, make_admission
from kueue_tpu.resources import FlavorResource


def build_cache():
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="on-demand"))
    cache.add_or_update_flavor(ResourceFlavor(name="spot"))
    cq_a = ClusterQueue(
        name="cq-a",
        cohort="team",
        resource_groups=(
            ResourceGroup(
                ("cpu", "memory"),
                (
                    FlavorQuotas.build("on-demand", {"cpu": "10", "memory": "10Gi"}),
                    FlavorQuotas.build("spot", {"cpu": "20", "memory": "20Gi"}),
                ),
            ),
        ),
    )
    cq_b = ClusterQueue(
        name="cq-b",
        cohort="team",
        resource_groups=(
            ResourceGroup(
                ("cpu", "memory"),
                (FlavorQuotas.build("on-demand", {"cpu": "5", "memory": "5Gi"}),),
            ),
        ),
    )
    cache.add_or_update_cluster_queue(cq_a)
    cache.add_or_update_cluster_queue(cq_b)
    return cache


def admitted_wl(name, cq, cpu_per_pod="1", count=2):
    wl = Workload(
        namespace="ns",
        name=name,
        queue_name="lq",
        pod_sets=(PodSet.build("main", count, {"cpu": cpu_per_pod, "memory": "1Gi"}),),
    )
    wl.admission = make_admission(
        cq, {"main": {"cpu": "on-demand", "memory": "on-demand"}}, wl
    )
    return wl


def test_admission_usage_vector():
    wl = admitted_wl("w", "cq-a")
    usage = admission_usage(wl)
    assert usage[FlavorResource("on-demand", "cpu")] == 2000
    assert usage[FlavorResource("on-demand", "memory")] == 2 * 2**30


def test_reclaimable_pods_discount_usage():
    wl = admitted_wl("w", "cq-a", count=4)
    wl.reclaimable_pods["main"] = 1
    usage = admission_usage(wl)
    assert usage[FlavorResource("on-demand", "cpu")] == 3000


def test_cache_usage_tracking():
    cache = build_cache()
    wl = admitted_wl("w1", "cq-a")
    assert cache.add_or_update_workload(wl)
    assert cache.usage_for("cq-a")[FlavorResource("on-demand", "cpu")] == 2000
    assert cache.delete_workload(wl)
    assert cache.usage_for("cq-a")[FlavorResource("on-demand", "cpu")] == 0


def test_assume_and_forget():
    cache = build_cache()
    wl = admitted_wl("w1", "cq-a")
    assert cache.assume_workload(wl)
    assert not cache.assume_workload(wl)  # double assume rejected
    assert cache.usage_for("cq-a")[FlavorResource("on-demand", "cpu")] == 2000
    assert cache.forget_workload(wl)
    assert cache.usage_for("cq-a")[FlavorResource("on-demand", "cpu")] == 0
    assert not cache.forget_workload(wl)


def test_cq_status_reasons():
    cache = Cache()
    cq = ClusterQueue(
        name="cq",
        resource_groups=(
            ResourceGroup(("cpu",), (FlavorQuotas.build("missing", {"cpu": "1"}),)),
        ),
        admission_checks=("nonexistent",),
    )
    cache.add_or_update_cluster_queue(cq)
    st = cache.cluster_queue_status("cq")
    assert not st.active
    assert "FlavorNotFound" in st.reasons
    assert "AdmissionCheckNotFound" in st.reasons
    cache.add_or_update_flavor(ResourceFlavor(name="missing"))
    cache.add_or_update_admission_check(
        AdmissionCheck(name="nonexistent", controller_name="ctrl")
    )
    assert cache.cluster_queue_status("cq").active


def test_cq_status_tas_misconfig():
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="tpu", topology_name="default"))
    cq = ClusterQueue(
        name="cq",
        resource_groups=(
            ResourceGroup(("cpu",), (FlavorQuotas.build("tpu", {"cpu": "1"}),)),
        ),
    )
    cache.add_or_update_cluster_queue(cq)
    assert "TopologyNotFound" in cache.cluster_queue_status("cq").reasons
    cache.add_or_update_topology(
        Topology(name="default", levels=(TopologyLevel("rack"), TopologyLevel("host")))
    )
    assert cache.cluster_queue_status("cq").active


def test_stopped_cq_inactive():
    cache = build_cache()
    model = cache.cluster_queues["cq-a"].model
    import dataclasses

    stopped = dataclasses.replace(model, stop_policy=StopPolicy.HOLD)
    cache.add_or_update_cluster_queue(stopped)
    assert "Stopped" in cache.cluster_queue_status("cq-a").reasons
    snap = take_snapshot(cache)
    assert "cq-a" in snap.inactive_cqs
    assert "cq-b" in snap.flat.cq_names


def test_snapshot_quota_and_fits():
    cache = build_cache()
    cache.add_or_update_workload(admitted_wl("w1", "cq-a", count=8))  # 8 cpu
    snap = take_snapshot(cache)
    od_cpu = snap.fr_index[FlavorResource("on-demand", "cpu")]
    # cohort subtree: 10+20 (cq-a) + 5 (cq-b) = 35 cpu across flavors;
    # on-demand cpu cell: 10 + 5 = 15
    team_row = snap.flat.index["team"]
    assert snap.subtree[team_row, od_cpu] == 15_000
    # cq-b can use on-demand cpu: 15 - 8 used = 7
    assert snap.available_for("cq-b")[od_cpu] == 7_000
    vec = np.zeros(len(snap.fr_list), dtype=np.int64)
    vec[od_cpu] = 7_000
    assert snap.fits("cq-b", vec)
    vec[od_cpu] = 7_001
    assert not snap.fits("cq-b", vec)


def test_snapshot_simulate_remove_workload():
    cache = build_cache()
    cache.add_or_update_workload(admitted_wl("w1", "cq-a", count=8))
    snap = take_snapshot(cache)
    od_cpu = snap.fr_index[FlavorResource("on-demand", "cpu")]
    ws = snap.remove_workload("ns/w1")
    assert ws is not None
    assert snap.available_for("cq-b")[od_cpu] == 15_000
    snap.add_workload(ws)  # undo
    assert snap.available_for("cq-b")[od_cpu] == 7_000


def test_snapshot_cohort_members():
    cache = build_cache()
    lone = ClusterQueue(
        name="lone",
        resource_groups=(
            ResourceGroup(("cpu",), (FlavorQuotas.build("on-demand", {"cpu": "1"}),)),
        ),
    )
    cache.add_or_update_cluster_queue(lone)
    snap = take_snapshot(cache)
    assert snap.cohort_members("cq-a") == {"cq-a", "cq-b"}
    assert snap.cohort_members("lone") == {"lone"}
    assert not snap.has_cohort("lone")


def cq_in(name, cohort, cpu="1"):
    return ClusterQueue(
        name=name,
        cohort=cohort,
        resource_groups=(
            ResourceGroup(("cpu",), (FlavorQuotas.build("on-demand", {"cpu": cpu}),)),
        ),
    )


def recorded_statuses(cache):
    """take_snapshot(cache), and the status it used for each
    ClusterQueue (read through a wrapper on the cache)."""
    seen = {}
    status = cache.cluster_queue_status

    def wrapper(name, *args, **kwargs):
        seen[name] = status(name, *args, **kwargs)
        return seen[name]

    cache.cluster_queue_status = wrapper
    try:
        snap = take_snapshot(cache)
    finally:
        del cache.cluster_queue_status
    return snap, seen


# cohort edges (name, parent); "c0" holds a cycle in each, and every
# cohort named here gets two ClusterQueues
CYCLES = {
    "two": [("c0", "c1"), ("c1", "c0")],
    "three": [("c0", "c1"), ("c1", "c2"), ("c2", "c0")],
    # a tree hanging below a two-cohort cycle: c2 and c3 are children of
    # c1, c4 a child of c3
    "tree_below": [("c0", "c1"), ("c1", "c0"), ("c2", "c1"), ("c3", "c1"),
                   ("c4", "c3")],
}


@pytest.mark.parametrize("shape", sorted(CYCLES))
def test_cohort_cycle_makes_its_cluster_queues_inactive(shape):
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="on-demand"))
    edges = CYCLES[shape]
    for name, parent in edges:
        cache.add_or_update_cohort(Cohort(name=name, parent=parent))
    cyclic_cqs = set()
    for name, _ in edges:
        for k in range(2):
            cache.add_or_update_cluster_queue(cq_in(f"{name}-cq{k}", name))
            cyclic_cqs.add(f"{name}-cq{k}")
    # a healthy cohort beside the cycle, and a ClusterQueue in no cohort
    cache.add_or_update_cohort(Cohort(name="ok"))
    cache.add_or_update_cluster_queue(cq_in("ok-cq", "ok"))
    cache.add_or_update_cluster_queue(cq_in("lone", None))

    snap, used = recorded_statuses(cache)
    assert set(snap.inactive_cqs) == cyclic_cqs
    assert not cyclic_cqs & set(snap.flat.cq_names)
    assert set(snap.flat.cq_names) == {"ok-cq", "lone"}
    assert not {name for name, _ in edges} & set(snap.flat.cohort_names)
    for name in cyclic_cqs:
        assert used[name].reasons == ("CohortCycle",)
        assert not used[name].active
    assert used["ok-cq"].active and used["lone"].active
    # each status called alone equals the one the snapshot used
    assert used == {name: cache.cluster_queue_status(name) for name in used}

    # break the cycle: c0 becomes a root
    cache.add_or_update_cohort(Cohort(name="c0"))
    snap, used = recorded_statuses(cache)
    assert snap.inactive_cqs == ()
    assert cyclic_cqs <= set(snap.flat.cq_names)
    assert all(st.active for st in used.values())
    assert used == {name: cache.cluster_queue_status(name) for name in used}
    # the ClusterQueues in c0's former cycle share one tree again
    assert snap.cohort_members("c0-cq0") >= {"c0-cq0", "c1-cq0", "c1-cq1"}


def chain_in_cycle(forest, cohort):
    """Whether following parent edges up from ``cohort`` comes back to
    a cohort already passed: the per-ClusterQueue check, written
    without ``cyclic_cohorts``."""
    seen = set()
    cur = cohort
    while cur in forest.cohorts and forest.cohorts[cur].parent:
        if cur in seen:
            return True
        seen.add(cur)
        cur = forest.cohorts[cur].parent
    return False


@pytest.mark.parametrize("seed", range(12))
def test_hoisted_cycle_check_equals_per_cq_recomputation(seed):
    rng = random.Random(seed)
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="on-demand"))
    n_cohorts, n_cqs = rng.randint(1, 40), rng.randint(1, 120)
    # parents may name a cohort that has no Cohort object (implicit)
    names = [f"c{i}" for i in range(n_cohorts)]
    parents = names + [f"ghost{i}" for i in range(4)] + [None]
    for name in names:
        if rng.random() < 0.8:
            cache.add_or_update_cohort(
                Cohort(name=name, parent=rng.choice([p for p in parents if p != name]))
            )
    for name in rng.sample(names, k=min(3, n_cohorts)):
        if rng.random() < 0.5:
            # a self-loop, as the forest takes it from an edge no model
            # validated
            cache.forest.update_cohort(name, name)
    for i in range(n_cqs):
        cache.add_or_update_cluster_queue(cq_in(f"cq{i}", rng.choice(parents)))
    for _ in range(rng.randint(0, 10)):
        if rng.random() < 0.5:
            cache.delete_cluster_queue(f"cq{rng.randrange(n_cqs)}")
        else:
            cache.delete_cohort(rng.choice(names))

    cyclic = cache.forest.cyclic_cohorts()
    walks = cache.forest.walks
    snap, used = recorded_statuses(cache)
    assert cache.forest.walks == walks + 1
    # the oracle: every ClusterQueue's status recomputed on its own
    oracle = {name: cache.cluster_queue_status(name) for name in cache.cluster_queues}
    assert used == oracle
    assert sorted(snap.inactive_cqs) == sorted(
        name for name, st in oracle.items() if not st.active
    )
    assert set(snap.flat.cq_names) == {n for n, st in oracle.items() if st.active}
    for name in cache.cluster_queues:
        parent = cache.forest.cq_parent.get(name)
        in_cycle = parent is not None and chain_in_cycle(cache.forest, parent)
        assert ("CohortCycle" in oracle[name].reasons) == in_cycle, name
        assert cache.forest.cq_in_cycle(name, cyclic) == in_cycle
    assert not cyclic & set(snap.flat.cohort_names)


def test_one_snapshot_walks_the_forest_once_at_the_cells_shape(monkeypatch):
    """1,002 ClusterQueues in 167 cohorts of 6: one snapshot makes one
    cycle walk, not one per ClusterQueue."""
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="on-demand"))
    for c in range(167):
        for k in range(6):
            cache.add_or_update_cluster_queue(cq_in(f"cq-{c}-{k}", f"cohort-{c}", "20"))
    calls = []
    walk = CohortForest.cyclic_cohorts

    def counting(self):
        calls.append(1)
        return walk(self)

    monkeypatch.setattr(CohortForest, "cyclic_cohorts", counting)
    walks = cache.forest.walks
    snap = take_snapshot(cache)
    assert len(calls) == 1
    assert cache.forest.walks == walks + 1
    assert snap.flat.n_cq == 1002 and len(snap.flat.cohort_names) == 167
    # a status asked alone still walks for itself
    assert cache.cluster_queue_status("cq-0-0").active
    assert len(calls) == 2


class _CountingNames(tuple):
    """A tuple of names that counts the passes made over it."""

    passes = 0

    def __iter__(self):
        type(self).passes += 1
        return super().__iter__()


@pytest.mark.parametrize("seed", range(6))
def test_cohort_members_grouped_in_one_pass(seed):
    """On a multi-level forest with cohort-less ClusterQueues, every
    group equals a scan for the ClusterQueues under the same root, and
    one pass over the names fills them all."""
    rng = random.Random(seed)
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="on-demand"))
    names = [f"c{i}" for i in range(rng.randint(3, 12))]
    for i, name in enumerate(names):
        # parents come earlier in the list: a forest with no cycle, and
        # c0 > c1 > c2 a chain three levels deep
        parent = names[i - 1] if 0 < i < 3 else rng.choice([None] + names[:i])
        cache.add_or_update_cohort(Cohort(name=name, parent=parent))
    cache.add_or_update_cluster_queue(cq_in("deep", "c2"))
    cache.add_or_update_cluster_queue(cq_in("alone", None))
    for i in range(rng.randint(4, 40)):
        cache.add_or_update_cluster_queue(cq_in(f"cq{i}", rng.choice(names + [None])))
    snap = take_snapshot(cache)
    assert snap.flat.max_depth >= 3
    roots = snap.roots()
    flat = snap.flat
    oracle = {
        name: {
            other
            for other in flat.cq_names
            if roots[flat.index[other]] == roots[flat.index[name]]
        }
        for name in flat.cq_names
    }
    snap.flat = dataclasses.replace(flat, cq_names=_CountingNames(flat.cq_names))
    _CountingNames.passes = 0
    got = {name: snap.cohort_members(name) for name in flat.cq_names}
    assert got == oracle
    assert _CountingNames.passes == 1
    for name in flat.cq_names:
        assert (got[name] == {name}) == (
            not snap.has_cohort(name) or oracle[name] == {name}
        )


def test_np_jax_kernel_parity():
    """The host-side numpy mirrors must agree with the jit kernels."""
    from kueue_tpu._jax import jnp
    from kueue_tpu.ops import quota as qj
    from kueue_tpu.ops import quota_np as qn

    rng = np.random.default_rng(7)
    cache = build_cache()
    cache.add_or_update_cohort(Cohort(name="team", parent="org"))
    cache.add_or_update_cohort(
        Cohort(
            name="org",
            resource_groups=(
                ResourceGroup(
                    ("cpu",), (FlavorQuotas.build("on-demand", {"cpu": "100"}),)
                ),
            ),
        )
    )
    snap = take_snapshot(cache)
    n, fr = snap.local_usage.shape
    local = rng.integers(0, 30_000, size=(n, fr)).astype(np.int64)
    local[snap.flat.n_cq :] = 0
    lm = snap.flat.level_masks()

    st_np, g_np = qn.subtree_quota_np(snap.flat.parent, lm, snap.nominal, snap.lending_limit)
    u_np = qn.usage_tree_np(snap.flat.parent, lm, g_np, local)
    a_np = qn.available_all_np(snap.flat.parent, lm, st_np, g_np, snap.borrowing_limit, u_np)

    tree = qj.QuotaTree(
        parent=jnp.asarray(snap.flat.parent),
        level_mask=jnp.asarray(lm),
        nominal=jnp.asarray(snap.nominal),
        lending_limit=jnp.asarray(snap.lending_limit),
        borrowing_limit=jnp.asarray(snap.borrowing_limit),
    )
    st_j, g_j = qj.subtree_quota(tree)
    u_j = qj.usage_tree(tree, g_j, jnp.asarray(local))
    a_j = qj.available_all(tree, st_j, g_j, u_j)

    np.testing.assert_array_equal(st_np, np.asarray(st_j))
    np.testing.assert_array_equal(g_np, np.asarray(g_j))
    np.testing.assert_array_equal(u_np, np.asarray(u_j))
    np.testing.assert_array_equal(a_np, np.asarray(a_j))

    wl_req = rng.integers(0, 10_000, size=(n, fr)).astype(np.int64)
    weight = np.where(rng.random(n) < 0.2, 0, 1000).astype(np.int64)
    d_np, dom_np = qn.dominant_resource_share_np(
        snap.flat.parent, lm, st_np, g_np, snap.borrowing_limit, u_np,
        wl_req, weight, snap.resource_index, len(snap.resource_names),
    )
    d_j, dom_j = qj.dominant_resource_share(
        tree, st_j, g_j, u_j, jnp.asarray(wl_req), jnp.asarray(weight),
        jnp.asarray(snap.resource_index), len(snap.resource_names),
    )
    np.testing.assert_array_equal(d_np, np.asarray(d_j))
    np.testing.assert_array_equal(dom_np, np.asarray(dom_j))


def test_incremental_available_row_parity():
    """available_row (path-walk over incrementally-maintained tree
    usage) must match the full available_all_np reduction cell-for-cell
    across random interleaved add/remove mutations."""
    import numpy as np

    from kueue_tpu.models import ClusterQueue, ResourceFlavor, LocalQueue
    from kueue_tpu.models.cluster_queue import FlavorQuotas, ResourceGroup
    from kueue_tpu.models.cohort import Cohort
    from kueue_tpu.core.cache import Cache
    from kueue_tpu.core.snapshot import take_snapshot

    rng = np.random.default_rng(7)
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="f"))
    # depth-3 forest: root <- mid-a/mid-b <- cqs, with lending/borrowing
    cache.add_or_update_cohort(Cohort(name="root"))
    cache.add_or_update_cohort(Cohort(name="mid-a", parent="root"))
    cache.add_or_update_cohort(Cohort(name="mid-b", parent="root"))
    names = []
    for i in range(8):
        name = f"cq{i}"
        names.append(name)
        cache.add_or_update_cluster_queue(
            ClusterQueue(
                name=name,
                cohort="mid-a" if i % 2 else "mid-b",
                namespace_selector={},
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (
                            FlavorQuotas.build(
                                "f",
                                {
                                    "cpu": (
                                        str(4 + i),
                                        str(3),  # borrowingLimit
                                        str(2),  # lendingLimit
                                    )
                                },
                            ),
                        ),
                    ),
                ),
            )
        )
    snap = take_snapshot(cache)
    # force the incremental structures alive before mutations
    for name in names:
        snap.available_row(snap.row(name))
    for step in range(200):
        name = names[int(rng.integers(0, len(names)))]
        vec = np.zeros(len(snap.fr_list), dtype=np.int64)
        vec[int(rng.integers(0, len(snap.fr_list)))] = int(rng.integers(1, 5000))
        if rng.random() < 0.5:
            snap.add_usage(name, vec)
        else:
            snap.remove_usage(name, vec)
        full = snap.available()
        for q in names:
            r = snap.row(q)
            np.testing.assert_array_equal(
                snap.available_row(r), full[r], err_msg=f"step {step} row {q}"
            )
