"""Batched (device) preemption vs the host Preemptor.

For every preempt-mode head the kernel's victim set — and each victim's
reason — must match core/preemption.py's sequential simulate/undo
search exactly. Parity targets: preemption.go:127-342.
"""

import numpy as np
import pytest

from kueue_tpu.models import (
    ClusterQueue,
    FlavorQuotas,
    Preemption,
    ResourceFlavor,
    ResourceGroup,
    Workload,
    WorkloadConditionType,
)
from kueue_tpu.models.cluster_queue import BorrowWithinCohort
from kueue_tpu.models.constants import (
    BorrowWithinCohortPolicy,
    PreemptionPolicy,
    ReclaimWithinCohortPolicy,
)
from kueue_tpu.models.workload import PodSet
from kueue_tpu.core.cache import Cache
from kueue_tpu.core.flavor_assigner import FlavorAssigner, Mode
from kueue_tpu.core.preempt_batch import batched_get_targets
from kueue_tpu.core.preemption import Preemptor
from kueue_tpu.core.snapshot import take_snapshot
from kueue_tpu.core.workload_info import make_admission
from kueue_tpu.resources import FlavorResource
from kueue_tpu.utils.clock import FakeClock

from tests.test_preemption import admit, build_cache, cq_one_flavor, pending


def targets_set(targets):
    return {(t.workload.workload.name, t.reason) for t in targets}


def assert_target_parity(cache, incoming, fair=False):
    """Assign the incoming workloads, then compare host vs batched
    victim sets for every PREEMPT-mode head. Returns the batched sets
    keyed by workload name (for scenario-level assertions)."""
    snap = take_snapshot(cache)
    assigner = FlavorAssigner(snap, cache.flavors)
    preemptor = Preemptor(FakeClock(), enable_fair_sharing=fair)
    items = []
    for wl, cq_name in incoming:
        assignment = assigner.assign(wl, cq_name)
        if assignment.representative_mode() == Mode.PREEMPT:
            items.append((wl, cq_name, assignment))
    assert items, "scenario produced no PREEMPT-mode heads"
    batched = batched_get_targets(snap, items, preemptor)
    out = {}
    for (wl, cq_name, assignment), got in zip(items, batched):
        want = preemptor.get_targets(wl, cq_name, assignment, snap)
        assert targets_set(got) == targets_set(want), (
            wl.name,
            targets_set(got),
            targets_set(want),
        )
        out[wl.name] = targets_set(got)
    return out


class TestDeterministicParity:
    def test_within_cq_minimal_set(self):
        cq = cq_one_flavor(
            "cq",
            preemption=Preemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
            ),
        )
        cache = build_cache(cq)
        admit(cache, "a", "cq", "3", prio=1, reserved_at=1.0)
        admit(cache, "b", "cq", "3", prio=2, reserved_at=2.0)
        admit(cache, "c", "cq", "4", prio=3, reserved_at=3.0)
        got = assert_target_parity(
            cache, [(pending("new", "cq", "4", prio=100), "cq")]
        )
        assert {n for n, _ in got["new"]} == {"a", "b"}

    def test_reclaim_within_cohort(self):
        prem = Preemption(reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY)
        cq_a = cq_one_flavor("cq-a", cpu="5", cohort="team", preemption=prem)
        cq_b = cq_one_flavor("cq-b", cpu="5", cohort="team")
        cache = build_cache(cq_a, cq_b)
        admit(cache, "borrower", "cq-b", "8", prio=100)
        got = assert_target_parity(
            cache, [(pending("new", "cq-a", "5", prio=0), "cq-a")]
        )
        assert {n for n, _ in got["new"]} == {"borrower"}

    def test_borrow_within_cohort_threshold(self):
        prem = Preemption(
            reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
            borrow_within_cohort=BorrowWithinCohort(
                policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                max_priority_threshold=10,
            ),
        )
        cq_a = cq_one_flavor("cq-a", cpu="4", cohort="team", preemption=prem)
        cq_b = cq_one_flavor("cq-b", cpu="4", cohort="team")
        cache = build_cache(cq_a, cq_b)
        admit(cache, "low", "cq-b", "5", prio=5, reserved_at=1.0)
        admit(cache, "high", "cq-b", "3", prio=50, reserved_at=2.0)
        assert_target_parity(
            cache, [(pending("new", "cq-a", "6", prio=100), "cq-a")]
        )

    def test_fill_back_keeps_unnecessary_victims(self):
        cq = cq_one_flavor(
            "cq",
            cpu="10",
            preemption=Preemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY
            ),
        )
        cache = build_cache(cq)
        # removal order (lowest prio first): a(2) then b(5); the head
        # needs 5, so both get removed — and fill-back re-adds a because
        # b's removal alone satisfies the request
        admit(cache, "a", "cq", "2", prio=1, reserved_at=1.0)
        admit(cache, "b", "cq", "5", prio=2, reserved_at=2.0)
        admit(cache, "c", "cq", "3", prio=50, reserved_at=3.0)
        got = assert_target_parity(
            cache, [(pending("new", "cq", "5", prio=100), "cq")]
        )
        assert {n for n, _ in got["new"]} == {"b"}

    def test_multiple_heads_one_dispatch(self):
        prem = Preemption(
            within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
            reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
        )
        cqs = [
            cq_one_flavor(f"cq-{i}", cpu="4", cohort="team", preemption=prem)
            for i in range(4)
        ]
        cache = build_cache(*cqs)
        for i in range(4):
            admit(cache, f"v{i}", f"cq-{i}", "6", prio=1, reserved_at=float(i))
        incoming = [
            (pending(f"new{i}", f"cq-{i}", "4", prio=50), f"cq-{i}")
            for i in range(4)
        ]
        assert_target_parity(cache, incoming)


def admit_multi(cache, name, cq, requests, prio=0, reserved_at=0.0):
    wl = Workload(
        namespace="ns", name=name, queue_name=f"lq-{cq}", priority=prio,
        pod_sets=(PodSet.build("main", 1, requests),),
    )
    flavors = {"main": {r: "default" for r in requests}}
    wl.admission = make_admission(cq, flavors, wl)
    wl.set_condition(
        WorkloadConditionType.QUOTA_RESERVED, True, reason="QuotaReserved",
        now=reserved_at,
    )
    cache.add_or_update_workload(wl)
    return wl


def random_preempt_cache(seed):
    rng = np.random.default_rng(seed)
    policies_wcq = [
        PreemptionPolicy.NEVER,
        PreemptionPolicy.LOWER_PRIORITY,
        PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY,
    ]
    policies_rec = [
        ReclaimWithinCohortPolicy.NEVER,
        ReclaimWithinCohortPolicy.ANY,
        ReclaimWithinCohortPolicy.LOWER_PRIORITY,
    ]
    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="default"))
    multi_res = bool(rng.random() < 0.5)
    resources = ("cpu", "memory") if multi_res else ("cpu",)
    n_cohorts = int(rng.integers(1, 3))
    cq_names = []
    for ci in range(n_cohorts):
        for qi in range(int(rng.integers(2, 4))):
            name = f"cq-{ci}-{qi}"
            cq_names.append(name)
            bwc = BorrowWithinCohort()
            if rng.random() < 0.4:
                bwc = BorrowWithinCohort(
                    policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                    max_priority_threshold=(
                        int(rng.integers(0, 60)) if rng.random() < 0.7 else None
                    ),
                )
            prem = Preemption(
                within_cluster_queue=policies_wcq[int(rng.integers(0, 3))],
                reclaim_within_cohort=policies_rec[int(rng.integers(0, 3))],
                borrow_within_cohort=bwc,
            )
            quotas = {}
            for r in resources:
                quota = str(int(rng.integers(4, 12)))
                bl = str(int(rng.integers(0, 12))) if rng.random() < 0.5 else None
                ll = str(int(rng.integers(0, 6))) if rng.random() < 0.4 else None
                quotas[r] = (quota, bl, ll)
            cache.add_or_update_cluster_queue(
                ClusterQueue(
                    name=name,
                    cohort=f"cohort-{ci}",
                    namespace_selector={},
                    resource_groups=(
                        ResourceGroup(
                            resources,
                            (FlavorQuotas.build("default", quotas),),
                        ),
                    ),
                    preemption=prem,
                )
            )

    def rand_requests():
        return {r: str(int(rng.integers(1, 8))) for r in resources}

    # admitted population, deliberately oversubscribed
    n_admitted = int(rng.integers(4, 14))
    for i in range(n_admitted):
        cq_name = cq_names[int(rng.integers(0, len(cq_names)))]
        admit_multi(
            cache,
            f"adm-{i}",
            cq_name,
            rand_requests(),
            prio=int(rng.integers(0, 100)),
            reserved_at=float(i),
        )
    return cache, cq_names, rng, rand_requests


class TestSchedulerCycleParity:
    """Full drain traces with the batched preempt solver on vs off must
    be identical — admissions, preemptions, skips, final placement."""

    @pytest.mark.parametrize("seed", range(12))
    def test_randomized_contended(self, seed):
        from tests.test_solver_path import build_env, drain_and_trace, random_spec

        spec = random_spec(seed, with_preemption=True)
        traces = {}
        finals = {}
        for preempt_solver in (False, True):
            sched, mgr, cache, _ = build_env(spec, use_solver=False)
            sched.use_preempt_solver = preempt_solver
            traces[preempt_solver], finals[preempt_solver] = drain_and_trace(
                sched, mgr, cache
            )
        assert traces[True] == traces[False]
        assert finals[True] == finals[False]


class TestRandomizedParity:
    @pytest.mark.parametrize("seed", range(48))
    def test_seeded(self, seed):
        cache, cq_names, rng, rand_requests = random_preempt_cache(seed)
        snap = take_snapshot(cache)
        assigner = FlavorAssigner(snap, cache.flavors)
        preemptor = Preemptor(FakeClock())
        items = []
        for i in range(6):
            cq_name = cq_names[int(rng.integers(0, len(cq_names)))]
            wl = Workload(
                namespace="ns", name=f"new-{i}", queue_name=f"lq-{cq_name}",
                priority=int(rng.integers(0, 120)), creation_time=float(100 + i),
                pod_sets=(PodSet.build("main", 1, rand_requests()),),
            )
            assignment = assigner.assign(wl, cq_name)
            if assignment.representative_mode() == Mode.PREEMPT:
                items.append((wl, cq_name, assignment))
        if not items:
            pytest.skip("no PREEMPT heads this seed")
        batched = batched_get_targets(snap, items, preemptor)
        for (wl, cq_name, assignment), got in zip(items, batched):
            want = preemptor.get_targets(wl, cq_name, assignment, snap)
            assert targets_set(got) == targets_set(want), (
                seed,
                wl.name,
                targets_set(got),
                targets_set(want),
            )


# ---- the snapshot's preemption-candidate index ----
def _cq(name, cohort=None, prem=None, resources=("cpu",), quota="5"):
    return ClusterQueue(
        name=name,
        cohort=cohort,
        namespace_selector={},
        resource_groups=(
            ResourceGroup(
                resources,
                (FlavorQuotas.build("default", {r: quota for r in resources}),),
            ),
        ),
        preemption=prem or Preemption(),
    )


def _head(name, cq, requests, prio, t=0.0):
    return Workload(
        namespace="ns", name=name, queue_name=f"lq-{cq}", priority=prio,
        creation_time=t, pod_sets=(PodSet.build("main", 1, requests),),
    )


_LOWER = Preemption(within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY)


def _reclaim(policy, within=PreemptionPolicy.NEVER):
    return Preemption(within_cluster_queue=within, reclaim_within_cohort=policy)


def _index_no_cohort_lower():
    cache = build_cache(_cq("cq", prem=_LOWER, quota="10"))
    admit(cache, "low", "cq", "10", prio=1)
    return cache, [(_head("new", "cq", {"cpu": "5"}, 50), "cq", True)]


def _index_no_cohort_higher():
    cache = build_cache(_cq("cq", prem=_LOWER, quota="10"))
    admit(cache, "high", "cq", "10", prio=100)
    return cache, [(_head("new", "cq", {"cpu": "5"}, 50), "cq", False)]


def _index_other_fr_borrower():
    # cq-b borrows memory only; the head needs preemption in cpu
    prem = _reclaim(ReclaimWithinCohortPolicy.ANY, PreemptionPolicy.LOWER_PRIORITY)
    res = ("cpu", "memory")
    cache = build_cache(
        _cq("cq-a", "team", prem, res), _cq("cq-b", "team", None, res)
    )
    admit_multi(cache, "own", "cq-a", {"cpu": "5", "memory": "1"}, prio=100)
    admit_multi(cache, "memhog", "cq-b", {"cpu": "5", "memory": "8"}, prio=0)
    head = _head("new", "cq-a", {"cpu": "3", "memory": "1"}, 50)
    return cache, [(head, "cq-a", True)]


def _index_own_fr_borrower():
    cache = build_cache(
        _cq("cq-a", "team", _reclaim(ReclaimWithinCohortPolicy.ANY)),
        _cq("cq-b", "team"),
    )
    admit(cache, "borrower", "cq-b", "8", prio=100)
    return cache, [(_head("new", "cq-a", {"cpu": "5"}, 0), "cq-a", True)]


def _index_equal_priority(policy):
    def build():
        cache = build_cache(
            _cq("cq", prem=Preemption(within_cluster_queue=policy), quota="10")
        )
        peer = admit(cache, "peer", "cq", "10", prio=50)
        cache.cluster_queues["cq"].workloads[peer.key].creation_time = 10.0
        return cache, [(_head("new", "cq", {"cpu": "5"}, 50, t=-100.0), "cq", True)]

    return build


def _index_within_never():
    cache = build_cache(_cq("cq", quota="10"))
    admit(cache, "low", "cq", "10", prio=1)
    return cache, [(_head("new", "cq", {"cpu": "5"}, 50), "cq", False)]


def _index_reclaim(policy):
    def build():
        cache = build_cache(_cq("cq-a", "team", _reclaim(policy)), _cq("cq-b", "team"))
        admit(cache, "borrower", "cq-b", "8", prio=10)
        maybe = policy != ReclaimWithinCohortPolicy.NEVER
        return cache, [
            (_head("low", "cq-a", {"cpu": "5"}, 0), "cq-a", maybe),
            (_head("high", "cq-a", {"cpu": "5"}, 50), "cq-a", maybe),
        ]

    return build


def _index_fair_borrowers():
    prem = _reclaim(ReclaimWithinCohortPolicy.ANY)
    cache = build_cache(
        _cq("cq-a", "team", prem, quota="4"), _cq("cq-b", "team", quota="4"),
        _cq("cq-c", "team", quota="4"),
    )
    admit(cache, "hog", "cq-b", "8", prio=0, reserved_at=1.0)
    admit(cache, "slight", "cq-c", "4", prio=0, reserved_at=2.0)
    return cache, [(_head("new", "cq-a", {"cpu": "4"}, 0), "cq-a", True)]


def _index_fair_at_nominal():
    prem = _reclaim(ReclaimWithinCohortPolicy.ANY, PreemptionPolicy.LOWER_PRIORITY)
    cache = build_cache(
        _cq("cq-a", "team", prem, quota="4"), _cq("cq-b", "team", quota="4")
    )
    admit(cache, "own", "cq-a", "4", prio=100)
    admit(cache, "peer", "cq-b", "4", prio=0)
    return cache, [(_head("new", "cq-a", {"cpu": "2"}, 50), "cq-a", False)]


def _index_invalidated():
    # cq-b sits at nominal until an in-cycle add_usage pushes it over;
    # removing its workload brings it back under
    cache = build_cache(
        _cq("cq-a", "team", _reclaim(ReclaimWithinCohortPolicy.ANY, PreemptionPolicy.LOWER_PRIORITY)),
        _cq("cq-b", "team"),
    )
    admit(cache, "keep", "cq-a", "5", prio=100)
    admit(cache, "b1", "cq-b", "5", prio=0)
    return cache, [(_head("new", "cq-a", {"cpu": "3"}, 50), "cq-a", False)]


def _push_cq_b_over(snap):
    vec = np.zeros(len(snap.fr_list), dtype=np.int64)
    vec[snap.fr_index[FlavorResource("default", "cpu")]] = 3000
    snap.add_usage("cq-b", vec)
    return True


def _remove_b1(snap):
    snap.remove_workload("ns/b1")
    return False


# case -> (builder, fair sharing, in-cycle mutations each with the
# index's answer it leads to)
INDEX_CASES = {
    "no_cohort_lower_priority": (_index_no_cohort_lower, False, ()),
    "no_cohort_higher_priority": (_index_no_cohort_higher, False, ()),
    "borrower_in_other_flavor_resource": (_index_other_fr_borrower, False, ()),
    "borrower_in_own_flavor_resource": (_index_own_fr_borrower, False, ()),
    "equal_priority_lower_or_newer": (
        _index_equal_priority(PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY), False, ()),
    "equal_priority_lower": (
        _index_equal_priority(PreemptionPolicy.LOWER_PRIORITY), False, ()),
    "within_cluster_queue_never": (_index_within_never, False, ()),
    "reclaim_never": (_index_reclaim(ReclaimWithinCohortPolicy.NEVER), False, ()),
    "reclaim_lower_priority": (
        _index_reclaim(ReclaimWithinCohortPolicy.LOWER_PRIORITY), False, ()),
    "reclaim_any": (_index_reclaim(ReclaimWithinCohortPolicy.ANY), False, ()),
    "fair_sharing_borrowers": (_index_fair_borrowers, True, ()),
    "fair_sharing_at_nominal": (_index_fair_at_nominal, True, ()),
    "invalidated_in_cycle": (_index_invalidated, False, (_push_cq_b_over, _remove_b1)),
}


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_candidate_index_keeps_decisions(case):
    """The index only rules heads out: host get_targets and the batched
    search equal the full candidate scan, and a head it rules out has
    no candidate at all."""
    from kueue_tpu.core.preemption import _Ctx

    build, fair, mutations = INDEX_CASES[case]
    cache, heads = build()
    snap = take_snapshot(cache)
    assigner = FlavorAssigner(snap, cache.flavors)
    preemptor = Preemptor(FakeClock(), enable_fair_sharing=fair)
    items, expected = [], []
    for wl, cq_name, maybe in heads:
        assignment = assigner.assign(wl, cq_name)
        assert assignment.representative_mode() == Mode.PREEMPT, wl.name
        items.append((wl, cq_name, assignment))
        expected.append(maybe)
    for step in (None,) + tuple(mutations):
        if step is not None:
            expected = [step(snap)] * len(items)
        batched = batched_get_targets(snap, items, preemptor)
        for (wl, cq_name, assignment), maybe, got in zip(items, expected, batched):
            ctx = _Ctx(
                preemptor=wl, cq_name=cq_name, cq_row=snap.row(cq_name),
                snapshot=snap,
                frs_need_preemption=preemptor._frs_need_preemption(assignment),
                usage_vec=snap.vector_of(assignment.usage),
            )
            candidates = preemptor._find_candidates(ctx)
            full = targets_set(preemptor._get_targets(ctx))
            answered = preemptor.no_candidates
            host = targets_set(preemptor.get_targets(wl, cq_name, assignment, snap))
            assert preemptor.no_candidates - answered == (0 if maybe else 1)
            assert preemptor.may_have_candidates(wl, cq_name, snap) == maybe
            if not maybe:
                assert candidates == []
            assert host == full == targets_set(got), (wl.name, host, full)
