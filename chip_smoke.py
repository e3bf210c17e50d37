#!/usr/bin/env python3
"""Chip smoke: the admission drain's main path, end to end, on one TPU.

Everything runs in this one process, so only it holds the chip:

1. Device check. The first line names the platform, device kind and
   device count. Anything but ``tpu`` exits non-zero before any phase.
2. Plain phase. ``KueueServer`` serves on a thread over a
   ``ClusterRuntime``. The BASELINE.json north-star deployment (8
   ResourceFlavors, 1,000 ClusterQueues in 50 cohorts with borrowing
   limits, one LocalQueue each, 50,000 pending workloads) is
   batch-applied through ``KueueClient`` and ``POST /reconcile`` drains
   it to quiescence: first in the default ``auto`` mode from a cold
   process (its compiles must not count against the guard's deadline,
   so nothing fails over), then with ``solver_path="device"``, where
   device faults propagate instead of failing over to the host.
3. Preempt phase. The contended cross-CQ-reclaim scope (5,000 pending
   over 1,000 CQs in hoarder/reclaimer cohorts, shaped like
   ``bench.py:contended_drain_bench``) runs through
   ``ClusterRuntime.run_until_idle`` on the runtime's running clock.

Each phase is held to a reference: a ``ClusterRuntime(solver_path=
"host", use_solver=False, use_preempt_solver=False)`` fed the same
objects, which decides on the host cycle loop with Python and numpy and
never touches the chip. Its admitted ``(namespace, name, flavors)`` set
must equal the device run's. Times printed here are smoke timings, not
benchmark results.

The last line of a passing run is ``{"ok": true, "device": {...}}``. Any
failed check or exception exits non-zero without it.

``--multichip`` runs only the mesh path: the plain and contended drains
on device 0 and under ``resolve_mesh("auto")``, requiring equal decision
signatures and drain inputs placed across every device.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np

N_FLAVORS = 8
N_CQ = 1000
N_COHORT = 50
WL_PER_CQ = 50  # 50,000 pending
CONTENDED_COHORT_SIZE = 10  # 500 reclaimers x 10 = 5,000 pending


class CheckFailed(Exception):
    """A smoke check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def compile_meter():
    """Seconds every thread of this process spent in XLA backend
    compiles (persistent-cache fetches included) while active, read
    from the solver guard's own accounting. A cache hit is timed in the
    same event, so a warm cache shows as fewer compile seconds."""
    from kueue_tpu.core.guard import compile_seconds

    c0 = compile_seconds(all_threads=True)
    spent = {}
    try:
        yield spent
    finally:
        spent["compileSeconds"] = round(
            compile_seconds(all_threads=True) - c0, 3
        )


# ---- the deployments, generated from a seed ----
def plain_deployment(
    seed: int,
    n_cq: int = N_CQ,
    n_cohort: int = N_COHORT,
    n_flavors: int = N_FLAVORS,
    wl_per_cq: int = WL_PER_CQ,
) -> dict:
    """The north-star deployment in the flat batch wire format, with the
    shapes of ``bench.py:_build_drain_loop_rt``: per flavor cpu nominal
    8-63 (borrowing 8-31) and memory 16-127Gi (borrowing 16-63Gi);
    workloads of 1-4 pods asking 1-15 cpu and 1-31Gi at four priority
    levels, spread round-robin over the LocalQueues."""
    rng = np.random.default_rng(seed)
    flavors = [f"fl-{i}" for i in range(n_flavors)]
    cpu_nom = rng.integers(8, 64, size=(n_cq, n_flavors))
    cpu_borrow = rng.integers(8, 32, size=(n_cq, n_flavors))
    mem_nom = rng.integers(16, 128, size=(n_cq, n_flavors))
    mem_borrow = rng.integers(16, 64, size=(n_cq, n_flavors))
    cqs = [
        {
            "name": f"pcq-{i}",
            "cohort": f"pcohort-{i % n_cohort}",
            "namespaceSelector": {},
            "resourceGroups": [
                {
                    "coveredResources": ["cpu", "memory"],
                    "flavors": [
                        {
                            "name": f,
                            "resources": [
                                {
                                    "name": "cpu",
                                    "nominalQuota": str(cpu_nom[i, k]),
                                    "borrowingLimit": str(cpu_borrow[i, k]),
                                },
                                {
                                    "name": "memory",
                                    "nominalQuota": f"{mem_nom[i, k]}Gi",
                                    "borrowingLimit": f"{mem_borrow[i, k]}Gi",
                                },
                            ],
                        }
                        for k, f in enumerate(flavors)
                    ],
                }
            ],
        }
        for i in range(n_cq)
    ]
    n = n_cq * wl_per_cq
    prios = rng.integers(0, 4, size=n) * 50
    cpus = rng.integers(1, 16, size=n)
    mems = rng.integers(1, 32, size=n)
    counts = rng.integers(1, 5, size=n)
    workloads = [
        {
            "namespace": "ns",
            "name": f"pw{j}",
            "queueName": f"plq-{j % n_cq}",
            "priority": int(prios[j]),
            "creationTime": float(j),
            "podSets": [
                {
                    "name": "main",
                    "count": int(counts[j]),
                    "requests": {"cpu": str(cpus[j]), "memory": f"{mems[j]}Gi"},
                }
            ],
        }
        for j in range(n)
    ]
    return {
        "resourceflavors": [{"name": f} for f in flavors],
        "clusterqueues": cqs,
        "localqueues": [
            {"namespace": "ns", "name": f"plq-{i}", "clusterQueue": f"pcq-{i}"}
            for i in range(n_cq)
        ],
        "workloads": workloads,
    }


def populate_contended(rt, seed: int, n_cq: int = N_CQ,
                       cohort_size: int = CONTENDED_COHORT_SIZE) -> None:
    """The contended reclaim scope of ``bench.py:contended_drain_bench``
    added to ``rt``: per cohort, half the CQs are hoarders admitted
    above nominal (8 x 3 cpu against 16, never preempting) and half are
    reclaimers (4 x 2 cpu admitted) whose higher-priority backlog of 10
    each can only start by cross-CQ reclaim or within-CQ preemption."""
    from kueue_tpu.core.workload_info import make_admission
    from kueue_tpu.models import (
        ClusterQueue,
        FlavorQuotas,
        LocalQueue,
        Preemption,
        ResourceFlavor,
        Workload,
        WorkloadConditionType,
    )
    from kueue_tpu.models.cluster_queue import BorrowWithinCohort, ResourceGroup
    from kueue_tpu.models.constants import (
        BorrowWithinCohortPolicy,
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
    )
    from kueue_tpu.models.workload import PodSet

    rng = np.random.default_rng(seed)
    rt.add_flavor(ResourceFlavor(name="default"))
    for i in range(n_cq):
        name = f"ccq-{i}"
        hoarder = (i % cohort_size) < cohort_size // 2
        if hoarder:
            prem = Preemption()
        else:
            prem = Preemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=(
                    ReclaimWithinCohortPolicy.ANY
                    if i % 2
                    else ReclaimWithinCohortPolicy.LOWER_PRIORITY
                ),
                borrow_within_cohort=(
                    BorrowWithinCohort(
                        policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                        max_priority_threshold=60,
                    )
                    if i % 3 == 0
                    else BorrowWithinCohort()
                ),
            )
        rt.add_cluster_queue(
            ClusterQueue(
                name=name,
                cohort=f"ccohort-{i // cohort_size}",
                namespace_selector={},
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (FlavorQuotas.build("default", {"cpu": "16"}),),
                    ),
                ),
                preemption=prem,
            )
        )
        rt.add_local_queue(
            LocalQueue(namespace="ns", name=f"lq-{name}", cluster_queue=name)
        )
        n_vic, v_cpu = (8, "3") if hoarder else (4, "2")
        for v in range(n_vic):
            wl = Workload(
                namespace="ns", name=f"victim-{i}-{v}",
                queue_name=f"lq-{name}", priority=int(rng.integers(0, 40)),
                creation_time=float(v),
                pod_sets=(PodSet.build("main", 1, {"cpu": v_cpu}),),
            )
            wl.admission = make_admission(name, {"main": {"cpu": "default"}}, wl)
            wl.set_condition(
                WorkloadConditionType.QUOTA_RESERVED, True,
                reason="QuotaReserved", now=float(v),
            )
            rt.add_workload(wl)
        if not hoarder:
            for w in range(10):
                rt.add_workload(
                    Workload(
                        namespace="ns", name=f"pre-{i}-{w}",
                        queue_name=f"lq-{name}",
                        priority=50 + 10 * int(rng.integers(0, 6)),
                        creation_time=float(1000 + i * 10 + w),
                        pod_sets=(
                            PodSet.build(
                                "main", 1,
                                {"cpu": str(int(rng.integers(2, 8)))},
                            ),
                        ),
                    )
                )


# ---- reading the outcome ----
def admitted_set(state: dict) -> frozenset:
    """(namespace, name, flavor assignment) of every workload holding
    quota in a ``/state`` dump."""
    out = set()
    for wl in state["workloads"]:
        if not any(
            c["type"] == "QuotaReserved" and c["status"]
            for c in wl["conditions"]
        ):
            continue
        psas = (wl.get("admission") or {}).get("podSetAssignments", ())
        flavors = tuple(
            sorted((p["name"], tuple(sorted(p["flavors"].items()))) for p in psas)
        )
        out.add((wl["namespace"], wl["name"], flavors))
    return frozenset(out)


def run_to_quiescence(rt) -> None:
    rt.run_until_idle(max_iterations=100_000)


def run_deleting_preempted(rt, limit: int = 100_000) -> frozenset:
    """``run_until_idle`` one iteration at a time, deleting every
    workload whose preemption has completed, as the controller of a job
    that does not retry would, until an iteration changes nothing and
    every pending head is parked.

    A preempted workload that requeues keeps the contended scope from
    ever settling on either path: reclaimers that borrow reclaim from
    each other in turn, and requeued victims flip between LostQuotaRace
    and InsufficientQuota every cycle. Deleting them makes the end state
    the compressed-eviction one the preempt drain decides. Returns the
    keys of the deleted (preempted) workloads."""
    from kueue_tpu.models import WorkloadConditionType

    def reserved():
        return {k for k, wl in rt.workloads.items() if wl.has_quota_reservation}

    deleted = set()
    for _ in range(limit):
        before = reserved()
        rt.run_until_idle(max_iterations=1)
        gone = [
            wl for wl in rt.workloads.values()
            if wl.condition_true(WorkloadConditionType.PREEMPTED)
            and not wl.has_quota_reservation
        ]
        for wl in gone:
            deleted.add(wl.key)
            rt.delete_workload(wl)
        active = sum(
            pq.pending_active() for pq in rt.queues.cluster_queues.values()
        )
        if not gone and not active and reserved() == before:
            return frozenset(deleted)
    raise CheckFailed(f"not settled after {limit} iterations")


def host_reference(populate, settle=run_to_quiescence) -> tuple:
    """(state dump, what ``settle`` returned, seconds) of the host cycle
    loop over the objects ``populate(rt)`` adds, run by ``settle(rt)``:
    no solver, no preempt solver, no drain."""
    from kueue_tpu import serialization as ser
    from kueue_tpu.controllers import ClusterRuntime

    rt = ClusterRuntime(
        solver_path="host", use_solver=False,
        use_preempt_solver=False, bulk_drain_threshold=None,
    )
    t0 = time.perf_counter()
    populate(rt)
    settled = settle(rt)
    return ser.runtime_to_state(rt), settled, time.perf_counter() - t0


def check_same(what: str, device: frozenset, host: frozenset) -> None:
    """Check d: the device run decided what the host reference did."""
    check(
        device == host,
        f"{what} sets differ: {len(device - host)} only on device (e.g. "
        f"{sorted(device - host)[:3]}), {len(host - device)} only on "
        f"host (e.g. {sorted(host - device)[:3]})",
    )


def check_guard(health: dict) -> None:
    """Check b: the device decided, and nothing fell to the host."""
    check(health["deviceSolves"] >= 1, f"no device solve: {health}")
    check(health["path"] == "device", f"solver path left the device: {health}")
    check(health["breaker"] == "closed", f"breaker tripped: {health}")
    check(
        health["failovers"] == 0 and health["divergences"] == 0,
        f"host-mirror solve (failover/divergence): {health}",
    )


def _drain_rounds(cycles: list) -> list:
    return [c for c in cycles if c["resolution"] == "drain"]


def _round_summary(rounds: list) -> list:
    return [
        {"heads": c["heads"], "admitted": c["admitted"],
         "preempting": c["preempting"],
         "solveMs": c["spansMs"].get("solve")}
        for c in rounds
    ]


# ---- phase 1: the north-star backlog through the server ----
def serve_and_drain(body: dict, solver_path: str) -> dict:
    """Batch-apply ``body`` to a fresh ``KueueServer`` over a
    ``ClusterRuntime(solver_path=...)`` and ``POST /reconcile`` until
    the backlog is quiescent; checks a, b and c."""
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.server import KueueServer
    from kueue_tpu.server.client import KueueClient

    n_pending = len(body["workloads"])
    srv = KueueServer(
        runtime=ClusterRuntime(solver_path=solver_path), auto_reconcile=False
    )
    port = srv.start()
    try:
        client = KueueClient(f"http://127.0.0.1:{port}", timeout=1800.0)
        t0 = time.perf_counter()
        applied = client.apply_batch(body)
        t_apply = time.perf_counter() - t0
        check(not applied["rejected"], f"batch rejected objects: {applied}")
        with compile_meter() as compiled:
            t0 = time.perf_counter()
            reconciles = 0
            while True:
                reconciles += 1
                cycles = client.reconcile()["cycles"]
                if cycles <= 1 or reconciles >= 64:
                    break
            t_drain = time.perf_counter() - t0
        check(cycles <= 1, f"backlog not quiescent after {reconciles} reconciles")
        traces = client._request("GET", "/debug/cycles")["cycles"]
        health = client.healthz()["solver"]
        t0 = time.perf_counter()
        state = client.state()
        t_state = time.perf_counter() - t0
    finally:
        srv.stop()

    rounds = _drain_rounds(traces)
    # check a: drain rounds decided the backlog, the first seeing all of it
    check(rounds, f"{solver_path}: /debug/cycles shows no drain round")
    check(
        rounds[0]["heads"] == n_pending,
        f"first drain round saw {rounds[0]['heads']} of {n_pending} heads",
    )
    check_guard(health)  # check b
    admitted = admitted_set(state)
    check(len(admitted) >= 1, "nothing admitted")  # check c
    return {
        "solverPath": solver_path,
        "admitted": admitted,
        "drainRounds": len(rounds),
        "drainAdmitted": sum(c["admitted"] for c in rounds),
        "firstRounds": _round_summary(rounds[:3]),
        "deviceSolves": health["deviceSolves"],
        "failovers": health["failovers"],
        "firstSolveS": round(
            (rounds[0]["spansMs"].get("solve") or 0.0) / 1e3, 3
        ),
        "compile": compiled,
        "wallS": {
            "apply": round(t_apply, 3),
            "reconcile": round(t_drain, 3),
            "state": round(t_state, 3),
        },
    }


def run_plain_phase(seed: int = 0, n_cq: int = N_CQ, n_cohort: int = N_COHORT,
                    n_flavors: int = N_FLAVORS,
                    wl_per_cq: int = WL_PER_CQ) -> dict:
    """The north-star backlog served twice: first in the default
    ``auto`` mode from a cold process — its first drains compile, and
    the guard must not count that against its deadline and fail over —
    then in ``device`` mode, where device faults propagate. Both must
    decide what the host reference does."""
    from kueue_tpu.core.guard import GuardConfig
    from kueue_tpu.server import KueueServer

    t0 = time.perf_counter()
    body = plain_deployment(seed, n_cq, n_cohort, n_flavors, wl_per_cq)
    t_gen = time.perf_counter() - t0
    runs = [serve_and_drain(body, path) for path in ("auto", "device")]

    def populate(rt):
        KueueServer(runtime=rt, auto_reconcile=False).apply_batch(body)

    ref_state, _, t_host = host_reference(populate)
    ref = admitted_set(ref_state)
    for run in runs:
        check_same(f"{run['solverPath']}: admitted", run["admitted"], ref)  # d
        run["admitted"] = len(run["admitted"])
    return {
        "phase": "plain",
        "pending": len(body["workloads"]),
        "clusterQueues": n_cq,
        "admitted": len(ref),
        "deviceDeadlineS": GuardConfig().device_deadline_s,
        "runs": runs,
        "wallS": {"generate": round(t_gen, 3), "hostReference": round(t_host, 3)},
        "matchesHostReference": True,
    }


# ---- phase 2: classic preemption, cross-CQ reclaim ----
def run_preempt_phase(seed: int = 1, n_cq: int = N_CQ,
                      cohort_size: int = CONTENDED_COHORT_SIZE) -> dict:
    """The contended reclaim scope on the runtimes' own running clocks:
    the host cycle loop stamps each admission as it applies it, and the
    preempt drain ranks its admissions as reclaim candidates the same
    way."""
    from kueue_tpu import serialization as ser
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.core.guard import GuardConfig

    def populate(rt):
        populate_contended(rt, seed, n_cq, cohort_size)

    rt = ClusterRuntime(solver_path="device")
    populate(rt)
    n_pending = sum(
        pq.pending_active() for pq in rt.queues.cluster_queues.values()
    )
    with compile_meter() as compiled:
        t0 = time.perf_counter()
        preempted = run_deleting_preempted(rt)
        t_drain = time.perf_counter() - t0
    rounds = _drain_rounds([t.to_dict() for t in rt.scheduler.last_traces])
    check(rounds, "no drain round decided the contended backlog")
    check(
        any(c["preempting"] for c in rounds),
        "no drain round preempted",
    )
    check_guard(rt.scheduler.guard.health())
    state = ser.runtime_to_state(rt)
    admitted = admitted_set(state)
    check(len(admitted) >= 1, "nothing admitted")
    check(len(preempted) > 0, "no workload evicted")

    ref_state, ref_preempted, t_host = host_reference(
        populate, run_deleting_preempted
    )
    check_same("admitted", admitted, admitted_set(ref_state))
    check_same("evicted", preempted, ref_preempted)
    first_solve_s = (rounds[0]["spansMs"].get("solve") or 0.0) / 1e3
    return {
        "phase": "preempt",
        "pending": n_pending,
        "clusterQueues": n_cq,
        "admitted": len(admitted),
        "evicted": len(preempted),
        "drainRounds": len(rounds),
        "firstRounds": _round_summary(rounds[:3]),
        "deviceSolves": rt.scheduler.guard.device_solves,
        "firstSolveS": round(first_solve_s, 3),
        "deviceDeadlineS": GuardConfig().device_deadline_s,
        "compile": compiled,
        "wallS": {
            "runUntilIdle": round(t_drain, 3),
            "hostReference": round(t_host, 3),
        },
        "matchesHostReference": True,
    }


# ---- --multichip: one device vs the full local mesh ----
@contextlib.contextmanager
def placed_device_counts():
    """Record how many devices each placed drain input is split over
    (1 for a replicated one), by wrapping the sharded placement entry
    points the drains call."""
    from kueue_tpu._jax import jax
    from kueue_tpu.parallel import sharded_solver

    seen = []
    names = ("place_drain_inputs", "place_preempt_drain_inputs")
    originals = {n: getattr(sharded_solver, n) for n in names}

    def wrap(fn):
        def placed(*args, **kwargs):
            out = fn(*args, **kwargs)
            seen.extend(
                1 if leaf.sharding.is_fully_replicated
                else len(leaf.sharding.device_set)
                for leaf in jax.tree_util.tree_leaves(out)
            )
            return out
        return placed

    for n, fn in originals.items():
        setattr(sharded_solver, n, wrap(fn))
    try:
        yield seen
    finally:
        for n, fn in originals.items():
            setattr(sharded_solver, n, fn)


def _drain_signature(outcome) -> dict:
    from kueue_tpu.core.pipeline import outcome_signature

    sig = outcome_signature(outcome)
    sig["preempted"] = sorted(
        wl.key for wl, *_ in getattr(outcome, "preempted", ())
    )
    return sig


def _one_vs_mesh(label: str, populate, mesh) -> dict:
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.core.drain import classify_drain_scope, run_drain_for_scope
    from kueue_tpu.core.queue_manager import queue_order_timestamp
    from kueue_tpu.core.snapshot import take_snapshot

    rt = ClusterRuntime()
    populate(rt)
    rt.reconcile_once()

    def drain(mesh_):
        snapshot = take_snapshot(rt.cache)
        pending = rt.drain_backlog(snapshot)
        kind, pending = classify_drain_scope(snapshot, pending, set(), False)
        t0 = time.perf_counter()
        out = run_drain_for_scope(
            kind, snapshot, pending, rt.cache.flavors,
            timestamp_fn=lambda wl: queue_order_timestamp(
                wl, rt.queues._ts_policy
            ),
            mesh=mesh_,
            ordered_stamps=True,  # the server's running clock
        )
        return kind, out, time.perf_counter() - t0

    kind, single, t_single = drain(None)
    with placed_device_counts() as spans:
        kind_m, meshed, t_mesh = drain(mesh)
    check(kind == kind_m, f"{label}: scope changed under the mesh")
    check(single.admitted, f"{label}: single-device drain admitted nothing")
    check(
        _drain_signature(single) == _drain_signature(meshed),
        f"{label}: decision signatures differ between 1 device and the mesh",
    )
    check(spans, f"{label}: the mesh drain placed no inputs")
    check(
        max(spans) == mesh.size,
        f"{label}: placed inputs split over at most {max(spans)} of "
        f"{mesh.size} devices",
    )
    return {
        "scope": kind,
        "admitted": len(single.admitted),
        "preempted": len(getattr(single, "preempted", ())),
        "cycles": single.cycles,
        "signaturesEqual": True,
        "placedDeviceSpans": sorted(set(spans)),
        "wallS": {"oneDevice": round(t_single, 3), "mesh": round(t_mesh, 3)},
    }


def run_multichip(seed: int = 0, n_cq: int = N_CQ,
                  wl_per_cq: int = WL_PER_CQ) -> dict:
    from kueue_tpu import serialization as ser
    from kueue_tpu._jax import jax
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.core.drain import PanelTuner
    from kueue_tpu.parallel import resolve_mesh
    from kueue_tpu.parallel.harness import (
        last_panel_schedule,
        narrow_panels_supported,
    )

    mesh = resolve_mesh("auto")
    n_dev = len(jax.devices())
    check(mesh is not None and mesh.size == n_dev,
          f"resolve_mesh('auto') gave {mesh} over {n_dev} devices")
    mesh_devices = ClusterRuntime(mesh=mesh).mesh_status()["devices"]
    check(mesh_devices == n_dev, f"kueue_mesh_devices {mesh_devices} != {n_dev}")

    body = plain_deployment(seed, n_cq, N_COHORT, N_FLAVORS, wl_per_cq)

    def populate_plain(rt):
        for f in body["resourceflavors"]:
            rt.add_flavor(ser.flavor_from_dict(f))
        for cq in body["clusterqueues"]:
            rt.add_cluster_queue(ser.cq_from_dict(cq))
        for lq in body["localqueues"]:
            rt.add_local_queue(ser.lq_from_dict(lq))
        for wl in body["workloads"]:
            rt.add_workload(ser.workload_from_dict(wl))

    drains = (
        ("plain", populate_plain),
        ("preempt", lambda rt: populate_contended(rt, seed + 1, n_cq)),
    )
    for label, populate in drains:
        with compile_meter() as compiled:
            report = _one_vs_mesh(label, populate, mesh)
        report.update(meshDevices=mesh_devices, compile=compiled)
        say(f"smoke timing (not a benchmark): {json.dumps(report)}")
    # the narrow rungs below the preempt drain's exact search width
    widths = [w for w in PanelTuner.LADDER if w < 32]
    return {
        "phase": "multichip",
        "meshDevices": mesh_devices,
        "panelSchedule": last_panel_schedule(),
        "narrowPanelVerdicts": {
            str(w): narrow_panels_supported(mesh, w) for w in widths
        },
    }


# ---- entry point ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--multichip", action="store_true",
        help="run only the one-device vs mesh drains (needs >= 2 chips)",
    )
    args = ap.parse_args(argv)

    from kueue_tpu._jax import jax, use_compile_cache

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"device: {json.dumps(device)}")
    if device["platform"] != "tpu":
        say(f"chip smoke needs a TPU; JAX found {device['platform']!r}")
        return 2
    say(f"compile cache: {use_compile_cache()}")

    try:
        if args.multichip:
            say(f"narrow-panel probe: {json.dumps(run_multichip(args.seed))}")
        else:
            for run in (
                lambda: run_plain_phase(args.seed),
                lambda: run_preempt_phase(args.seed + 1),
            ):
                report = run()
                say(f"smoke timing (not a benchmark): {json.dumps(report)}")
    except CheckFailed as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
