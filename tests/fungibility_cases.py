"""Shared by the fungibility comparison tests: seeded random deployments
with one or two resource groups and a random ``whenCanBorrow`` per
ClusterQueue, the program's runtimes on each decision path, and the
pass loop that logs what they admitted for the plain reference
(``benchmark/references/kueue_fungible.py``) to replay.

Not a test module (no ``test_`` prefix)."""

import json

import numpy as np

import chip_smoke
from benchmark.loops.passes import _decisions, replay
from benchmark.references.kueue_fungible import Reference
from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.core.scheduler import _LatencyEstimate
from kueue_tpu.server import KueueServer

SEEDS = [3, 11, 2**31 + 5, 2**32 + 17, 41, 97, 2**33 + 1, 1234567]
# groups x flavors of a deployment: at most 8 candidates a head, the
# kernels' K
SHAPES = [(2,), (3,), (4,), (2, 2), (2, 3), (3, 2), (2, 4), (4, 2)]
N_COHORTS, PER_COHORT = 3, 6  # 18 ClusterQueues: above the 16-head threshold
PER_CQ = 15  # 270 pending a pass: above the bulk drain's 256


class _OpenGate(_LatencyEstimate):
    """The drain's latency gate pinned open: these tests compare the
    drain's decisions, not the routing heuristic."""

    @property
    def value(self):
        return None


def deployment(seed, shape=None):
    """The flavors, ClusterQueues and LocalQueues of one random
    deployment, and the number of its resource groups."""
    rng = np.random.default_rng(seed)
    shape = shape or SHAPES[int(rng.integers(0, len(SHAPES)))]
    groups = [
        (["cpu", "memory"] if g == 0 else ["gpu"], [f"g{g}f{k}" for k in range(n)])
        for g, n in enumerate(shape)
    ]
    cqs = []
    for i in range(N_COHORTS * PER_COHORT):
        rgs = []
        for resources, names in groups:
            flavors = []
            for f in names:
                quotas = []
                for r in resources:
                    n = int(rng.integers(4, 40)) if r == "memory" else int(rng.integers(2, 12))
                    q = {"name": r, "nominalQuota": f"{n}Gi" if r == "memory" else str(n)}
                    if rng.random() < 0.3:
                        b = int(rng.integers(0, 8))
                        q["borrowingLimit"] = f"{b}Gi" if r == "memory" else str(b)
                    quotas.append(q)
                flavors.append({"name": f, "resources": quotas})
            rgs.append({"coveredResources": resources, "flavors": flavors})
        cqs.append({
            "name": f"cq-{i}",
            "cohort": f"co-{i // PER_COHORT}",
            "namespaceSelector": {},
            "flavorFungibility": {
                "whenCanBorrow": "TryNextFlavor" if rng.random() < 0.5 else "Borrow",
                "whenCanPreempt": "TryNextFlavor",
            },
            "resourceGroups": rgs,
        })
    body = {
        "resourceflavors": [{"name": f} for _, names in groups for f in names],
        "clusterqueues": cqs,
        "localqueues": [
            {"namespace": "ns", "name": f"lq-{i}", "clusterQueue": f"cq-{i}"}
            for i in range(len(cqs))
        ],
    }
    return body, len(shape), rng


def workloads(rng, n_groups, n_cq, prefix, t0):
    out = []
    for j in range(n_cq * PER_CQ):
        req = {"cpu": str(int(rng.integers(1, 7))),
               "memory": f"{int(rng.integers(1, 9))}Gi"}
        gpu = int(rng.integers(0, 4)) if n_groups > 1 else 0
        if gpu:
            req["gpu"] = str(gpu)
        out.append({
            "namespace": "ns", "name": f"{prefix}{j}", "queueName": f"lq-{j % n_cq}",
            "priority": int(rng.integers(0, 3)) * 50, "creationTime": t0 + j,
            "podSets": [{"name": "main", "count": 1, "requests": req}],
        })
    return out


def runtime(path, chunk=16):
    if path == "host":
        return ClusterRuntime(solver_path="host", use_solver=False,
                              use_preempt_solver=False, bulk_drain_threshold=None)
    if path == "device":
        return ClusterRuntime(use_solver=True, bulk_drain_threshold=None)
    rt = ClusterRuntime(
        use_solver=True, bulk_drain_threshold=256, drain_gate=_OpenGate(),
        pipeline_chunk_cycles=chunk,
        drain_pipeline="serial" if path == "serial" else "on",
        drain_megaloop="on" if path == "megaloop" else "off",
    )
    rt.guard.config.divergence_check_every = 0
    return rt


def admitted(rt):
    return {
        k: (wl.admission.cluster_queue,
            tuple(sorted((p.name, r, f) for p in wl.admission.pod_set_assignments
                         for r, f in p.flavors.items())),
            None)
        for k, wl in rt.workloads.items() if wl.has_quota_reservation
    }


def settle(rt):
    for _ in range(100):
        rt.run_until_idle(max_iterations=1000)
        if not any(pq.pending_active() for pq in rt.queues.cluster_queues.values()):
            return


def run_passes(rt, body, batches):
    """Load ``body``, then one pass a batch (the last pass's workloads
    leave first); the pass loop's decision log."""
    srv = KueueServer(runtime=rt, auto_reconcile=False)
    srv.apply_batch(body)
    log = [((), json.dumps(body), _decisions(admitted(rt)))]
    live = []
    for batch in batches:
        for key in live:
            rt.delete_workload(rt.workloads[key])
        srv.apply_batch({"workloads": batch})
        settle(rt)
        log.append((tuple(live), json.dumps({"workloads": batch}),
                    _decisions(admitted(rt))))
        live = [f"ns/{w['name']}" for w in batch]
    return log


def compare(path, seed, passes=2, chunk=16):
    body, n_groups, rng = deployment(seed)
    n_cq = len(body["clusterqueues"])
    batches = [workloads(rng, n_groups, n_cq, f"p{p}w", 1e5 * p) for p in range(passes)]
    rt = runtime(path, chunk)
    compared = replay(Reference(), run_passes(rt, body, batches))
    return compared, list(rt.scheduler.last_traces)


def assert_equal(compared, passes):
    assert [c["mismatched"] for c in compared] == [0] * (passes + 1), compared
    assert all(c["program"] > 0 for c in compared[1:])


def one_cohort(seed, cohort, n_cq=200, n_cohort=10, per_cq=50):
    """One cohort of ``chip_smoke.plain_deployment``'s 8-flavor shapes
    at 200 ClusterQueues: 20 of them and their 1,000 workloads."""
    full = chip_smoke.plain_deployment(seed, n_cq=n_cq, n_cohort=n_cohort,
                                       wl_per_cq=per_cq)
    keep = {f"pcq-{i}" for i in range(n_cq) if i % n_cohort == cohort}
    body = {
        "resourceflavors": full["resourceflavors"],
        "clusterqueues": [c for c in full["clusterqueues"] if c["name"] in keep],
        "localqueues": [q for q in full["localqueues"] if q["clusterQueue"] in keep],
    }
    queues = {q["name"] for q in body["localqueues"]}
    return body, [w for w in full["workloads"] if w["queueName"] in queues]
