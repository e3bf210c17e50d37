"""Shared mesh-sharding harness for the drain family.

PR-8 grows ``parallel/`` from a plain-cycle side module into the ONE
place every drain-family kernel (plain / contended-preempt / fair /
TAS) routes its mesh concerns through:

  - **mesh resolution** (``resolve_mesh``): the server's ``--mesh
    auto|N|off`` spec -> a ``jax.sharding.Mesh`` (None for ``auto`` on
    a one-device machine — sharding a 1-device "mesh" would only add
    partitioner overhead; an explicit N the machine lacks raises);
  - **size-bucketed jit-cache accounting** (``note_bucket``): every
    sharded solve registers its (kernel, padded static shapes, mesh)
    key — exactly the tuple ``jax.jit`` caches executables on — so the
    SIGUSR2 dump and the dashboard can show bucket compile/reuse rates
    (a low hit rate means the size buckets are mistuned and every
    backlog shape recompiles);
  - **placement accounting** (``note_place_seconds``): cumulative host
    wall time spent in ``device_put`` sharding of drain inputs (the
    observable host-side cost of the mesh; feeds
    ``kueue_mesh_allgather_seconds``);
  - **the narrow-panel GSPMD probe** (``narrow_panels_supported``):
    PR-7's ``PanelTuner`` width ladder is enabled under a mesh only
    after a canary drain PROVES the partitioner compiles the
    narrow-panel compaction correctly on that mesh — see the function
    docstring for the fence semantics;
  - **the sharded-entry-point registry** (``SHARDED_KERNELS``): the
    machine-checked twin of ``ops.KERNEL_MIRRORS`` — every kernel with
    a mesh path must resolve to the SAME host mirror as its
    single-device twin (mirrors are mesh-agnostic by construction; the
    lint in tests/test_drain_parity.py enforces it).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

__all__ = [
    "SHARDED_KERNELS",
    "bucket_stats",
    "mesh_fingerprint",
    "mesh_shape_str",
    "narrow_panels_supported",
    "note_bucket",
    "note_panel_schedule",
    "note_place_seconds",
    "place_seconds",
    "reset_stats",
    "resolve_mesh",
]


# ---- sharded entry points (the KERNEL_MIRRORS twin) ----
# kernel module under ops/ -> dotted "module:attr" of the placement
# entry that shards it. Every key must also appear in
# ops.KERNEL_MIRRORS: a sharded launch answers to the SAME numpy mirror
# as its single-device twin (the guard's failover and the pipelined
# drain's divergence sampling never change with the mesh — mirrors are
# mesh-agnostic). Linted by tests/test_drain_parity.py.
SHARDED_KERNELS = {
    "assign_kernel": "kueue_tpu.parallel.sharded_solver:place_cycle_inputs",
    "drain_kernel": "kueue_tpu.parallel.sharded_solver:place_drain_inputs",
    "preempt_kernel": (
        "kueue_tpu.parallel.sharded_solver:place_preempt_drain_inputs"
    ),
    "fair_preempt_kernel": (
        "kueue_tpu.parallel.sharded_solver:place_fair_preempt_drain_inputs"
    ),
    "tas_kernel": "kueue_tpu.parallel.sharded_solver:place_tas_drain_inputs",
}


# ---- mesh resolution (server --mesh auto|N|off) ----
def resolve_mesh(spec, fr_parallel: bool = False):
    """Operator spec -> Mesh or None.

    ``None``/``"off"``/``""`` -> None. ``"auto"`` -> all local devices,
    or None (single-device) on a host with one: a 1-device mesh buys
    nothing and pays the partitioner. ``N`` (int or digit string) -> the
    first N devices; N < 2 is single-device, and an N the host cannot
    supply raises ValueError instead of quietly running on fewer."""
    if spec is None or spec in ("off", ""):
        return None
    from kueue_tpu._jax import jax
    from kueue_tpu.parallel.sharded_solver import make_mesh

    have = len(jax.devices())
    if spec == "auto":
        n = have
    else:
        n = int(spec)
        if n > have:
            raise ValueError(
                f"mesh of {n} devices requested; this host has {have}"
            )
    if n < 2:
        return None
    return make_mesh(n, fr_parallel=fr_parallel)


def mesh_fingerprint(mesh) -> tuple:
    """Hashable identity of a mesh: (axis layout, device ids). Used to
    memoize per-mesh verdicts (the narrow-panel probe) and to key the
    jit-bucket accounting."""
    shape = dict(mesh.shape)
    return (
        tuple((a, int(shape[a])) for a in mesh.axis_names),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def mesh_shape_str(mesh) -> str:
    """Human/metric label: "off", "wl=8", "wl=4,fr=2"."""
    if mesh is None:
        return "off"
    shape = dict(mesh.shape)
    return ",".join(f"{a}={int(shape[a])}" for a in mesh.axis_names)


# ---- size-bucketed jit-cache + placement accounting ----
_LOCK = threading.Lock()
# (kernel, shapes-key, mesh-fingerprint-or-None) -> times seen
_BUCKETS: Dict[tuple, int] = {}
_PLACE_SECONDS = [0.0]
# last panel schedule the contended drain ran under a mesh:
# {"widths": tuple, "fenced": bool} — SIGUSR2/debug surface for the
# narrow-panel fence
_LAST_PANEL: Dict[str, object] = {}


def note_bucket(kernel: str, shapes_key: tuple, mesh=None) -> bool:
    """Register one solve's jit-cache key; True = the bucket was seen
    before (the executable is reused — ``jax.jit`` keys on exactly
    these statics plus the input shardings)."""
    key = (kernel, shapes_key, mesh_fingerprint(mesh) if mesh is not None else None)
    with _LOCK:
        seen = _BUCKETS.get(key, 0)
        _BUCKETS[key] = seen + 1
    return seen > 0


def bucket_stats() -> dict:
    """{"buckets", "hits", "misses", "perKernel": {kernel: {...}}} —
    one miss per distinct key (the compile), the rest are hits."""
    with _LOCK:
        items = list(_BUCKETS.items())
    per: Dict[str, Dict[str, int]] = {}
    for (kernel, _k, _m), n in items:
        st = per.setdefault(kernel, {"buckets": 0, "hits": 0, "misses": 0})
        st["buckets"] += 1
        st["misses"] += 1
        st["hits"] += n - 1
    return {
        "buckets": sum(s["buckets"] for s in per.values()),
        "hits": sum(s["hits"] for s in per.values()),
        "misses": sum(s["misses"] for s in per.values()),
        "perKernel": per,
    }


def note_place_seconds(dt: float) -> None:
    with _LOCK:
        _PLACE_SECONDS[0] += float(dt)


def place_seconds() -> float:
    """Cumulative host seconds spent placing sharded drain inputs."""
    with _LOCK:
        return _PLACE_SECONDS[0]


def note_panel_schedule(widths: Tuple[int, ...], fenced: bool) -> None:
    with _LOCK:
        _LAST_PANEL["widths"] = tuple(int(w) for w in widths)
        _LAST_PANEL["fenced"] = bool(fenced)


def last_panel_schedule() -> dict:
    with _LOCK:
        return dict(_LAST_PANEL)


def reset_stats() -> None:
    """Test hook: clear bucket/placement accounting (NOT the probe
    verdicts — those are per-mesh facts, not run state)."""
    with _LOCK:
        _BUCKETS.clear()
        _PLACE_SECONDS[0] = 0.0
        _LAST_PANEL.clear()


# ---- the narrow-panel GSPMD probe ----
# (mesh fingerprint, width) -> bool (that panel width safe on this mesh)
_NARROW_VERDICTS: Dict[tuple, bool] = {}


def _canary_preempt_case():
    """A minimal contended cohort exercising the narrow-panel victim
    search end-to-end: one hoarder ClusterQueue saturated ABOVE nominal
    (borrowing; never preempts) and one reclaimer whose higher-priority
    backlog can only start by cross-CQ reclaim — so the probe drain
    runs the strategy ladder, the candidate compaction, and at least
    one eviction. Returns (snapshot, pending, flavors)."""
    from kueue_tpu.core.cache import Cache
    from kueue_tpu.core.snapshot import take_snapshot
    from kueue_tpu.core.workload_info import make_admission
    from kueue_tpu.models import (
        ClusterQueue,
        FlavorQuotas,
        Preemption,
        ResourceFlavor,
        Workload,
    )
    from kueue_tpu.models.cluster_queue import ResourceGroup
    from kueue_tpu.models.constants import (
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
        WorkloadConditionType,
    )
    from kueue_tpu.models.workload import PodSet

    cache = Cache()
    cache.add_or_update_flavor(ResourceFlavor(name="probe-fl"))
    specs = [
        ("probe-hoard", Preemption()),
        (
            "probe-reclaim",
            Preemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=ReclaimWithinCohortPolicy.ANY,
            ),
        ),
    ]
    for name, prem in specs:
        cache.add_or_update_cluster_queue(
            ClusterQueue(
                name=name,
                cohort="probe-cohort",
                namespace_selector={},
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (FlavorQuotas.build("probe-fl", {"cpu": "8"}),),
                    ),
                ),
                preemption=prem,
            )
        )
    # hoarder: 6 x 2 = 12 > nominal 8 (borrows 4 from the cohort)
    for v in range(6):
        wl = Workload(
            namespace="probe", name=f"victim-{v}",
            queue_name="lq-probe-hoard", priority=v % 3,
            creation_time=float(v),
            pod_sets=(PodSet.build("main", 1, {"cpu": "2"}),),
        )
        wl.admission = make_admission(
            "probe-hoard", {"main": {"cpu": "probe-fl"}}, wl
        )
        wl.set_condition(
            WorkloadConditionType.QUOTA_RESERVED, True,
            reason="QuotaReserved", now=float(v),
        )
        cache.add_or_update_workload(wl)
    pending = [
        (
            Workload(
                namespace="probe", name=f"head-{w}",
                queue_name="lq-probe-reclaim", priority=100,
                creation_time=100.0 + w,
                pod_sets=(PodSet.build("main", 1, {"cpu": "5"}),),
            ),
            "probe-reclaim",
        )
        for w in range(3)
    ]
    return take_snapshot(cache), pending, dict(cache.flavors)


def _preempt_sig(outcome) -> tuple:
    return (
        frozenset((wl.name, cyc) for wl, _, _, cyc in outcome.admitted),
        frozenset((wl.name, cyc) for wl, _, cyc in outcome.preempted),
        frozenset(wl.name for wl, _ in outcome.parked),
        outcome.cycles,
    )


def narrow_panels_supported(mesh, width: int = 8) -> bool:
    """Is THIS narrow panel width trustworthy on this mesh?

    The GSPMD partitioner miscompiles the narrow-panel candidate
    compaction at small static widths (a mixed s32/s64 index compare in
    the partitioned HLO — on the 8-device CPU mesh, width 8 is rejected
    by the hlo verifier while 16+ compiles), which would silently
    change preemption decisions — the one failure mode the
    ``overflowed`` escape hatch CANNOT catch (a wrong answer is not an
    overflow). So each ladder rung is enabled under a mesh only after a
    canary proves it: a tiny contended drain runs at that width on the
    mesh and must reproduce the single-device decisions bit-for-bit. A
    mismatch — or any compile / runtime error — marks the width
    unsupported, and ``mesh_safe_widths`` clamps the schedule to the
    next supported rung (ending at the pinned exact ``search_width``,
    the PR-7 fallback). Verdicts are memoized per (mesh fingerprint,
    width): one probe per process per pair.

    The per-shard narrow panels themselves need no extra collectives:
    ``perm``/``entry_slot`` are per-queue tensors already sharded along
    ``wl``, and the replicated ``overflowed`` escape hatch reduces over
    all shards exactly like the single-device flag."""
    key = (mesh_fingerprint(mesh), int(width))
    verdict = _NARROW_VERDICTS.get(key)
    if verdict is None:
        verdict = _probe_narrow_panels(mesh, int(width))
        _NARROW_VERDICTS[key] = verdict
    return verdict


def demote_panel_width(mesh, width: int) -> None:
    """Mark a panel width unsupported on this mesh AFTER a live compile
    failure (the miscompile is problem-shape-dependent: the canary can
    certify a width the verifier later rejects for a bigger Q/V shape).
    ``run_drain_preempt`` calls this from its narrow-tier containment;
    future schedules clamp past the width without re-trying it."""
    _NARROW_VERDICTS[(mesh_fingerprint(mesh), int(width))] = False


def mesh_safe_widths(mesh, widths: Tuple[int, ...]) -> Tuple[int, ...]:
    """Clamp a panel schedule's narrow rungs to mesh-supported widths.

    Each narrow rung walks UP (doubling) until a probed-safe width is
    found; rungs that reach the final (exact) width drop out. The final
    width is never probed or dropped — it is the trusted exact
    fallback, and an escalated run at it IS the single-width PR-7
    launch. Returns the original schedule when every rung is safe."""
    final = int(widths[-1])
    out = []
    for w in widths[:-1]:
        ww = int(w)
        while ww < final and not narrow_panels_supported(mesh, ww):
            ww = min(final, max(ww * 2, 8))
        if ww < final and ww not in out:
            out.append(ww)
    return tuple(out) + (final,)


def _probe_narrow_panels(mesh, width: int) -> bool:
    from kueue_tpu.core.drain import run_drain_preempt

    try:
        snap_ref, pending_ref, flavors = _canary_preempt_case()
        ref = run_drain_preempt(
            snap_ref, pending_ref, flavors, panel_widths=(width,),
        )
        snap_m, pending_m, flavors_m = _canary_preempt_case()
        got = run_drain_preempt(
            snap_m, pending_m, flavors_m, panel_widths=(width,),
            mesh=mesh, _trust_panel_widths=True,
        )
    except Exception:  # noqa: BLE001 — a partitioner crash IS a verdict
        return False
    if not ref.preempted:
        # a canary that exercised no eviction proves nothing: refuse to
        # certify the mesh on vacuous evidence
        return False
    return _preempt_sig(ref) == _preempt_sig(got)
