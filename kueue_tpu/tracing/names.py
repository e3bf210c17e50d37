"""Closed span-name registry.

The same low-cardinality contract ``EVENT_REASONS`` and
``InadmissibleReason`` enforce on the event/audit surfaces applies to
spans: ``kueue_trace_spans_total{name=...}`` is labeled by span name,
so the set must stay closed. ``Tracer`` rejects names outside this
registry at the call site, ``Phase`` where a phase is defined, and the
kueuelint ``span-name`` rule checks every literal ``add_cycle_span("...")``
/ ``Phase("...")`` in the source tree against it — the reason-enum lint
pattern applied to tracing.
"""

from __future__ import annotations

# workload lifecycle traces: one trace per workload, root opened at
# enqueue and closed at admission (or finish/delete). Children are
# point-in-time decision/transition spans; their durations live on the
# correlated cycle trace (the ``cycleTrace`` attr).
WORKLOAD_SPAN_NAMES = frozenset(
    {
        "workload.lifecycle",
        "workload.enqueue",
        "workload.nominate",
        "workload.flavor_assign",
        "workload.victim_search",
        "workload.quota_reserve",
        "workload.admit",
        "workload.preempt",
        "workload.evict",
        "workload.requeue",
        "workload.quarantine",
        # MultiKueue federation hops on the same lifecycle trace: the
        # manager's dispatch fan-out, the winner pick, and every
        # sync-back observation of the winner's reservation
        "federation.dispatch",
        "federation.winner",
        "federation.sync_back",
        "federation.retract",
        # global scheduler (federation/global_scheduler.py): one span
        # per APPLIED rebalance, joining the federation hop spans on
        # the workload's lifecycle trace (from/to/fence/forecast gain)
        "global.rescore",
    }
)

# cycle span trees: one trace per scheduling cycle / drain round, the
# phase children carrying real durations (the CycleTrace spans lowered
# into parent/child structure).
CYCLE_SPAN_NAMES = frozenset(
    {
        "cycle",
        "cycle.heads",
        "cycle.snapshot",
        "cycle.nominate",
        "cycle.admit",
        "cycle.classify",
        "cycle.solve",
        "cycle.apply",
        "cycle.prefetch",
        "cycle.commit",
        # sub-phases of a scheduler cycle (children of nominate/admit)
        "cycle.prevalidate",
        "cycle.assign",
        "cycle.victims",
        "cycle.park",
        "cycle.encode",
        "cycle.launch",
        "cycle.fetch",
        "cycle.decode",
        "cycle.discard",
        "cycle.megaloop",
        "cycle.mesh_place",
        "cycle.divergence_check",
        "cycle.guard_failover",
        "cycle.journal_fsync",
    }
)

SPAN_NAMES = WORKLOAD_SPAN_NAMES | CYCLE_SPAN_NAMES

# runtime phases: regions of the runtime loop and the server's batch
# apply. They are never stored as spans (one per loop iteration would
# flood the store); they live as cumulative totals on the runtime
# (``ClusterRuntime.phase_totals``) and as ``kueue.<name>`` profiler
# annotations.
RUNTIME_PHASE_NAMES = frozenset(
    {
        "loop.fingerprint",
        "loop.reconcile",
        "loop.drain_gate",
        "loop.report",
        "loop.audit",
        "ingest.decode",
        "ingest.apply",
    }
)

# every name a ``tracing.Phase`` may carry
PHASE_NAMES = CYCLE_SPAN_NAMES | RUNTIME_PHASE_NAMES

# phase name -> the one counter its regions' counts are added to (a
# ``CycleTrace.counts`` key, served on ``/debug/cycles``, or a
# ``PhaseTotals`` counter); a phase not listed counts nothing
PHASE_COUNTERS = {
    "cycle.snapshot": "forest_walks",
    "cycle.prevalidate": "prevalidated",
    "cycle.assign": "walked",
    "cycle.victims": "deferred",
    "cycle.encode": "lowered",
    "cycle.launch": "launches",
    "cycle.park": "parked",
    "loop.fingerprint": "loop.fingerprint_walks",
    "ingest.apply": "ingest.objects",
}

# CycleTrace.counts keys that no phase owns, each added where its event
# happens (core/scheduler.py for a cycle, controllers/cluster.py for a
# drain round): heads the lowering handed to the host, heads the kernel
# returned non-Fit, heads the victim search's candidate index answered
# empty; heads of a non-default-fungibility ClusterQueue offered to the
# per-cycle lowering, and those lowered onto the device; admissions
# whose flavor in some resource group lies past one where they fit only
# by borrowing; heads lowered with a resume cursor
CYCLE_COUNTS = frozenset(
    {
        "unrepresentable",
        "returned",
        "no_candidates",
        "fungible_heads",
        "fungible_lowered",
        "borrow_skipped",
        "walks_resumed",
    }
)

# CycleTrace phase key -> cycle span name (the lowering used by
# Tracer.record_cycle; a phase without a registry entry is a bug in
# the emitting site, same contract as classify_inadmissible_message)
CYCLE_PHASE_SPANS = {
    "heads": "cycle.heads",
    "snapshot": "cycle.snapshot",
    "nominate": "cycle.nominate",
    "admit": "cycle.admit",
    "classify": "cycle.classify",
    "solve": "cycle.solve",
    "apply": "cycle.apply",
    "prefetch": "cycle.prefetch",
    "commit": "cycle.commit",
}

# CycleTrace sub-phase key -> (span name, parent phase key): record_cycle
# lowers each as a child of its parent phase's span
CYCLE_SUBPHASE_SPANS = {
    "prevalidate": ("cycle.prevalidate", "nominate"),
    "assign": ("cycle.assign", "nominate"),
    "victims": ("cycle.victims", "nominate"),
    "encode": ("cycle.encode", "nominate"),
    "launch": ("cycle.launch", "nominate"),
    "fetch": ("cycle.fetch", "nominate"),
    "decode": ("cycle.decode", "nominate"),
    "park": ("cycle.park", "admit"),
}

# event reason -> workload lifecycle span (ClusterRuntime.event funnel;
# reasons not listed here do not produce spans)
EVENT_SPANS = {
    "QuotaReserved": "workload.quota_reserve",
    "Admitted": "workload.admit",
    "Evicted": "workload.evict",
    "Preempted": "workload.preempt",
    "Pending": "workload.requeue",
    "WorkloadQuarantined": "workload.quarantine",
}
