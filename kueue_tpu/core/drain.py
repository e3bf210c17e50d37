"""Bulk admission drain — host glue for ops/drain_kernel.py.

Lowers an entire pending backlog (every queued workload, not just the
cycle heads) into dense per-CQ queue tensors, runs the multi-cycle
drain on the device in ONE dispatch + ONE fetch, and maps the decisions
back to workloads. The per-cycle semantics match the sequential
Scheduler exactly for preemption-free, fully-representable backlogs
(asserted in tests/test_drain.py); workloads the dense form can't
express are reported in ``fallback`` for the normal cycle loop.

Use cases: the runtime's bulk path (``ClusterRuntime.bulk_drain``, the
50k-pending north-star drain of ``chip_smoke.py``), bulk import (cli
import), and capacity what-if planning.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kueue_tpu.models import ResourceFlavor, Workload
from kueue_tpu.core.snapshot import Snapshot
from kueue_tpu.core.solver import (
    MultiLowered,
    _bucket,
    lower_heads_multi,
    tree_arrays,
)


@dataclass
class DrainPlan:
    queues_np: dict  # field name -> numpy array (DrainQueues layout)
    # (q, pos) -> index into lowered.heads
    head_of: Dict[Tuple[int, int], int]
    lowered: MultiLowered
    cq_order: List[str]  # queue index -> cq name
    n_segments: int
    n_steps: int
    max_cycles: int
    fallback: List[int] = field(default_factory=list)


@dataclass
class DrainOutcome:
    # (workload, cq_name, resource->flavor map, cycle index)
    admitted: List[Tuple[Workload, str, Dict[str, str], int]]
    parked: List[Tuple[Workload, str]]
    fallback: List[Tuple[Workload, str]]
    cycles: int
    # max_cycles hit before quiescence: entries the kernel never
    # processed were routed to ``fallback`` (not parked), so the cycle
    # loop — not a silent park — decides them
    truncated: bool = False
    # the truncation-routed subset of ``fallback``: entries the kernel
    # simply never reached before max_cycles (NOT structurally
    # unrepresentable, NOT stuck-frozen) — re-running the drain over
    # exactly these from the post-apply state continues where this
    # chunk stopped. The pipelined drain loop (core/pipeline.py) feeds
    # them to the next round.
    undecided: List[Tuple[Workload, str]] = field(default_factory=list)
    # final leaf usage [N, FR] as the kernel left it — the speculative
    # post-apply snapshot the pipeline launches round t+1 against
    # (None on paths that don't report it)
    final_usage: Optional[np.ndarray] = None
    # (workload, cq_name, int[P, G] next flavor per group) of each
    # undecided head the kernel nominated: the runtime stores the walk
    # as the head's LastAssignment before the next round is planned
    resume: List[Tuple[Workload, str, np.ndarray]] = field(
        default_factory=list
    )
    # heads the plan lowered with a resume cursor (its round's
    # ``walks_resumed``)
    resumed: int = 0


def _resume_of(plan, cursor, stuck, g_start) -> List[Tuple[Workload, str, np.ndarray]]:
    """Undecided queue heads (at ``cursor``, queue not stuck) the kernel
    nominated, with their flavor walks ``g_start`` [Q, P, G]; a head it
    never nominated carries -1 there and keeps its LastAssignment."""
    out = []
    for qi in np.flatnonzero(np.any(g_start >= 0, axis=(1, 2))):
        i = plan.head_of.get((int(qi), int(cursor[qi])))
        if i is not None and not bool(stuck[qi]):
            out.append(
                (plan.lowered.heads[i], plan.lowered.cq_names[i],
                 np.array(g_start[qi]))
            )
    return out


def _admitted_flavors(lowered, i: int, adm_k_row) -> Dict[str, str]:
    """resource -> flavor map of an admitted head.

    Single-podset heads keep the flat {resource: flavor} shape; a
    multi-podset head returns {podset name: {resource: flavor}} (the
    per-PodSetAssignment flavors of the reference Admission)."""
    npods = int(lowered.n_podsets[i])
    wl = lowered.heads[i]
    if npods <= 1:
        return dict(lowered.candidate_flavors[i][0][int(adm_k_row[0])])
    return {
        wl.pod_sets[pp].name: dict(
            lowered.candidate_flavors[i][pp][int(adm_k_row[pp])]
        )
        for pp in range(npods)
    }


def plan_drain(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    timestamp_fn=None,
    max_podsets: int = 4,
    allow_tas: bool = False,
    policy=None,  # kueue_tpu/policy AdmissionPolicy: compiles the
    #               per-entry candidate score tensor (zeros = first-fit)
    now: float = 0.0,  # policy clock (deadline boosts)
) -> DrainPlan:
    """Lower the backlog and pack it into per-CQ queue tensors.

    ``pending`` must be in per-CQ heap order (priority desc, timestamp
    asc — use QueueManager pending snapshots); relative order across
    CQs is irrelevant.

    Every drain kernel starts each queue's walk at ``g_start0``: every
    queue's first head is lowered over its whole flavor walk and its
    LastAssignment goes to ``g_start0``, so a head a round cut off
    mid-walk can still restart at the first flavor in the next round.
    """
    from kueue_tpu.ops.assign_kernel import build_roots

    lowered = lower_heads_multi(
        snapshot, pending, flavors, max_candidates, max_cells, max_podsets,
        timestamp_fn, any_fungibility=True, allow_tas=allow_tas,
    )
    if policy is not None and not policy.is_default:
        from kueue_tpu.policy import annotate_multi

        annotate_multi(policy, lowered, now)
    fallback = set(lowered.fallback)
    if fallback:
        # a head the drain cannot represent still takes its turn in its
        # ClusterQueue and competes for its cohort's quota cycle by
        # cycle: the whole cohort tree goes to the cycle loop with it
        roots = build_roots(snapshot.flat.parent)
        held = {
            int(roots[snapshot.row(lowered.cq_names[i])])
            for i in fallback
            if lowered.cq_names[i] in snapshot.cq_models
        }
        if held:
            fallback.update(
                i
                for i, cq_name in enumerate(lowered.cq_names)
                if cq_name in snapshot.cq_models
                and int(roots[snapshot.row(cq_name)]) in held
            )

    by_cq: Dict[str, List[int]] = {}
    for i, cq_name in enumerate(lowered.cq_names):
        if i in fallback:
            continue
        by_cq.setdefault(cq_name, []).append(i)

    cq_order = sorted(by_cq)
    q = max(len(cq_order), 1)
    l = max((len(v) for v in by_cq.values()), default=1)
    k, c = max_candidates, max_cells
    # P = widest podset vector among representable heads (padded
    # podsets are inert in the kernel: no cells, mode FIT)
    pdim = max(
        [1]
        + [
            int(lowered.n_podsets[i])
            for i in range(len(lowered.heads))
            if i not in fallback
        ]
    )

    cq_rows = np.full(q, -1, dtype=np.int32)
    qlen = np.zeros(q, dtype=np.int32)
    n_podsets = np.ones((q, l), dtype=np.int32)
    cells = np.full((q, l, pdim, k, c), -1, dtype=np.int32)
    qty = np.zeros((q, l, pdim, k, c), dtype=np.int64)
    valid = np.zeros((q, l, pdim, k), dtype=bool)
    # per-group candidate cursor inputs (drain_kernel.DrainQueues):
    # G = widest resource-group vector among representable heads
    g = max(
        [1]
        + [
            lowered.n_groups[i]
            for i in range(len(lowered.heads))
            if i not in fallback
        ]
    )
    gidx = np.zeros((q, l, pdim, k, g), dtype=np.int32)
    glast = np.zeros((q, l, pdim, k, g), dtype=bool)
    cgrp = np.full(cells.shape, -1, dtype=np.int8)
    # policy candidate scores (zeros = the default first-fit policy —
    # the kernels' score-argmax then IS the first-fit walk)
    score = np.zeros((q, l, pdim, k), dtype=np.int64)
    ffb = np.ones(q, dtype=bool)
    ffp = np.zeros(q, dtype=bool)
    # each queue's first flavor walk: its head's resume cursor per
    # podset and group, -1 where the head carries none
    g_start0 = np.full((q, pdim, g), -1, dtype=np.int32)
    # convergent-retry budget per queue: the max joint cursor-odometer
    # size of its entries (clamped; see drain_kernel stuck machinery)
    retry_cap = np.full(q, 2 * max_candidates + 2, dtype=np.int32)
    priority = np.zeros((q, l), dtype=np.int64)
    timestamp = np.zeros((q, l), dtype=np.int64)
    no_reclaim = np.zeros(q, dtype=bool)
    head_of: Dict[Tuple[int, int], int] = {}

    cursor_rows_of: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    for qi, cq_name in enumerate(cq_order):
        idxs = by_cq[cq_name]
        cq_rows[qi] = snapshot.row(cq_name)
        qlen[qi] = len(idxs)
        no_reclaim[qi] = bool(lowered.no_reclaim[idxs[0]])
        ffb[qi] = bool(lowered.ffb[idxs[0]])
        ffp[qi] = bool(lowered.ffp[idxs[0]])
        retry_cap[qi] = min(
            4096, max(lowered.walk_states[i] for i in idxs) + 1
        )
        n = len(idxs)
        idx_arr = np.asarray(idxs, dtype=np.int64)
        n_podsets[qi, :n] = lowered.n_podsets[idx_arr]
        cells[qi, :n] = lowered.cells[idx_arr, :pdim]
        qty[qi, :n] = lowered.qty[idx_arr, :pdim]
        valid[qi, :n] = lowered.valid[idx_arr, :pdim]
        cgrp[qi, :n] = lowered.cgrp[idx_arr, :pdim]
        if lowered.score is not None:
            score[qi, :n] = lowered.score[idx_arr, :pdim]
        priority[qi, :n] = lowered.priority[idx_arr]
        timestamp[qi, :n] = lowered.timestamp[idx_arr]
        for p, starts in enumerate(lowered.head_starts.get(idxs[0], ())):
            g_start0[qi, p, : len(starts)] = starts
        for pos, i in enumerate(idxs):
            head_of[(qi, pos)] = i
            for pp in range(int(lowered.n_podsets[i])):
                groups = lowered.candidate_groups[i][pp]
                # group lists are shared per lowering template: memoize
                # the dense cursor rows per list identity
                rows = cursor_rows_of.get(id(groups))
                if rows is None:
                    gi_row = np.zeros((k, g), dtype=np.int32)
                    # pad group slots (heads touching fewer than G
                    # groups) must stay permanently eligible:
                    # glast=True makes the resumed start 0
                    gl_row = np.ones((k, g), dtype=bool)
                    for kk, gvec in enumerate(groups):
                        for gx, (fi, lastf) in enumerate(gvec):
                            gi_row[kk, gx] = fi
                            gl_row[kk, gx] = lastf
                    rows = (gi_row, gl_row)
                    cursor_rows_of[id(groups)] = rows
                gidx[qi, pos, pp] = rows[0]
                glast[qi, pos, pp] = rows[1]

    roots = build_roots(snapshot.flat.parent)
    seg_id = np.full(q, -1, dtype=np.int32)
    live = cq_rows >= 0
    if live.any():
        uniq, inv = np.unique(roots[cq_rows[live]], return_inverse=True)
        seg_id[live] = inv.astype(np.int32)
        n_segments = _bucket(len(uniq), minimum=8)
        n_steps = _bucket(int(np.bincount(inv).max()), minimum=8)
        # Sound cycle cap: every cycle, each root cohort with live heads
        # retires at least one entry OR advances some head's per-group
        # flavor cursor — its rank-0 valid head admits (no in-segment
        # predecessor has touched usage yet); NoFit heads park unless a
        # group's walk stored a pending cursor, in which case the
        # cursor strictly advances and can do so at most K times per
        # entry before the walk exhausts and parks. Conflict-lost heads
        # retrying per remaining candidate pair with a same-segment
        # admission that cycle. So cycles <= largest segment's entries
        # x (1 + K pending retries each).
        max_seg_events = int(
            np.bincount(inv, weights=qlen[live].astype(np.float64)).max()
        ) * (int(retry_cap.max()) + 1)
    else:
        n_segments = n_steps = 8
        max_seg_events = 0

    return DrainPlan(
        queues_np=dict(
            cq_rows=cq_rows,
            seg_id=seg_id,
            qlen=qlen,
            cells=cells,
            qty=qty,
            valid=valid,
            n_podsets=n_podsets,
            gidx=gidx,
            glast=glast,
            cgrp=cgrp,
            ffb=ffb,
            ffp=ffp,
            retry_cap=retry_cap,
            priority=priority,
            timestamp=timestamp,
            no_reclaim=no_reclaim,
            score=score,
            g_start0=g_start0,
        ),
        head_of=head_of,
        lowered=lowered,
        cq_order=cq_order,
        n_segments=n_segments,
        n_steps=n_steps,
        # the while_loop stops at quiescence; this is a backstop only —
        # bucketed because it is a static jit arg (compile reuse)
        max_cycles=_bucket(max_seg_events + 8, minimum=16),
        # the COMPLETE fallback set — outcome mapping must use this,
        # not lowered.fallback, or extra exclusions silently vanish
        fallback=sorted(fallback),
    )


@dataclass
class DrainEviction:
    """One eviction with evictor attribution (the kernel records the
    evicting queue exactly; the evicting ENTRY is the queue's next
    admission at/after the eviction cycle — exact except for the rare
    head that evicts, then loses every later fits() re-check)."""

    victim: Workload
    victim_cq: str
    cycle: int
    by_cq: Optional[str] = None
    by_workload: Optional[Workload] = None
    # Preempted condition reason (preemption.py IN_* constants)
    reason: str = "InClusterQueue"


@dataclass
class PreemptDrainOutcome(DrainOutcome):
    # (victim workload, cq_name, cycle index of the eviction)
    preempted: List[Tuple[Workload, str, int]] = field(default_factory=list)
    # same evictions, with evictor attribution (aligned order)
    evictions: List[DrainEviction] = field(default_factory=list)


def _fair_lendable(snapshot: Snapshot, paths_np: np.ndarray):
    """(depth_of, lendable, res_of_fr) for the fair-sharing drains.

    lendable depends on quota only: potentialAvailable of the PARENT,
    summed per resource (fair_sharing.go:90-104)."""
    from kueue_tpu.ops.quota_np import potential_available_all_np

    parent = snapshot.flat.parent
    depth_of = (np.sum(paths_np >= 0, axis=1) - 1).astype(np.int32)
    pot = potential_available_all_np(
        parent, snapshot.flat.level_masks(), snapshot.subtree,
        snapshot.guaranteed, snapshot.borrowing_limit,
    )
    n_res = len(snapshot.resource_names)
    lendable = np.zeros((len(parent), n_res), dtype=np.int64)
    parent_pot = pot[np.maximum(parent, 0)]
    np.add.at(lendable.T, snapshot.resource_index, parent_pot.T)
    lendable[parent < 0] = 0
    return depth_of, lendable, snapshot.resource_index.astype(np.int32)


@dataclass
class _VictimLowering:
    """Shared per-root-cohort candidate-pool lowering, consumed by the
    classic (run_drain_preempt) and fair (run_drain_fair_preempt)
    preemption drains."""

    victims_np: dict
    slot_meta: Dict[int, list]
    victim_of: Dict[Tuple[int, int], object]
    extra_fb_entries: List[Tuple[Workload, str]]
    seg_root: Dict[int, int]
    seg_queues: Dict[int, List[int]]
    seg_members: Dict[int, List[int]]
    local_ids: Dict[int, Dict[int, int]]  # s -> global row -> local id
    row_names: list
    tree: object
    paths_j: object
    v_cap: int
    s_dim: int
    cv: int
    m_dim: int


def _lower_victim_pools(
    snapshot: Snapshot,
    plan: DrainPlan,
    timestamp_fn,
    now: Optional[float],
    max_victims: int,
    max_victim_cells: int,
    max_cycles: Optional[int],
    # fn(s, members, seg_queues_s) -> bool: extra scope veto, given the
    # segment id, its member CQ rows and its queue-index list
    extra_segment_bad=None,
    policy=None,  # kueue_tpu/policy: PREMA victim-cost adjustments
) -> _VictimLowering:
    """Build the SegVictims arrays + metadata for a preemption drain
    (the shared middle of run_drain_preempt, unchanged semantics) and
    set plan.max_cycles. Mutates plan (drops ineligible queues)."""
    from kueue_tpu.models.constants import (
        BorrowWithinCohortPolicy,
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
        WorkloadConditionType,
    )
    from kueue_tpu.ops.assign_kernel import build_roots
    from kueue_tpu.ops.drain_kernel import NO_BWC_THRESHOLD as NO_THR

    q = max(len(plan.cq_order), 1)
    nl = plan.queues_np["cells"].shape[1]
    pdim, kdim, cdim = plan.queues_np["cells"].shape[2:]
    merged_cells = pdim * cdim  # the kernel's mcells width

    # ---- per-queue preemption policy flags ----
    same_enabled = np.zeros(q, dtype=bool)
    same_prio_ok = np.zeros(q, dtype=bool)
    reclaim_enabled = np.zeros(q, dtype=bool)
    only_lower = np.zeros(q, dtype=bool)
    bwc = np.zeros(q, dtype=bool)
    bwc_thr1 = np.full(q, NO_THR, dtype=np.int64)
    for qi, cq_name in enumerate(plan.cq_order):
        prem = snapshot.cq_models[cq_name].preemption
        same_enabled[qi] = prem.within_cluster_queue != PreemptionPolicy.NEVER
        same_prio_ok[qi] = (
            prem.within_cluster_queue
            == PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY
        )
        reclaim_enabled[qi] = snapshot.has_cohort(cq_name) and (
            prem.reclaim_within_cohort != ReclaimWithinCohortPolicy.NEVER
        )
        # the host rule is != Any (preemption.py _find_candidates), not
        # == LowerPriority — they differ on unknown policy values
        only_lower[qi] = (
            prem.reclaim_within_cohort != ReclaimWithinCohortPolicy.ANY
        )
        pol = prem.borrow_within_cohort
        bwc[qi] = pol.policy != BorrowWithinCohortPolicy.NEVER
        if pol.max_priority_threshold is not None:
            bwc_thr1[qi] = int(pol.max_priority_threshold) + 1
    can_search = same_enabled | reclaim_enabled

    # ---- segment membership ----
    cq_rows = plan.queues_np["cq_rows"]
    seg_id = plan.queues_np["seg_id"]
    qlen = plan.queues_np["qlen"]
    roots_all = build_roots(snapshot.flat.parent)
    n_cq = snapshot.flat.n_cq
    row_names = snapshot.flat.cq_names  # row i -> name
    queue_of_row = {int(cq_rows[qi]): qi for qi in range(len(plan.cq_order))}
    seg_root = {}
    seg_queues: Dict[int, List[int]] = {}
    for qi in range(len(plan.cq_order)):
        s = int(seg_id[qi])
        if s < 0:
            continue
        seg_root[s] = int(roots_all[int(cq_rows[qi])])
        seg_queues.setdefault(s, []).append(qi)
    seg_members: Dict[int, List[int]] = {
        s: [r for r in range(n_cq) if int(roots_all[r]) == root]
        for s, root in seg_root.items()
    }
    scoped = {
        s: any(reclaim_enabled[qi] for qi in seg_queues[s])
        for s in seg_root
    }
    # part-B (drain-admitted-entry) slots are needed whenever ANY queue
    # in the segment searches — not just under cohort reclaim: a parked
    # higher-priority head reactivated by an eviction can preempt a
    # drain-admitted lower-priority entry of its OWN ClusterQueue
    dynamic = {
        s: any(can_search[qi] for qi in seg_queues[s]) for s in seg_root
    }

    # ---- pool membership + segment scope checks ----
    tree, paths_j, _ = tree_arrays(snapshot)
    paths_np = np.asarray(paths_j)
    pool_of: Dict[int, list] = {}  # s -> [(ws, owner_row)]
    bad_segments: List[int] = []
    for s, members in seg_members.items():
        entries = []
        bad = False
        for r in members:
            name = row_names[r]
            qi = queue_of_row.get(r)
            include = scoped[s] or (qi is not None and same_enabled[qi])
            if not include:
                continue
            for ws in snapshot.workloads_in_cq(name):
                if int(np.count_nonzero(ws.usage_vec)) > max_victim_cells:
                    # an unrepresentable victim of an included CQ makes
                    # the whole segment's searches unsound
                    bad = True
                    break
                entries.append((ws, r))
            if bad:
                break
        n_b = sum(int(qlen[qi]) for qi in seg_queues[s]) if dynamic[s] else 0
        if extra_segment_bad is not None and not bad:
            bad = bool(extra_segment_bad(s, members, seg_queues[s]))
        if bad or len(entries) + n_b > max_victims:
            bad_segments.append(s)
            pool_of[s] = []
        else:
            pool_of[s] = entries

    # searching queues of bad segments fall back to the cycle loop
    drop_queues: List[int] = [
        qi
        for s in bad_segments
        for qi in seg_queues[s]
        if can_search[qi]
    ]
    for s in bad_segments:
        scoped[s] = False
        dynamic[s] = False
    dropped = set(drop_queues)

    # ---- dense pool arrays ----
    pool_totals = [
        len(pool_of.get(s, []))
        + (
            sum(int(qlen[qi]) for qi in seg_queues[s] if qi not in dropped)
            if dynamic[s]
            else 0
        )
        for s in seg_root
    ]
    v_cap = _bucket(max(pool_totals, default=1), minimum=8)
    s_dim = plan.n_segments
    cv = max(
        merged_cells,
        max(
            (
                int(np.count_nonzero(ws.usage_vec))
                for pool in pool_of.values()
                for ws, _ in pool
            ),
            default=1,
        ),
    )
    dmax = paths_np.shape[1]
    node_counts = [
        len(
            np.unique(
                paths_np[np.asarray(members, dtype=np.int64)][
                    paths_np[np.asarray(members, dtype=np.int64)] >= 0
                ]
            )
        )
        for members in seg_members.values()
    ]
    m_dim = _bucket(max(node_counts, default=1), minimum=4)

    scells = np.full((s_dim, v_cap, cv), -1, dtype=np.int32)
    sqty = np.zeros((s_dim, v_cap, cv), dtype=np.int64)
    sprio = np.zeros((s_dim, v_cap), dtype=np.int64)
    sts = np.zeros((s_dim, v_cap), dtype=np.int64)
    svalid0 = np.zeros((s_dim, v_cap), dtype=bool)
    sowner = np.full((s_dim, v_cap), -1, dtype=np.int32)
    sowner_local = np.zeros((s_dim, v_cap), dtype=np.int32)
    sslot_q = np.full((s_dim, v_cap), -1, dtype=np.int32)
    sslot_l = np.full((s_dim, v_cap), -1, dtype=np.int32)
    seg_nodes = np.full((s_dim, m_dim), -1, dtype=np.int32)
    lpaths = np.full((s_dim, m_dim, dmax), -1, dtype=np.int32)
    hlocal = np.zeros(q, dtype=np.int32)
    perm = np.tile(np.arange(v_cap, dtype=np.int32), (q, 1))
    ptier = perm.copy()  # each ord position its own tier: order fixed
    entry_slot = np.full((q, nl), -1, dtype=np.int32)
    victim_of: Dict[Tuple[int, int], object] = {}
    slot_meta: Dict[int, list] = {}  # s -> [(evicted0, owner, prio, rt, uid, adj)]
    # PREMA-style victim-cost adjustment (kueue_tpu/policy): inserted
    # into the candidate sort key between the (evicted, other-CQ)
    # tiers and priority; zero for every victim under the default
    # policy, so the ordering is byte-identical to the unadjusted sort
    def _cost_adjust(wl) -> int:
        if policy is None or policy.is_default:
            return 0
        return int(policy.victim_cost_adjust(wl))

    if now is None:
        rts = [
            ws.quota_reserved_time
            for pool in pool_of.values()
            for ws, _ in pool
        ]
        now = (max(rts) + 1.0) if rts else 0.0

    local_ids: Dict[int, Dict[int, int]] = {}
    for s, members in seg_members.items():
        nodes = np.unique(
            paths_np[np.asarray(members, dtype=np.int64)]
        )
        nodes = nodes[nodes >= 0]
        local_id = {int(g): i for i, g in enumerate(nodes)}
        local_ids[s] = local_id
        seg_nodes[s, : len(nodes)] = nodes
        for i, gnode in enumerate(nodes):
            gp = paths_np[int(gnode)]
            for d in range(dmax):
                if gp[d] >= 0:
                    lpaths[s, i, d] = local_id[int(gp[d])]
        for qi in seg_queues[s]:
            hlocal[qi] = local_id[int(cq_rows[qi])]
        meta = []
        slot = 0
        for ws, owner in pool_of.get(s, []):
            js = np.flatnonzero(ws.usage_vec)
            scells[s, slot, : len(js)] = js
            sqty[s, slot, : len(js)] = ws.usage_vec[js]
            sprio[s, slot] = ws.priority
            tsv = (
                timestamp_fn(ws.workload)
                if timestamp_fn
                else ws.workload.creation_time
            )
            sts[s, slot] = int(tsv * 1e9)
            svalid0[s, slot] = True
            sowner[s, slot] = owner
            sowner_local[s, slot] = local_id[int(owner)]
            victim_of[(s, slot)] = ws
            meta.append(
                (
                    ws.workload.condition_true(WorkloadConditionType.EVICTED),
                    int(owner),
                    int(ws.priority),
                    float(ws.quota_reserved_time),
                    ws.workload.uid,
                    _cost_adjust(ws.workload),
                )
            )
            slot += 1
        if dynamic[s]:
            for qi in seg_queues[s]:
                if qi in dropped:
                    continue
                for pos in range(int(qlen[qi])):
                    i = plan.head_of[(qi, pos)]
                    wl = plan.lowered.heads[i]
                    sprio[s, slot] = plan.queues_np["priority"][qi, pos]
                    sts[s, slot] = plan.queues_np["timestamp"][qi, pos]
                    sowner[s, slot] = cq_rows[qi]
                    sowner_local[s, slot] = local_id[int(cq_rows[qi])]
                    sslot_q[s, slot] = qi
                    sslot_l[s, slot] = pos
                    entry_slot[qi, pos] = slot
                    meta.append(
                        (
                            False,
                            int(cq_rows[qi]),
                            int(plan.queues_np["priority"][qi, pos]),
                            float(now),
                            wl.uid,
                            _cost_adjust(wl),
                        )
                    )
                    slot += 1
        slot_meta[s] = meta
        # candidate order per queue (preemption.go:591-618): evicted
        # first, other-CQ first, lowest priority, most recently
        # reserved, uid; pad slots last
        for qi in seg_queues[s]:
            own = int(cq_rows[qi])
            keyed = sorted(
                range(len(meta)),
                key=lambda j: (
                    0 if meta[j][0] else 1,
                    0 if meta[j][1] != own else 1,
                    meta[j][5],
                    meta[j][2],
                    -meta[j][3],
                    meta[j][4],
                ),
            )
            perm[qi, : len(keyed)] = np.asarray(keyed, dtype=np.int32)
            perm[qi, len(keyed) :] = np.arange(
                len(keyed), v_cap, dtype=np.int32
            )
            # the order's tiers above reservation time: within one, the
            # kernel may re-rank part B by when the drain admitted it
            tier = [
                (meta[j][0], meta[j][1] != own, meta[j][5], meta[j][2])
                for j in keyed
            ]
            ptier[qi, : len(keyed)] = np.cumsum(
                [0] + [a != b for a, b in zip(tier, tier[1:])]
            )

    # ---- drop ineligible queues to the fallback path ----
    extra_fb_entries: List[Tuple[Workload, str]] = []
    if drop_queues:
        for qi in drop_queues:
            plan.queues_np["qlen"][qi] = 0
            plan.queues_np["cq_rows"][qi] = -1
            plan.queues_np["seg_id"][qi] = -1
            for pos in range(plan.queues_np["cells"].shape[1]):
                i = plan.head_of.pop((qi, pos), None)
                if i is not None:
                    extra_fb_entries.append(
                        (plan.lowered.heads[i], plan.lowered.cq_names[i])
                    )

    # cycle cap: between evictions the preemption-free per-segment
    # progress bound applies (>=1 retire per cycle per live segment);
    # each eviction cycle retires nothing but consumes a pool slot and
    # can reactivate the segment's parked entries once
    qlen = plan.queues_np["qlen"]
    seg_id = plan.queues_np["seg_id"]
    live = seg_id >= 0
    if live.any():
        nseg = int(seg_id[live].max()) + 1
        seg_entries = np.bincount(
            seg_id[live], weights=qlen[live].astype(np.float64), minlength=nseg
        )
        seg_victims = np.zeros(nseg, dtype=np.float64)
        for s in seg_root:
            if s < nseg:
                seg_victims[s] = len(slot_meta.get(s, []))
        # each entry may additionally burn up to max_candidates cycles
        # retrying with advanced per-group pending cursors before it
        # retires (the PendingFlavors emulation), hence the (K+1) factor
        cap = (
            int(((seg_victims + 1) * seg_entries + seg_victims).max())
            * (int(plan.queues_np["retry_cap"].max()) + 1)
            + 8
        )
    else:
        cap = 16
    plan.max_cycles = _bucket(cap, minimum=16)
    if max_cycles is not None:
        plan.max_cycles = max_cycles

    victims_np = dict(
        scells=scells, sqty=sqty, sprio=sprio, sts=sts, svalid0=svalid0,
        sowner=sowner, sowner_local=sowner_local, sslot_q=sslot_q,
        sslot_l=sslot_l, seg_nodes=seg_nodes, lpaths=lpaths,
        hlocal=hlocal, perm=perm, ptier=ptier, entry_slot=entry_slot,
        same_enabled=same_enabled, same_prio_ok=same_prio_ok,
        reclaim_enabled=reclaim_enabled, only_lower=only_lower, bwc=bwc,
        bwc_thr1=bwc_thr1,
    )
    return _VictimLowering(
        victims_np=victims_np,
        slot_meta=slot_meta,
        victim_of=victim_of,
        extra_fb_entries=extra_fb_entries,
        seg_root=seg_root,
        seg_queues=seg_queues,
        seg_members=seg_members,
        local_ids=local_ids,
        row_names=row_names,
        tree=tree,
        paths_j=paths_j,
        v_cap=v_cap,
        s_dim=s_dim,
        cv=cv,
        m_dim=m_dim,
    )


def classify_drain_scope(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    tas_flavors,
    fair_sharing: bool,
):
    """Pick which drain covers a backlog — shared by the service bulk
    path (ClusterRuntime.bulk_drain) and the CLI's ``--drain`` what-if
    plan, so the plan printout routes exactly like production.

    Returns ``(kind, pending2)`` with kind one of ``"fair_preempt"``,
    ``"fair"``, ``"preempt"``, ``"tas"``, ``"plain"``. TAS heads ride
    the drain only through run_drain_tas, which has no eviction
    support: with fair sharing or any preempt-capable plain CQ in the
    backlog they are dropped from ``pending2`` (the cycle loop decides
    them) and the rest drains under the preempt/fair scopes.
    """
    from kueue_tpu.models.constants import (
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
    )

    tas_flavors = set(tas_flavors or ())

    def _on_tas_cq(cq_name: str) -> bool:
        cq = snapshot.cq_models.get(cq_name)
        return cq is not None and any(
            fq.name in tas_flavors
            for rg in cq.resource_groups
            for fq in rg.flavors
        )

    def _preempt_capable(cq_name: str) -> bool:
        cq = snapshot.cq_models.get(cq_name)
        if cq is None:
            return False
        prem = cq.preemption
        return prem.within_cluster_queue != PreemptionPolicy.NEVER or (
            snapshot.has_cohort(cq_name)
            and prem.reclaim_within_cohort != ReclaimWithinCohortPolicy.NEVER
        )

    cq_names = {c for _, c in pending}
    tas_cqs = (
        {c for c in cq_names if _on_tas_cq(c)} if tas_flavors else set()
    )
    any_preempt = any(_preempt_capable(c) for c in cq_names - tas_cqs)
    use_tas = bool(tas_cqs) and not fair_sharing and not any_preempt
    pending2 = list(pending)
    if tas_cqs and not use_tas:
        pending2 = [(w, c) for w, c in pending2 if c not in tas_cqs]
    if fair_sharing and any_preempt:
        return "fair_preempt", pending2
    if fair_sharing:
        return "fair", pending2
    if any_preempt:
        return "preempt", pending2
    if use_tas:
        return "tas", pending2
    return "plain", pending2


def run_drain_for_scope(
    kind: str,
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    tas_cache=None,
    fs_strategies=None,
    timestamp_fn=None,
    mesh=None,  # jax.sharding.Mesh: shard every drain kind's Q axis
    policy=None,  # kueue_tpu/policy AdmissionPolicy, every kind
    now: float = 0.0,
    ordered_stamps: bool = False,
):
    """Dispatch the drain a classify_drain_scope kind names — the ONE
    place the kind→drain mapping lives, so the service bulk path and
    the CLI what-if stay identical by construction. ``mesh`` flows to
    every kind: the whole drain family runs under a ``(wl[, fr])`` mesh
    with decisions bit-for-bit the single-device kernels'
    (tests/test_mesh_drain.py). ``ordered_stamps``: the caller's clock
    runs (see run_drain_preempt)."""
    if kind == "fair_preempt":
        return run_drain_fair_preempt(
            snapshot, pending, flavors, timestamp_fn=timestamp_fn,
            fs_strategies=fs_strategies, mesh=mesh, policy=policy,
            ordered_stamps=ordered_stamps,
        )
    if kind == "fair":
        return run_drain(
            snapshot, pending, flavors, timestamp_fn=timestamp_fn,
            fair_sharing=True, mesh=mesh, policy=policy, now=now,
        )
    if kind == "preempt":
        return run_drain_preempt(
            snapshot, pending, flavors, timestamp_fn=timestamp_fn, mesh=mesh,
            policy=policy, ordered_stamps=ordered_stamps,
        )
    if kind == "tas":
        return run_drain_tas(
            snapshot, pending, flavors, tas_cache, timestamp_fn=timestamp_fn,
            mesh=mesh, policy=policy, now=now,
        )
    return run_drain(
        snapshot, pending, flavors, timestamp_fn=timestamp_fn, mesh=mesh,
        policy=policy, now=now,
    )


def launch_drain_for_scope(
    kind: str,
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    timestamp_fn=None,
    max_cycles: Optional[int] = None,
    mesh=None,
    resident=None,
    policy=None,  # kueue_tpu/policy AdmissionPolicy
    now: float = 0.0,
) -> Optional[DrainLaunch]:
    """Async (launch/fetch) twin of ``run_drain_for_scope`` for the
    scopes the pipelined drain loop can double-buffer. Returns None for
    scopes without a launch/fetch split yet (fair / preempt / TAS keep
    the blocking path — the pipeline falls back to serial rounds for
    them)."""
    if kind != "plain":
        return None
    return launch_drain(
        snapshot, pending, flavors, timestamp_fn=timestamp_fn,
        max_cycles=max_cycles, mesh=mesh, resident=resident,
        policy=policy, now=now,
    )


class PanelTuner:
    """Online kernel-shape search for the victim-search panel width.

    The contended drain is THROUGHPUT-bound in the strategy-ladder scan
    (the round-5 capture: cost scales with ``search_width``; fusing the
    two attempts changed nothing), so the shape lever is the panel
    width itself. Candidate panels are already sorted by the
    preemption-cost key (evicted first, other-CQ first, lowest
    priority, most recently reserved — preemption.go:591-618, the
    ordering PREMA/arXiv:1909.04548 motivates), so the true victim set
    is a PREFIX of the panel in the common case and a narrow window
    finds it. Exactness is guaranteed by the escape hatch in
    ``run_drain_preempt``: a solve whose ``overflowed`` flag fired
    (some eligible list overflowed the window AND the search missed)
    is discarded and re-solved at the next wider width, ending at the
    exact ``search_width`` — decisions are bit-for-bit the fixed-width
    kernel's at every step.

    This tuner is the per-workload-mix coordinate descent of
    arXiv:2406.20037 reduced to the one live coordinate: per final
    (exact) width it walks the width ladder — an escalation widens the
    starting panel for the next call, ``shrink_after`` consecutive
    clean narrow solves try the next narrower rung. State only ever
    changes WHICH executable runs, never what it answers."""

    LADDER = (8, 16, 32, 64, 128, 256, 512)

    def __init__(self, shrink_after: int = 8):
        self.shrink_after = shrink_after
        self._narrow: Dict[int, int] = {}  # final width -> narrow width
        self._clean: Dict[int, int] = {}  # consecutive clean solves
        self.escalations = 0
        self.solves = 0

    def _default_narrow(self, final: int) -> int:
        for w in self.LADDER:
            if w * 4 >= final:
                return min(w, final)
        return final

    def widths_for(self, final: int) -> Tuple[int, ...]:
        """The width schedule for one drain: (narrow, ..., final)."""
        narrow = self._narrow.get(final)
        if narrow is None:
            narrow = self._default_narrow(final)
            self._narrow[final] = narrow
        if narrow >= final:
            return (final,)
        return (narrow, final)

    def observe(self, final: int, escalated: bool) -> None:
        self.solves += 1
        narrow = self._narrow.get(final, final)
        if escalated:
            self.escalations += 1
            self._clean[final] = 0
            # widen: next rung up (capped at final)
            self._narrow[final] = min(final, max(narrow * 2, 8))
        else:
            n = self._clean.get(final, 0) + 1
            self._clean[final] = n
            if n >= self.shrink_after and narrow > self.LADDER[0]:
                self._narrow[final] = narrow // 2
                self._clean[final] = 0


# process-wide default tuner: every drain in the process shares it so
# the shape converges to the live workload mix
_PANEL_TUNER = PanelTuner()

# operator override (server --panel-widths): a fixed schedule replaces
# the tuner's; None = tune online
_PANEL_WIDTHS_OVERRIDE: Optional[Tuple[int, ...]] = None


def set_default_panel_widths(widths: Optional[Sequence[int]]) -> None:
    """Pin the victim-search panel schedule process-wide (the server's
    ``--panel-widths`` knob); None restores the online PanelTuner."""
    global _PANEL_WIDTHS_OVERRIDE
    _PANEL_WIDTHS_OVERRIDE = tuple(widths) if widths is not None else None


def run_drain_preempt(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    max_victims: int = 512,
    max_victim_cells: int = 4,
    timestamp_fn=None,
    max_cycles: Optional[int] = None,
    now: Optional[float] = None,
    search_width: int = 32,
    mesh=None,  # jax.sharding.Mesh: shard the Q axis across devices
    panel_widths: Optional[Sequence[int]] = None,
    panel_tuner: Optional[PanelTuner] = None,
    policy=None,  # kueue_tpu/policy AdmissionPolicy: scored flavor
    #               choice + PREMA victim-cost adjustments
    ordered_stamps: bool = False,
    # internal (the narrow-panel GSPMD probe): run the given
    # panel_widths under the mesh WITHOUT consulting the probe verdict
    # — the probe itself is what establishes it
    _trust_panel_widths: bool = False,
) -> PreemptDrainOutcome:
    """Multi-cycle drain WITH classic preemption — within-ClusterQueue
    and cross-CQ cohort reclamation — in one device dispatch + one
    fetch (ops/drain_kernel.solve_drain_preempt).

    Candidates are pooled per root cohort (segment): every member CQ's
    admitted workloads (part A), plus one slot per pending entry that
    becomes a live reclaim candidate once the drain admits it (part B —
    the host cycle loop sees drain-admitted workloads in its snapshot
    the same way). ``now`` is the quota-reservation instant attributed
    to in-drain admissions for candidate ordering (default: after every
    part-A reservation). With ``ordered_stamps`` — the runtime's clock
    runs, so the host cycle loop stamps each admission as it applies it
    — in-drain admissions instead rank by (cycle, entry order), and the
    outcome lists them in that order for the caller to apply. ``max_victims`` caps a SEGMENT's pool;
    overflowing segments route their preempt-capable queues to
    ``fallback`` for the sequential cycle loop, as do victims with more
    than ``max_victim_cells`` distinct usage cells. ``search_width``
    bounds one head's per-cycle candidate scan; a head that fails an
    overflowing search is reported via ``fallback`` (no-decision), not
    parked. The caller applies the reported admissions and evictions in
    cycle order (a drain-admitted entry may later be evicted by a
    reclaiming CQ: it appears in BOTH lists) — this function only
    decides.

    ``panel_widths`` overrides the panel schedule; default is the
    process-wide ``PanelTuner``'s (narrow, search_width) schedule. The
    solve runs at the narrow cost-ordered panel and re-solves wider ONLY
    when the kernel reports an inconclusive truncated search, doubling
    past the schedule's last width up to the whole candidate axis, so
    decisions always equal an untruncated search's (asserted in
    tests/test_drain_parity.py).
    """
    from kueue_tpu._jax import jnp
    from kueue_tpu.ops.drain_kernel import (
        DrainQueues,
        SegVictims,
        solve_drain_preempt_packed_jit,
    )

    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        policy=policy, now=now or 0.0,
    )
    low = _lower_victim_pools(
        snapshot, plan, timestamp_fn, now, max_victims, max_victim_cells,
        max_cycles, policy=policy,
    )
    tree, paths_j = low.tree, low.paths_j
    victims_np = low.victims_np

    queues_np = plan.queues_np
    if mesh is not None:
        import time as _time

        from kueue_tpu.parallel import harness
        from kueue_tpu.parallel.sharded_solver import (
            pad_queue_arrays,
            pad_victim_arrays,
            place_preempt_drain_inputs,
        )

        t0p = _time.perf_counter()
        mult = mesh.shape["wl"]
        queues_np = pad_queue_arrays(queues_np, mult)
        victims_np = pad_victim_arrays(
            victims_np, queues_np["qlen"].shape[0]
        )
        tree_in, usage_in, queues, victims, paths_in = (
            place_preempt_drain_inputs(
                mesh,
                tree,
                snapshot.local_usage,
                DrainQueues(**queues_np),
                SegVictims(**victims_np),
                paths_j,
            )
        )
        harness.note_place_seconds(_time.perf_counter() - t0p)
    else:
        tree_in, paths_in = tree, paths_j
        usage_in = jnp.asarray(snapshot.local_usage)
        queues = DrainQueues(
            **{k: jnp.asarray(v) for k, v in queues_np.items()}
        )
        victims = SegVictims(
            **{k: jnp.asarray(v) for k, v in victims_np.items()}
        )
    # ---- the two-tier panel ladder (exactness escape hatch) ----
    # Solve at the narrow cost-ordered panel first; if ANY head's
    # search overflowed the window and missed (the kernel's
    # ``overflowed`` flag — the only way truncation can be inexact),
    # discard and re-solve at the next wider width, ending at the
    # exact ``search_width``. Decisions are therefore bit-for-bit the
    # fixed ``search_width`` kernel's: a clean narrow run is provably
    # identical (every search succeeded in-window or failed with its
    # whole eligible list in-window), and an escalated run IS the wide
    # run.
    tuner = panel_tuner if panel_tuner is not None else _PANEL_TUNER
    if panel_widths is None:
        panel_widths = _PANEL_WIDTHS_OVERRIDE
    if panel_widths is not None:
        widths = tuple(panel_widths)
    else:
        widths = tuner.widths_for(search_width)
    if mesh is not None and not _trust_panel_widths:
        # The GSPMD partitioner miscompiles the narrow-panel compaction
        # at small static widths (mixed s32/s64 index compare in the
        # partitioned HLO). Under a mesh each narrow rung therefore
        # runs only after a per-(mesh, width) canary PROVES the
        # partitioned solve reproduces single-device decisions
        # (parallel/harness.narrow_panels_supported, memoized);
        # unsupported rungs are clamped up the ladder, degenerating to
        # the pinned exact ``search_width`` where the miscompile is
        # real at every rung (the PR-7 fence). The exactness escape
        # hatch is unchanged either way: ``overflowed`` is replicated
        # across shards and escalation re-solves wider, so a clean
        # narrow run is provably the wide run's decisions.
        from kueue_tpu.parallel.harness import (
            mesh_safe_widths,
            note_panel_schedule,
        )

        safe = mesh_safe_widths(mesh, widths)
        note_panel_schedule(safe, fenced=safe != widths)
        widths = safe
    # Past the exact width one last rung searches the whole candidate
    # axis, where no list can overflow: a search that missed in a
    # truncated window otherwise freezes its queue while the rest of
    # the cohort drains on, which changes the decisions (seen at 1,000
    # contended CQs, where a cohort's pool outgrows 32 slots).
    v_slots = int(victims_np["perm"].shape[1])
    if widths[-1] < v_slots:
        widths = tuple(widths) + (v_slots,)
    escalated = False
    for i, width in enumerate(widths):
        if mesh is not None:
            from kueue_tpu.parallel import harness

            harness.note_bucket(
                "preempt_kernel",
                (
                    queues_np["cells"].shape, plan.n_segments, plan.n_steps,
                    plan.max_cycles, int(width),
                ),
                mesh,
            )
        try:
            flat = np.asarray(
                solve_drain_preempt_packed_jit(
                    tree_in,
                    usage_in,
                    queues,
                    victims,
                    paths_in,
                    n_segments=plan.n_segments,
                    n_steps=plan.n_steps,
                    max_cycles=plan.max_cycles,
                    search_width=int(width),
                    ordered_stamps=ordered_stamps,
                )
            )  # one fetch per tier; the common case stops at the first
        except Exception as exc:
            from kueue_tpu.testing import faults

            if (
                mesh is None
                or i == len(widths) - 1
                or isinstance(exc, faults.InjectedCrash)
            ):
                raise
            # The GSPMD miscompile is shape-dependent: the canary probe
            # certifies a width per MESH, but a particular problem's
            # partitioned HLO can still be rejected by the verifier at
            # a narrow width (loud compile failure, never a silent
            # wrong answer). Demote the width for this mesh — future
            # schedules clamp past it — and escalate to the next rung;
            # only the final exact width is allowed to raise.
            from kueue_tpu.parallel.harness import demote_panel_width

            demote_panel_width(mesh, int(width))
            escalated = True
            continue
        overflowed = bool(flat[-2])
        if not overflowed or i == len(widths) - 1:
            break
        escalated = True
    if panel_widths is None:
        tuner.observe(search_width, escalated)
    return _preempt_outcome(plan, low, flat, queues_np, fair=False)


def _preempt_outcome(
    plan: DrainPlan,
    low: _VictimLowering,
    flat: np.ndarray,
    queues_np: dict,
    fair: bool,
) -> PreemptDrainOutcome:
    """Unpack a PreemptDrainResult flat vector and map decisions back
    to workloads (shared by the classic and fair preemption drains;
    ``fair`` switches the Preempted-condition reason rules)."""
    lowered = plan.lowered
    s_dim, v_cap = low.s_dim, low.v_cap
    slot_meta, victim_of = low.slot_meta, low.victim_of
    seg_root, row_names = low.seg_root, low.row_names
    extra_fb_entries = low.extra_fb_entries
    victims_np = low.victims_np
    sowner = victims_np["sowner"]
    sprio = victims_np["sprio"]
    sslot_q = victims_np["sslot_q"]
    sslot_l = victims_np["sslot_l"]
    bwc = victims_np["bwc"]
    bwc_thr1 = victims_np["bwc_thr1"]
    cq_rows = plan.queues_np["cq_rows"]

    nq, nl2, npd = queues_np["cells"].shape[:3]  # incl. mesh padding
    ql, sv, qlp = nq * nl2, s_dim * v_cap, nq * nl2 * npd
    off = 0
    status = flat[off : off + ql].reshape((nq, nl2)); off += ql
    adm_k = flat[off : off + qlp].reshape((nq, nl2, npd)); off += qlp
    adm_cycle = flat[off : off + ql].reshape((nq, nl2)); off += ql
    adm_order = flat[off : off + ql].reshape((nq, nl2)); off += ql
    evicted = flat[off : off + sv].reshape((s_dim, v_cap)).astype(bool); off += sv
    evict_cycle = flat[off : off + sv].reshape((s_dim, v_cap)); off += sv
    evict_by = flat[off : off + sv].reshape((s_dim, v_cap)); off += sv
    stuck_q = flat[off : off + nq].astype(bool); off += nq
    cycles = int(flat[-1])
    # truncated = the CYCLE CAP cut undecided work; queues frozen by
    # the stuck machinery are a terminal no-decision, not truncation —
    # rerunning with a larger cap cannot resolve them
    truncated = bool(
        np.any(
            (status == 0)
            & (np.arange(nl2)[None, :] < queues_np["qlen"][:, None])
            & ~stuck_q[:, None]
        )
    )

    lowered = plan.lowered
    admitted: List[Tuple[Workload, str, Dict[str, str], int]] = []
    parked: List[Tuple[Workload, str]] = []
    extra_fallback: List[Tuple[Workload, str]] = list(extra_fb_entries)
    order_of: Dict[str, int] = {}
    for (qi, pos), i in plan.head_of.items():
        wl = lowered.heads[i]
        cq_name = lowered.cq_names[i]
        st = int(status[qi, pos])
        kk = int(adm_k[qi, pos, 0])
        if st == 2 and kk >= 0:
            admitted.append(
                (wl, cq_name, _admitted_flavors(lowered, i, adm_k[qi, pos]),
                 int(adm_cycle[qi, pos]))
            )
            order_of[wl.key] = int(adm_order[qi, pos])
        elif st == 0:
            # still pending at max_cycles: not a decision
            extra_fallback.append((wl, cq_name))
        else:
            parked.append((wl, cq_name))
    # cycle by cycle, each in its entry order: the order the host cycle
    # loop admits (and stamps) them
    admitted.sort(key=lambda t: (t[3], order_of[t[0].key]))
    from kueue_tpu.core.preemption import (
        IN_CLUSTER_QUEUE,
        IN_COHORT_FAIR_SHARING,
        IN_COHORT_RECLAIM_WHILE_BORROWING,
        IN_COHORT_RECLAMATION,
    )
    from kueue_tpu.ops.drain_kernel import NO_BWC_THRESHOLD

    def _evictor_entry(qi: int, cyc: int):
        """(workload, priority) of queue qi's evicting entry at cycle
        cyc: its next admission at/after cyc (a preempting head charges
        usage at the eviction cycle and admits at a later one); falls
        back to the queue's first never-admitted entry when the head
        lost every later re-check and parked."""
        best = None
        first_unadmitted = None
        for pos in range(int(plan.queues_np["qlen"][qi])):
            i = plan.head_of.get((qi, pos))
            if i is None:
                continue
            if int(status[qi, pos]) == 2:
                ac = int(adm_cycle[qi, pos])
                if ac >= cyc and (best is None or ac < best[0]):
                    best = (ac, i, pos)
            elif first_unadmitted is None:
                first_unadmitted = (i, pos)
        if best is not None:
            i, pos = best[1], best[2]
        elif first_unadmitted is not None:
            i, pos = first_unadmitted
        else:
            return None, 0
        return (
            lowered.heads[i],
            int(plan.queues_np["priority"][qi, pos]),
        )

    preempted: List[Tuple[Workload, str, int]] = []
    evictions: List[DrainEviction] = []
    for s in seg_root:
        for slot in range(len(slot_meta.get(s, []))):
            if not evicted[s, slot]:
                continue
            cyc = int(evict_cycle[s, slot])
            ws = victim_of.get((s, slot))
            if ws is not None:
                victim_wl = ws.workload
                victim_cq = row_names[int(sowner[s, slot])]
            else:
                qi, pos = int(sslot_q[s, slot]), int(sslot_l[s, slot])
                i = plan.head_of.get((qi, pos))
                if i is None:
                    continue
                victim_wl = lowered.heads[i]
                victim_cq = lowered.cq_names[i]
            preempted.append((victim_wl, victim_cq, cyc))
            qi_by = int(evict_by[s, slot])
            by_cq = by_wl = None
            reason = IN_CLUSTER_QUEUE
            if 0 <= qi_by < len(plan.cq_order):
                by_cq = plan.cq_order[qi_by]
                by_wl, by_prio = _evictor_entry(qi_by, cyc)
                if int(cq_rows[qi_by]) != int(sowner[s, slot]):
                    if fair:
                        # fair tournament victims from another CQ
                        # (preemption.py _fair_preemptions)
                        reason = IN_COHORT_FAIR_SHARING
                    else:
                        # the ladder's threshold rule
                        # (preemption.go:353-357): below min(evictor
                        # priority, maxPriorityThreshold+1) the reclaim
                        # rode borrowWithinCohort
                        thr = min(
                            by_prio, int(bwc_thr1[qi_by]), NO_BWC_THRESHOLD
                        )
                        if bwc[qi_by] and int(sprio[s, slot]) < thr:
                            reason = IN_COHORT_RECLAIM_WHILE_BORROWING
                        else:
                            reason = IN_COHORT_RECLAMATION
            evictions.append(
                DrainEviction(
                    victim=victim_wl, victim_cq=victim_cq, cycle=cyc,
                    by_cq=by_cq, by_workload=by_wl, reason=reason,
                )
            )
    order = sorted(range(len(preempted)), key=lambda ix: preempted[ix][2])
    preempted = [preempted[ix] for ix in order]
    evictions = [evictions[ix] for ix in order]
    fb = [
        (lowered.heads[i], lowered.cq_names[i]) for i in plan.fallback
    ] + extra_fallback
    return PreemptDrainOutcome(
        admitted=admitted,
        parked=parked,
        fallback=fb,
        cycles=cycles,
        truncated=truncated,
        resumed=lowered.resumed,
        preempted=preempted,
        evictions=evictions,
    )


def run_drain_fair_preempt(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    max_victims: int = 512,
    max_victim_cells: int = 4,
    max_fair_cells: int = 64,
    timestamp_fn=None,
    max_cycles: Optional[int] = None,
    now: Optional[float] = None,
    policy=None,  # kueue_tpu/policy AdmissionPolicy
    fs_strategies: Optional[Sequence[str]] = None,
    mesh=None,  # jax.sharding.Mesh: shard the Q axis across devices
    ordered_stamps: bool = False,  # as run_drain_preempt
) -> PreemptDrainOutcome:
    """Multi-cycle drain with FAIR-SHARING admission ordering AND
    fair-sharing preemption — the production fair-cohort configuration
    — in one device dispatch + one fetch
    (ops/drain_kernel.solve_drain_fair_preempt).

    With ``mesh`` the per-queue tensors (and SegVictims' per-queue
    config) shard along ``wl``; the candidate pools, fair panels and
    node-space extras stay replicated — every panel tensor is SEGMENT
    space, the tournament reduces over whole root cohorts on each
    shard, and decisions are bit-for-bit the single-device kernel's
    (tests/test_mesh_drain.py).

    The candidate pools are the classic preemption drain's (fair
    sharing shares _find_candidates and the candidate ordering —
    preemption.go:480-524, :591-618); on top of them each segment gets
    LOCAL fair panels carrying its ACTIVE cell universe (every
    flavor-resource with quota or usage anywhere in the root cohort
    plus every queued entry's candidate cells — DRS aggregates over all
    of them, fair_sharing.go:49-104). A segment whose universe exceeds
    ``max_fair_cells`` routes its preempt-capable queues to
    ``fallback``, like the host batcher's MAX_FAIR_CELLS cap
    (core/preempt_batch.py). ``fs_strategies`` defaults to the
    Preemptor's [LessThanOrEqualToFinalShare, LessThanInitialShare].
    Victim attribution reasons are InClusterQueue / InCohortFairSharing
    (preemption.py _fair_preemptions)."""
    from kueue_tpu._jax import jnp
    from kueue_tpu.features import enabled as _feature_enabled
    from kueue_tpu.core.preemption import (
        LESS_THAN_OR_EQUAL_TO_FINAL_SHARE,
        LESS_THAN_INITIAL_SHARE,
    )
    from kueue_tpu.ops.drain_kernel import (
        DrainQueues,
        FairSegPanels,
        SegVictims,
        solve_drain_fair_preempt_packed_jit,
    )

    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        policy=policy, now=now or 0.0,
    )
    parent_arr = snapshot.flat.parent
    n_cq = snapshot.flat.n_cq
    n_res = len(snapshot.resource_names)
    res_of_fr = snapshot.resource_index.astype(np.int32)
    universe_of: Dict[int, np.ndarray] = {}
    qlen_np = plan.queues_np["qlen"]

    def seg_universe_bad(s: int, members, seg_queues_s) -> bool:
        """Compute the segment's active cell universe; veto the segment
        (dropping its searching queues to fallback) when it exceeds the
        panel cap. ``seg_queues_s`` is the lowering's own queue list
        for this segment."""
        nodes = set()
        for r in members:
            cur = int(r)
            while cur >= 0:
                nodes.add(cur)
                cur = int(parent_arr[cur])
        rows = np.asarray(sorted(nodes), dtype=np.int64)
        active = (snapshot.nominal[rows] > 0).any(axis=0) | (
            snapshot.local_usage[rows] > 0
        ).any(axis=0)
        for qi in seg_queues_s:
            cells_q = plan.queues_np["cells"][qi, : int(qlen_np[qi])]
            cs = cells_q[cells_q >= 0]
            if cs.size:
                active[np.unique(cs)] = True
        universe_of[s] = np.flatnonzero(active).astype(np.int32)
        return len(universe_of[s]) > max_fair_cells

    low = _lower_victim_pools(
        snapshot, plan, timestamp_fn, now, max_victims, max_victim_cells,
        max_cycles, extra_segment_bad=seg_universe_bad, policy=policy,
    )
    tree, paths_j = low.tree, low.paths_j
    victims_np = low.victims_np
    s_dim, v_cap, m_dim = low.s_dim, low.v_cap, low.m_dim

    # ---- fair panels ----
    good = {
        s: u for s, u in universe_of.items() if len(u) <= max_fair_cells
    }
    cu = _bucket(max((len(u) for u in good.values()), default=1), minimum=2)
    seg_cells = np.full((s_dim, cu), -1, dtype=np.int32)
    parent_local = np.full((s_dim, m_dim), -1, dtype=np.int32)
    depth_local = np.zeros((s_dim, m_dim), dtype=np.int32)
    is_cq_local = np.zeros((s_dim, m_dim), dtype=bool)
    node_valid = np.zeros((s_dim, m_dim), dtype=bool)
    weight_local = np.full((s_dim, m_dim), 1000, dtype=np.int64)
    res_of_cell = np.full((s_dim, cu), n_res, dtype=np.int32)
    svqty_cu = np.zeros((s_dim, v_cap, cu), dtype=np.int64)

    paths_np = np.asarray(paths_j)
    depth_of, lendable, _ = _fair_lendable(snapshot, paths_np)
    victims_by_seg: Dict[int, List[Tuple[int, object]]] = {}
    for (ss, slot), ws in low.victim_of.items():
        victims_by_seg.setdefault(ss, []).append((slot, ws))
    for s, local_id in low.local_ids.items():
        u = good.get(s)
        if u is None:
            continue  # vetoed segment: panels stay inert
        seg_cells[s, : len(u)] = u
        res_of_cell[s, : len(u)] = res_of_fr[u]
        root_depth = min(int(depth_of[g]) for g in local_id)
        for gnode, li in local_id.items():
            node_valid[s, li] = True
            is_cq_local[s, li] = gnode < n_cq
            parent_local[s, li] = local_id.get(int(parent_arr[gnode]), -1)
            weight_local[s, li] = int(snapshot.weight_milli[gnode])
            depth_local[s, li] = int(depth_of[gnode]) - root_depth
        cell_pos = {int(j): ci for ci, j in enumerate(u)}
        for slot, ws in victims_by_seg.get(s, ()):
            for j in np.flatnonzero(ws.usage_vec):
                ci = cell_pos.get(int(j))
                if ci is None:  # usage cells are in the universe by
                    raise AssertionError(  # construction
                        f"victim cell {j} outside segment {s} universe"
                    )
                svqty_cu[s, slot, ci] = int(ws.usage_vec[j])

    strategies = list(
        fs_strategies
        or [LESS_THAN_OR_EQUAL_TO_FINAL_SHARE, LESS_THAN_INITIAL_SHARE]
    )
    strategy1 = (
        0 if strategies[0] == LESS_THAN_OR_EQUAL_TO_FINAL_SHARE else 1
    )

    queues_np = plan.queues_np
    fairp_np = dict(
        seg_cells=seg_cells, parent_local=parent_local,
        depth_local=depth_local, is_cq_local=is_cq_local,
        node_valid=node_valid, weight_local=weight_local,
        res_of_cell=res_of_cell, svqty_cu=svqty_cu,
    )
    if mesh is not None:
        import time as _time

        from kueue_tpu.parallel import harness
        from kueue_tpu.parallel.sharded_solver import (
            pad_queue_arrays,
            pad_victim_arrays,
            place_fair_drain_extras,
            place_fair_preempt_drain_inputs,
        )

        t0p = _time.perf_counter()
        queues_np = pad_queue_arrays(queues_np, mesh.shape["wl"])
        victims_np = pad_victim_arrays(victims_np, queues_np["qlen"].shape[0])
        tree_in, usage_in, queues, victims, fairp, paths_in = (
            place_fair_preempt_drain_inputs(
                mesh,
                tree,
                snapshot.local_usage,
                DrainQueues(**queues_np),
                SegVictims(**victims_np),
                FairSegPanels(**fairp_np),
                paths_j,
            )
        )
        depth_in, weight_in, lendable_in, res_in = place_fair_drain_extras(
            mesh, depth_of, snapshot.weight_milli, lendable, res_of_fr
        )
        harness.note_place_seconds(_time.perf_counter() - t0p)
        harness.note_bucket(
            "fair_preempt_kernel",
            (
                queues_np["cells"].shape, plan.n_segments, plan.n_steps,
                plan.max_cycles,
            ),
            mesh,
        )
    else:
        tree_in, paths_in = tree, paths_j
        usage_in = jnp.asarray(snapshot.local_usage)
        queues = DrainQueues(
            **{k: jnp.asarray(v) for k, v in queues_np.items()}
        )
        victims = SegVictims(
            **{k: jnp.asarray(v) for k, v in victims_np.items()}
        )
        fairp = FairSegPanels(
            **{k: jnp.asarray(v) for k, v in fairp_np.items()}
        )
        depth_in = jnp.asarray(depth_of)
        weight_in = jnp.asarray(snapshot.weight_milli)
        lendable_in = jnp.asarray(lendable)
        res_in = jnp.asarray(res_of_fr)
    flat = np.asarray(
        solve_drain_fair_preempt_packed_jit(
            tree_in,
            usage_in,
            queues,
            victims,
            fairp,
            paths_in,
            depth_in,
            weight_in,
            lendable_in,
            res_in,
            n_segments=plan.n_segments,
            n_steps=plan.n_steps,
            max_cycles=plan.max_cycles,
            n_res=n_res,
            prio_tie=bool(_feature_enabled("PrioritySortingWithinCohort")),
            strategy1=strategy1,
            has_second=len(strategies) > 1,
            ordered_stamps=ordered_stamps,
        )
    )  # the single fetch
    return _preempt_outcome(plan, low, flat, queues_np, fair=True)


# caps keeping the TAS placement kernel's i32 prefix sums exact:
# MAX_TAS_COUNT * MAX_TAS_DOMAINS < 2^31 (drain_kernel.split). Gangs
# above a million pods or merged forests above 2048 leaves route to the
# host cycle loop.
MAX_TAS_COUNT = 1 << 20
MAX_TAS_DOMAINS = 1 << 11


def _merge_tas_forests(snaps, union_res, d_global):
    """Concatenate per-flavor topologies into ONE disjoint domain
    forest, aligned at the LEAF level.

    A flavor with fewer levels gets structural dummy TOP levels (one
    domain per missing level, chained) so seg_ids/parent maps stay
    rectangular; the dummies are semantically unreachable — the kernel
    clamps the preferred-mode relax-up at each flavor's real top
    (TASHeads.t_top). Returns (topo_free, tas_usage, seg_ids,
    n_domains, parent_map, leaf_flavor, leaf_off) on the union resource
    axis, or None when ``snaps`` is empty."""
    if not snaps:
        return None
    from kueue_tpu.ops.tas_kernel import _level_prefix_index

    n_res = max(len(union_res), 1)
    u_index = {r: j for j, r in enumerate(union_res)}
    n_f = len(snaps)
    idxs_of = []
    counts = []  # [F][D] domains per flavor per global level
    for s in snaps:
        df = len(s.level_keys)
        idxs = [_level_prefix_index(s, d) for d in range(df)]
        idxs_of.append(idxs)
        counts.append(
            [1] * (d_global - df) + [len(ix) for ix in idxs]
        )
    n_domains = tuple(
        sum(counts[f][d] for f in range(n_f)) for d in range(d_global)
    )
    dom_off = [[0] * n_f for _ in range(d_global)]
    for d in range(d_global):
        acc = 0
        for f in range(n_f):
            dom_off[d][f] = acc
            acc += counts[f][d]
    lf_total = sum(len(s._leaf_order) for s in snaps)
    nd_max = max(n_domains)
    seg_ids = np.zeros((d_global, lf_total), dtype=np.int32)
    parent_map = np.zeros((d_global, nd_max), dtype=np.int32)
    topo_free = np.zeros((lf_total, n_res), dtype=np.int64)
    tas_usage = np.zeros((lf_total, n_res), dtype=np.int64)
    leaf_flavor = np.zeros(lf_total, dtype=np.int32)
    leaf_off: Dict[int, int] = {}
    off_l = 0
    for f, s in enumerate(snaps):
        df = len(s.level_keys)
        lvl_off = d_global - df
        nl_f = len(s._leaf_order)
        idxs = idxs_of[f]
        leaf_off[f] = off_l
        leaf_flavor[off_l : off_l + nl_f] = f
        cols = [u_index[r] for r in s._resources]
        topo_free[off_l : off_l + nl_f, cols] = s._free
        tas_usage[off_l : off_l + nl_f, cols] = s._tas_usage
        for d in range(d_global):
            dl = d - lvl_off
            if dl < 0:
                seg_ids[d, off_l : off_l + nl_f] = dom_off[d][f]
            else:
                for i, leaf in enumerate(s._leaf_order):
                    seg_ids[d, off_l + i] = (
                        dom_off[d][f] + idxs[dl][leaf.level_values[: dl + 1]]
                    )
        for d in range(1, d_global):
            dl = d - lvl_off
            if dl < 0:
                parent_map[d, dom_off[d][f]] = dom_off[d - 1][f]
            elif dl == 0:
                for idx in idxs[0].values():
                    parent_map[d, dom_off[d][f] + idx] = dom_off[d - 1][f]
            else:
                for p, idx in idxs[dl].items():
                    parent_map[d, dom_off[d][f] + idx] = (
                        dom_off[d - 1][f] + idxs[dl - 1][p[:-1]]
                    )
        off_l += nl_f
    return (
        topo_free, tas_usage, seg_ids, n_domains, parent_map,
        leaf_flavor, leaf_off,
    )


@dataclass
class TASDrainOutcome(DrainOutcome):
    # TopologyAssignment per admitted entry, aligned with ``admitted``
    # (None for non-TAS workloads)
    assignments: List[object] = field(default_factory=list)


def run_drain_tas(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    tas_cache,
    max_candidates: int = 8,
    max_cells: int = 4,
    timestamp_fn=None,
    max_cycles: Optional[int] = None,
    mesh=None,  # jax.sharding.Mesh: shard the Q axis across devices
    policy=None,  # kueue_tpu/policy AdmissionPolicy
    now: float = 0.0,
) -> TASDrainOutcome:
    """Multi-cycle drain with Topology-Aware Scheduling heads decided
    on the device (ops/drain_kernel.solve_drain_tas) — one dispatch +
    one fetch, then a cheap host replay (one placement per ADMITTED
    workload, grouped per cycle against cycle-start state) that
    reconstructs the TopologyAssignments and asserts the kernel's final
    TAS leaf usage is reproduced exactly, flavor by flavor.

    With ``mesh`` the per-queue tensors (DrainQueues + TASHeads' Q
    rows) shard along ``wl`` and the merged domain forest stays
    replicated — every shard's queues place into the same forest and
    GSPMD resolves the sequential placement scan's leaf-usage scatters;
    the host replay's exactness assertion is unchanged and doubles as a
    per-drain mesh-parity check.

    Scope: single-podset topology requests in ALL THREE modes —
    Required, Preferred (level relaxation,
    tas_flavor_snapshot.go:513-549), Unconstrained — over ANY number of
    taint-free TAS flavors (queues segmented by flavor, each placing
    into its own subtree of one merged domain forest); TAS
    ClusterQueues must be preemption-free and single-flavor, and the
    default BestFit profile applies (the gated Most/LeastFree profiles
    stay host-side). Topology requests on non-TAS ClusterQueues PARK in
    kernel at the exact cycle the host would reject the flavor. Heads
    outside the scope route to ``fallback`` for the cycle loop.
    """
    from kueue_tpu._jax import jnp
    from kueue_tpu.core.workload_info import quota_per_pod
    from kueue_tpu.models.constants import (
        TOPOLOGY_MODE_PREFERRED,
        TOPOLOGY_MODE_REQUIRED,
        TOPOLOGY_MODE_UNCONSTRAINED,
        PreemptionPolicy,
        ReclaimWithinCohortPolicy,
    )
    from kueue_tpu.ops.drain_kernel import (
        DrainQueues,
        TASHeads,
        solve_drain_tas_packed_jit,
    )
    from kueue_tpu.resources import PODS
    from kueue_tpu.tas.snapshot import TASPodSetRequest, domain_id

    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        allow_tas=True, policy=policy, now=now,
    )
    q = max(len(plan.cq_order), 1)
    nl = plan.queues_np["cells"].shape[1]

    tas_flavor_names = set(tas_cache.flavors)
    TAS_MODE_ID = {
        TOPOLOGY_MODE_REQUIRED: 0,
        TOPOLOGY_MODE_PREFERRED: 1,
        TOPOLOGY_MODE_UNCONSTRAINED: 2,
    }

    def cq_flavor_names(cq_name):
        cq = snapshot.cq_models[cq_name]
        return {fq.name for rg in cq.resource_groups for fq in rg.flavors}

    # ---- scope: classify queues; EVERY in-scope TAS flavor joins the
    # merged domain forest (queues segmented by flavor) ----
    drop: List[int] = []
    tas_queue: Dict[int, str] = {}  # qi -> tas flavor name
    t_bad = np.zeros((q, nl), dtype=bool)
    for qi, cq_name in enumerate(plan.cq_order):
        prem = snapshot.cq_models[cq_name].preemption
        names = cq_flavor_names(cq_name)
        tnames = names & tas_flavor_names
        if not tnames:
            # plain quota queue — topology-requesting entries on a
            # non-TAS flavor are PARKED in kernel at the exact cycle
            # the host would reject the flavor ("does not support
            # TopologyAwareScheduling", tas/manager.py check); the
            # queue itself stays in the drain
            for pos in range(int(plan.queues_np["qlen"][qi])):
                i = plan.head_of.get((qi, pos))
                if i is not None and any(
                    ps.topology_request is not None
                    for ps in plan.lowered.heads[i].pod_sets
                ):
                    t_bad[qi, pos] = True
            continue
        capable = prem.within_cluster_queue != PreemptionPolicy.NEVER or (
            snapshot.has_cohort(cq_name)
            and prem.reclaim_within_cohort != ReclaimWithinCohortPolicy.NEVER
        )
        if capable or len(names) != 1:
            drop.append(qi)
            continue
        tas_queue[qi] = next(iter(tnames))

    # per-flavor snapshots; tainted flavors stay host-side (the kernel
    # has no toleration filtering), and the merged forest caps its
    # domain axis so the placement kernel's i32 prefix sums stay exact
    # (MAX_TAS_COUNT x MAX_TAS_DOMAINS < 2^31 — see drain_kernel.split)
    flavor_names = sorted(set(tas_queue.values()))
    snaps: Dict[str, object] = {}
    total_leaves = 0
    for fname in flavor_names:
        s = tas_cache.flavors[fname].snapshot()
        s.freeze()
        over = total_leaves + len(s._leaf_order) > MAX_TAS_DOMAINS
        if over or any(t for t in s._leaf_taints):
            for qi in [k for k, v in tas_queue.items() if v == fname]:
                drop.append(qi)
                del tas_queue[qi]
        else:
            total_leaves += len(s._leaf_order)
            snaps[fname] = s
    flavor_names = sorted(snaps)
    flavor_idx = {f: i for i, f in enumerate(flavor_names)}

    # union resource axis + merged level depth
    union_res = sorted(
        {r for s in snaps.values() for r in s._resources}
    )
    n_res_t = max(len(union_res), 1)
    u_index = {r: j for j, r in enumerate(union_res)}
    d_global = max(
        (len(s.level_keys) for s in snaps.values()), default=1
    )

    # per-entry TAS lowering + scope checks
    t_is = np.zeros(q, dtype=bool)
    t_req = np.zeros((q, nl, n_res_t), dtype=np.int64)
    t_count = np.zeros((q, nl), dtype=np.int32)
    t_level = np.zeros((q, nl), dtype=np.int32)
    t_mode = np.zeros((q, nl), dtype=np.int32)
    t_top = np.zeros(q, dtype=np.int32)
    t_flavor = np.zeros(q, dtype=np.int32)
    dropped = set(drop)
    for qi, fname in tas_queue.items():
        if qi in dropped:
            continue
        snap_f = snaps[fname]
        lvl_off = d_global - len(snap_f.level_keys)
        r_index_f = set(snap_f._resources)
        ok = True
        for pos in range(int(plan.queues_np["qlen"][qi])):
            i = plan.head_of.get((qi, pos))
            if i is None:
                continue
            wl = plan.lowered.heads[i]
            if len(wl.pod_sets) != 1:
                ok = False
                break
            ps = wl.pod_sets[0]
            tr = ps.topology_request
            if tr is None or tr.mode not in TAS_MODE_ID:
                ok = False
                break
            if tr.mode == TOPOLOGY_MODE_UNCONSTRAINED:
                lvl_local = len(snap_f.level_keys) - 1  # lowest level
            elif tr.level in snap_f.level_keys:
                lvl_local = snap_f.level_keys.index(tr.level)
            else:
                ok = False
                break
            per_pod = dict(quota_per_pod(ps, None))
            per_pod[PODS] = per_pod.get(PODS, 0) + 1
            if any(r not in r_index_f for r in per_pod):
                ok = False
                break
            if int(ps.count) > MAX_TAS_COUNT:
                ok = False  # keeps the kernel's i32 prefix sums exact
                break
            for r, v in per_pod.items():
                t_req[qi, pos, u_index[r]] = int(v)
            t_count[qi, pos] = int(ps.count)
            t_level[qi, pos] = lvl_off + lvl_local
            t_mode[qi, pos] = TAS_MODE_ID[tr.mode]
        if not ok:
            drop.append(qi)
            dropped.add(qi)
        else:
            t_is[qi] = True
            t_top[qi] = d_global - len(snap_f.level_keys)
            t_flavor[qi] = flavor_idx[fname]

    # drop out-of-scope queues to the fallback path
    extra_fb: List[Tuple[Workload, str]] = []
    for qi in sorted(set(drop)):
        plan.queues_np["qlen"][qi] = 0
        plan.queues_np["cq_rows"][qi] = -1
        plan.queues_np["seg_id"][qi] = -1
        for pos in range(nl):
            i = plan.head_of.pop((qi, pos), None)
            if i is not None:
                extra_fb.append(
                    (plan.lowered.heads[i], plan.lowered.cq_names[i])
                )

    if max_cycles is not None:
        plan.max_cycles = max_cycles
    tree, paths, _ = tree_arrays(snapshot)

    live_flavors = sorted(
        {tas_queue[qi] for qi in tas_queue if qi not in dropped}
    )
    merged = _merge_tas_forests(
        [snaps[f] for f in live_flavors], union_res, d_global
    )
    if merged is not None:
        (topo_free_np, tas_usage0_np, seg_ids_np, n_domains, parent_map,
         leaf_flavor_np, leaf_off) = merged
        # remap queue flavor ids onto the LIVE flavor axis
        live_idx = {f: i for i, f in enumerate(live_flavors)}
        for qi, fname in tas_queue.items():
            if qi not in dropped:
                t_flavor[qi] = live_idx[fname]
        lf_n = topo_free_np.shape[0]
    else:
        # no TAS queue in scope: inert 1-leaf topology
        topo_free_np = np.zeros((1, 1), dtype=np.int64)
        tas_usage0_np = np.zeros((1, 1), dtype=np.int64)
        seg_ids_np = np.zeros((1, 1), dtype=np.int32)
        n_domains = (1,)
        parent_map = np.zeros((1, 1), dtype=np.int32)
        leaf_flavor_np = np.zeros(1, dtype=np.int32)
        leaf_off = {}
        lf_n = 1
        n_res_t = max(n_res_t, 1)
        t_req = t_req[:, :, :1]

    theads_np = dict(
        t_is=t_is, t_req=t_req, t_count=t_count, t_level=t_level,
        t_mode=t_mode, t_top=t_top, t_flavor=t_flavor,
        leaf_flavor=leaf_flavor_np, parent_map=parent_map, t_bad=t_bad,
    )
    n_live = int((plan.queues_np["cq_rows"] >= 0).sum())
    n_steps = _bucket(max(n_live, 1), minimum=8)

    queues_np = plan.queues_np
    if mesh is not None:
        import time as _time

        from kueue_tpu.parallel import harness
        from kueue_tpu.parallel.sharded_solver import (
            pad_queue_arrays,
            pad_tas_arrays,
            place_tas_drain_inputs,
        )

        t0p = _time.perf_counter()
        queues_np = pad_queue_arrays(queues_np, mesh.shape["wl"])
        theads_np = pad_tas_arrays(theads_np, queues_np["qlen"].shape[0])
        (tree_in, usage_in, queues, paths_in, topo_in, tusage_in,
         seg_in, theads) = place_tas_drain_inputs(
            mesh, tree, snapshot.local_usage, DrainQueues(**queues_np),
            paths, topo_free_np, tas_usage0_np, seg_ids_np,
            TASHeads(**theads_np),
        )
        harness.note_place_seconds(_time.perf_counter() - t0p)
        harness.note_bucket(
            "tas_kernel",
            (
                queues_np["cells"].shape, tuple(n_domains), n_steps,
                plan.max_cycles,
            ),
            mesh,
        )
    else:
        tree_in, paths_in = tree, paths
        usage_in = jnp.asarray(snapshot.local_usage)
        queues = DrainQueues(
            **{k: jnp.asarray(v) for k, v in queues_np.items()}
        )
        topo_in = jnp.asarray(topo_free_np)
        tusage_in = jnp.asarray(tas_usage0_np)
        seg_in = jnp.asarray(seg_ids_np)
        theads = TASHeads(
            **{k: jnp.asarray(v) for k, v in theads_np.items()}
        )

    flat = np.asarray(
        solve_drain_tas_packed_jit(
            tree_in,
            usage_in,
            queues,
            paths_in,
            topo_in,
            tusage_in,
            seg_in,
            theads,
            n_domains=n_domains,
            n_steps=n_steps,
            max_cycles=plan.max_cycles,
        )
    )  # the single fetch
    nq, nl2, npd = queues_np["cells"].shape[:3]
    ql, qlp = nq * nl2, nq * nl2 * npd
    off = 0
    adm_k = flat[off : off + qlp].reshape((nq, nl2, npd)); off += qlp
    adm_cycle = flat[off : off + ql].reshape((nq, nl2)); off += ql
    adm_step = flat[off : off + ql].reshape((nq, nl2)); off += ql
    cursor = flat[off : off + nq]; off += nq
    stuck_q = flat[off : off + nq].astype(bool); off += nq
    tas_final = flat[off : off + lf_n * n_res_t].reshape((lf_n, n_res_t))
    off += lf_n * n_res_t
    cycles = int(flat[-1])
    qlen = queues_np["qlen"]
    truncated = bool(np.any((cursor < qlen) & ~stuck_q))

    lowered = plan.lowered
    admitted: List[Tuple[Workload, str, Dict[str, str], int]] = []
    adm_meta: List[Tuple[int, int, int]] = []  # (cycle, step, index)
    parked: List[Tuple[Workload, str]] = []
    extra_fallback: List[Tuple[Workload, str]] = list(extra_fb)
    for (qi, pos), i in plan.head_of.items():
        wl = lowered.heads[i]
        cq_name = lowered.cq_names[i]
        kk = int(adm_k[qi, pos, 0])
        if kk >= 0:
            adm_meta.append(
                (int(adm_cycle[qi, pos]), int(adm_step[qi, pos]), len(admitted))
            )
            admitted.append(
                (wl, cq_name, _admitted_flavors(lowered, i, adm_k[qi, pos]),
                 int(adm_cycle[qi, pos]))
            )
        elif pos >= int(cursor[qi]):
            extra_fallback.append((wl, cq_name))
        else:
            parked.append((wl, cq_name))
    order = sorted(range(len(admitted)), key=lambda j: adm_meta[j][:2])
    admitted = [admitted[adm_meta[j][2]] for j in order]
    adm_meta = [adm_meta[j] for j in order]

    # ---- replay: reconstruct TopologyAssignments per admission cycle
    # against cycle-start state (the kernel nominates against it too),
    # per FLAVOR; the final leaf usage must reproduce the kernel's
    # exactly, flavor by flavor ----
    assignments: List[object] = [None] * len(admitted)
    if live_flavors:
        live_idx = {f: i for i, f in enumerate(live_flavors)}
        flavor_of_cq = {
            plan.cq_order[qi]: fname
            for qi, fname in tas_queue.items()
            if qi not in dropped
        }
        j = 0
        while j < len(admitted):
            cyc = adm_meta[j][0]
            batch = []
            while j < len(admitted) and adm_meta[j][0] == cyc:
                wl, cq_name, _, _ = admitted[j]
                if (
                    wl.pod_sets[0].topology_request is not None
                    and cq_name in flavor_of_cq
                ):
                    batch.append(j)
                j += 1
            placed = []
            for bj in batch:
                wl, cq_name = admitted[bj][0], admitted[bj][1]
                sf = snaps[flavor_of_cq[cq_name]]
                ps = wl.pod_sets[0]
                req = TASPodSetRequest(
                    podset_name=ps.name,
                    count=ps.count,
                    single_pod_requests=dict(quota_per_pod(ps, None)),
                    topology_request=ps.topology_request,
                    tolerations=tuple(ps.tolerations),
                )
                ta, reason = sf.find_topology_assignment(req, {})
                if reason:  # explicit raise: must survive `python -O`
                    raise AssertionError(
                        f"TAS drain replay failed for {wl.name}: {reason}"
                    )
                assignments[bj] = ta
                placed.append((sf, req, ta))
            for sf, req, ta in placed:  # charge AFTER the batch
                for dom in ta.domains:
                    did = domain_id(dom.values)
                    usage = {
                        r: v * dom.count
                        for r, v in req.single_pod_requests.items()
                    }
                    sf.add_tas_usage(did, usage, dom.count)
        for fname in live_flavors:
            sf = snaps[fname]
            sf.freeze()
            off = leaf_off[live_idx[fname]]
            nl_f = len(sf._leaf_order)
            cols = [u_index[r] for r in sf._resources]
            sub = tas_final[off : off + nl_f][:, cols]
            if not np.array_equal(sf._tas_usage, sub):
                bad = np.argwhere(sf._tas_usage != sub)[:8]
                raise AssertionError(
                    f"TAS drain replay does not reproduce the kernel's "
                    f"leaf usage for flavor {fname} — placement parity "
                    "bug; first diffs (leaf, res): "
                    + "; ".join(
                        f"{tuple(ix)}: host={sf._tas_usage[tuple(ix)]} "
                        f"kernel={sub[tuple(ix)]}"
                        for ix in bad
                    )
                )

    fb = [
        (lowered.heads[i], lowered.cq_names[i]) for i in plan.fallback
    ] + extra_fallback
    return TASDrainOutcome(
        admitted=admitted,
        parked=parked,
        fallback=fb,
        cycles=cycles,
        truncated=truncated,
        resumed=lowered.resumed,
        assignments=assignments,
    )


@dataclass
class DrainLaunch:
    """An in-flight plain-drain device dispatch (launch/fetch split).

    ``launch_drain`` dispatches the packed solve and returns
    immediately — JAX's async dispatch keeps the device working while
    the host does something else (the pipelined drain loop applies the
    PREVIOUS round's outcome inside this window, core/pipeline.py).
    ``fetch()`` blocks on the ONE result fetch and maps decisions back
    to workloads. Nothing between construction and fetch touches
    runtime state, so an unfetched launch is always safe to discard
    (the pipeline's conflict-miss path)."""

    plan: DrainPlan
    queues_np: dict
    flat_dev: object  # unfetched device outputs (_pack_drain_result)
    usage_shape: Tuple[int, int]
    extra_fb_entries: List[Tuple[Workload, str]] = field(default_factory=list)
    # the exact backlog this launch solves, in per-CQ heap order — the
    # pipeline's commit check compares it against the real post-apply
    # backlog before trusting a speculative launch
    pending: Optional[List[Tuple[Workload, str]]] = None
    max_cycles: Optional[int] = None

    def fetch(self) -> DrainOutcome:
        from kueue_tpu._jax import jax

        # the single fetch (ops/drain_kernel._pack_drain_result)
        flat, final_usage, cycles = jax.device_get(self.flat_dev)
        nq, nl, npd = self.queues_np["cells"].shape[:3]  # incl. padding
        ng = self.queues_np["gidx"].shape[-1]
        ql = nq * nl
        qlp = nq * nl * npd
        adm_k = flat[:qlp].reshape((nq, nl, npd))
        adm_cycle = flat[qlp : qlp + ql].reshape((nq, nl))
        cursor = flat[qlp + ql : qlp + ql + nq]
        stuck_q = flat[qlp + ql + nq : qlp + ql + 2 * nq].astype(bool)
        g_start = flat[qlp + ql + 2 * nq :].reshape((nq, npd, ng))
        final_usage = np.asarray(final_usage).reshape(self.usage_shape)
        cycles = int(cycles)
        return _map_drain_result(
            self.plan, adm_k, adm_cycle, cursor, stuck_q, cycles,
            self.queues_np, self.extra_fb_entries,
            final_usage=final_usage, g_start=g_start,
        )


def launch_drain(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    timestamp_fn=None,
    max_cycles: Optional[int] = None,
    mesh=None,  # jax.sharding.Mesh: shard the Q axis across devices
    resident=None,  # core.encode.ResidentEncoder (single-device only)
    policy=None,  # kueue_tpu/policy AdmissionPolicy (scored admission)
    now: float = 0.0,
) -> DrainLaunch:
    """Plan + DISPATCH the plain device drain without fetching — the
    async half of ``run_drain`` (device, no fair sharing: the pipelined
    hot path). ``run_drain(...) == launch_drain(...).fetch()`` for that
    configuration, by construction.

    With ``mesh`` the per-queue tensors shard along the mesh's ``wl``
    axis exactly like ``run_drain(mesh=...)`` — prefetched pipelined
    launches ride the same sharded path as blocking solves. With
    ``resident`` (single-device only; ignored under a mesh) the quota
    tree + paths stay device-resident between rounds and only changed
    leaf-usage rows ship (core/encode.ResidentEncoder)."""
    import time as _time

    from kueue_tpu._jax import jnp
    from kueue_tpu.ops.drain_kernel import DrainQueues, solve_drain_packed_jit

    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        policy=policy, now=now,
    )
    if max_cycles is not None:
        plan.max_cycles = max_cycles
    queues_np = plan.queues_np
    if mesh is not None:
        if resident is not None:
            # documented single-device-only: reject loudly instead of
            # silently ignoring the resident buffers (the mesh path
            # re-places inputs with their shardings every round —
            # device_put onto shards IS its transfer plan)
            raise ValueError(
                "launch_drain(resident=...) is single-device only: the "
                "mesh path re-places inputs with their shardings every "
                "round — pass resident=None under a mesh"
            )
        from kueue_tpu.parallel import harness
        from kueue_tpu.parallel.sharded_solver import (
            pad_queue_arrays,
            place_drain_inputs,
        )

        tree, paths, _ = tree_arrays(snapshot)
        t0p = _time.perf_counter()
        queues_np = pad_queue_arrays(queues_np, mesh.shape["wl"])
        tree, usage_in, queues, paths = place_drain_inputs(
            mesh, tree, snapshot.local_usage, DrainQueues(**queues_np), paths
        )
        harness.note_place_seconds(_time.perf_counter() - t0p)
        harness.note_bucket(
            "drain_kernel",
            (
                queues_np["cells"].shape, plan.n_segments, plan.n_steps,
                plan.max_cycles,
            ),
            mesh,
        )
    else:
        if resident is not None:
            tree, paths, usage_in = resident.refresh(snapshot)
        else:
            tree, paths, _ = tree_arrays(snapshot)
            usage_in = jnp.asarray(snapshot.local_usage)
        queues = DrainQueues(
            **{k: jnp.asarray(v) for k, v in queues_np.items()}
        )
    flat_dev = solve_drain_packed_jit(
        tree,
        usage_in,
        queues,
        paths,
        n_segments=plan.n_segments,
        n_steps=plan.n_steps,
        max_cycles=plan.max_cycles,
    )
    return DrainLaunch(
        plan=plan,
        queues_np=queues_np,
        flat_dev=flat_dev,
        usage_shape=tuple(snapshot.local_usage.shape),
        pending=list(pending),
        max_cycles=plan.max_cycles,
    )


def _cap_suffix_of(plan: DrainPlan) -> np.ndarray:
    """int32[Q, L] suffix retry budgets: ``cap[q, p]`` is the
    ``retry_cap`` a fresh ``plan_drain`` over the queue's entries at
    positions >= p would compute (min(4096, max walk_states + 1)) —
    what the megaloop gathers at each round boundary so its in-kernel
    continuation budgets match a serial re-plan's. Column 0 equals
    ``plan.queues_np['retry_cap']`` by construction."""
    q = plan.queues_np["qlen"].shape[0]
    l = plan.queues_np["cells"].shape[1]
    cap = np.zeros((q, l), dtype=np.int32)
    per_q: Dict[int, List[Tuple[int, int]]] = {}
    for (qi, pos), i in plan.head_of.items():
        per_q.setdefault(qi, []).append((pos, i))
    ws = plan.lowered.walk_states
    for qi, items in per_q.items():
        items.sort()
        vals = np.array([ws[i] for _, i in items], dtype=np.int64)
        sfx = np.maximum.accumulate(vals[::-1])[::-1]
        cap[qi, : len(items)] = np.minimum(4096, sfx + 1).astype(np.int32)
    return cap


@dataclass
class MegaloopLog:
    """The host-decoded round-stamped decision log of one fused launch:
    one DrainOutcome per executed round, in round order — exactly the
    sequence of outcomes K serial ``launch_drain(max_cycles=chunk)``
    rounds would have fetched (asserted against the serial mirror in
    tests/test_megaloop.py). ``truncated`` means the final round still
    left entries undecided: the megaloop exhausted its round budget and
    the caller relaunches from the real post-apply state."""

    rounds: List[DrainOutcome]
    n_rounds: int
    cycles: int
    truncated: bool


@dataclass
class MegaloopLaunch:
    """An in-flight fused megaloop dispatch (the launch/fetch split of
    ``launch_drain`` extended to K rounds): ONE dispatch, ONE fetch for
    the whole batch. Nothing between construction and ``fetch`` touches
    runtime state, so an unfetched launch is always safe to discard."""

    plan: DrainPlan
    queues_np: dict
    flat_dev: object  # unfetched (int32 log, int64 round usage)
    usage_shape: Tuple[int, int]
    start_usage: np.ndarray  # launch-time leaf usage (row-0 fallback)
    pending: List[Tuple[Workload, str]]
    chunk_cycles: int
    max_rounds: int

    def usage_dev(self, r: int):
        """Round r's final leaf usage as a DEVICE slice of the packed
        log — the in-loop usage carry the ResidentEncoder adopts after
        a fully-committed launch (no host round trip)."""
        return self.flat_dev[1][r]

    def fetch(self) -> MegaloopLog:
        from kueue_tpu._jax import jax

        flat, r_usage = jax.device_get(self.flat_dev)  # the single fetch
        q, l, p = self.queues_np["cells"].shape[:3]
        n, fr = self.usage_shape
        rr = self.max_rounds
        qlp, ql = q * l * p, q * l
        off = 0
        adm_k = flat[off : off + qlp].reshape((q, l, p)); off += qlp
        adm_cycle = flat[off : off + ql].reshape((q, l)); off += ql
        adm_round = flat[off : off + ql].reshape((q, l)); off += ql
        r_cursor = flat[off : off + rr * q].reshape((rr, q)); off += rr * q
        r_stuck = (
            flat[off : off + rr * q].reshape((rr, q)).astype(bool)
        ); off += rr * q
        r_cycles = flat[off : off + rr]; off += rr
        ng = self.queues_np["gidx"].shape[-1]
        r_gstart = flat[off : off + rr * q * p * ng].reshape((rr, q, p, ng))
        r_usage = np.asarray(r_usage).reshape((rr, n, fr))
        n_rounds = int(flat[-2])
        cycles = int(flat[-1])
        rounds = _map_megaloop_rounds(
            self.plan, self.queues_np, adm_k, adm_cycle, adm_round,
            r_cursor, r_stuck, r_cycles, r_usage, n_rounds,
            self.start_usage, r_gstart,
        )
        return MegaloopLog(
            rounds=rounds,
            n_rounds=n_rounds,
            cycles=cycles,
            truncated=bool(rounds and rounds[-1].undecided),
        )


def _map_megaloop_rounds(
    plan: DrainPlan,
    queues_np: dict,
    adm_k,
    adm_cycle,
    adm_round,
    r_cursor,
    r_stuck,
    r_cycles,
    r_usage,
    n_rounds: int,
    start_usage: np.ndarray,
    r_gstart: np.ndarray,
) -> List[DrainOutcome]:
    """Slice the fused log into per-round DrainOutcomes — each
    bit-for-bit what ``_map_drain_result`` would have produced for a
    serial round launched over the previous round's undecided backlog:
    round scope is the entries past the previous cursor in queues not
    yet retired; unreached entries route to fallback (and to
    ``undecided`` unless their queue went stuck); the structural
    ``plan.fallback`` set belongs to round 0 only (later serial rounds
    are planned over undecided entries, all representable)."""
    lowered = plan.lowered
    q = queues_np["qlen"].shape[0]
    rounds: List[DrainOutcome] = []
    prev_cursor = np.zeros(q, dtype=np.int64)
    prev_dead = np.zeros(q, dtype=bool)
    for r in range(max(int(n_rounds), 1)):
        ran = r < int(n_rounds)
        cursor_r = np.asarray(r_cursor[r] if ran else prev_cursor)
        stuck_r = (
            np.asarray(r_stuck[r]).astype(bool) if ran else prev_dead
        )
        cycles_r = int(r_cycles[r]) if ran else 0
        usage_r = (
            np.asarray(r_usage[r]) if ran else np.asarray(start_usage)
        )
        admitted: List[Tuple[Workload, str, Dict[str, str], int]] = []
        parked: List[Tuple[Workload, str]] = []
        fb_extra: List[Tuple[Workload, str]] = []
        undecided: List[Tuple[Workload, str]] = []
        for (qi, pos), i in plan.head_of.items():
            if prev_dead[qi] or pos < prev_cursor[qi]:
                continue  # decided (or retired) in an earlier round
            wl = lowered.heads[i]
            cq_name = lowered.cq_names[i]
            if int(adm_round[qi, pos]) == r:
                admitted.append(
                    (wl, cq_name,
                     _admitted_flavors(lowered, i, adm_k[qi, pos]),
                     int(adm_cycle[qi, pos]))
                )
            elif pos >= int(cursor_r[qi]):
                # never processed this round: no decision; stuck-frozen
                # queues are terminal, the rest feed the next round
                fb_extra.append((wl, cq_name))
                if not stuck_r[qi]:
                    undecided.append((wl, cq_name))
            else:
                parked.append((wl, cq_name))
        admitted.sort(key=lambda t: t[3])
        fb = (
            [(lowered.heads[i], lowered.cq_names[i]) for i in plan.fallback]
            if r == 0
            else []
        ) + fb_extra
        rounds.append(
            DrainOutcome(
                admitted=admitted,
                parked=parked,
                fallback=fb,
                cycles=cycles_r,
                truncated=bool(undecided),
                undecided=undecided,
                final_usage=usage_r.astype(np.int64, copy=False),
                resume=(
                    _resume_of(plan, cursor_r, stuck_r, r_gstart[r])
                    if ran and undecided
                    else []
                ),
                # later rounds resume on the device, not in a lowering
                resumed=lowered.resumed if r == 0 else 0,
            )
        )
        prev_cursor = cursor_r.astype(np.int64).copy()
        prev_dead = prev_dead | stuck_r
    return rounds


def launch_drain_megaloop(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    timestamp_fn=None,
    chunk_cycles: int = 16,
    max_rounds: int = 8,
    mesh=None,  # jax.sharding.Mesh: shard the Q axis across devices
    resident=None,  # core.encode.ResidentEncoder (single-device only)
    policy=None,  # kueue_tpu/policy AdmissionPolicy (scored admission)
    now: float = 0.0,
) -> MegaloopLaunch:
    """Plan + DISPATCH the fused K-round megaloop without fetching —
    ``launch_drain`` with the host round trip amortized over up to
    ``max_rounds`` drain rounds of ``chunk_cycles`` kernel cycles each
    (ops/megaloop_kernel.solve_drain_megaloop). The policy score
    tensors flow through ``plan_drain`` unchanged, so the megaloop is
    policy-complete, not a first-fit fast path.

    With ``mesh`` the per-queue tensors (and the suffix retry budgets)
    shard along ``wl`` exactly like ``launch_drain(mesh=...)``. With
    ``resident`` (single-device only; a passed resident under a mesh
    raises — see launch_drain) the quota tree + paths stay
    device-resident between launches."""
    import time as _time

    from kueue_tpu._jax import jnp
    from kueue_tpu.ops.drain_kernel import DrainQueues
    from kueue_tpu.ops.megaloop_kernel import (
        solve_drain_megaloop_packed_jit,
    )

    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        policy=policy, now=now,
    )
    cap_suffix = _cap_suffix_of(plan)
    queues_np = plan.queues_np
    if mesh is not None:
        if resident is not None:
            raise ValueError(
                "launch_drain_megaloop(resident=...) is single-device "
                "only: the mesh path re-places inputs with their "
                "shardings every launch (device_put onto shards IS its "
                "transfer plan) — pass resident=None under a mesh"
            )
        from kueue_tpu.parallel import harness
        from kueue_tpu.parallel.sharded_solver import (
            _sh,
            pad_queue_arrays,
            place_drain_inputs,
        )

        t0p = _time.perf_counter()
        mult = mesh.shape["wl"]
        queues_np = pad_queue_arrays(queues_np, mult)
        q_pad = queues_np["qlen"].shape[0]
        if cap_suffix.shape[0] < q_pad:
            cap_suffix = np.concatenate(
                [
                    cap_suffix,
                    np.zeros(
                        (q_pad - cap_suffix.shape[0], cap_suffix.shape[1]),
                        dtype=cap_suffix.dtype,
                    ),
                ]
            )
        tree, paths, _ = tree_arrays(snapshot)
        tree, usage_in, queues, paths = place_drain_inputs(
            mesh, tree, snapshot.local_usage, DrainQueues(**queues_np), paths
        )
        from kueue_tpu._jax import jax as _jax

        cap_in = _jax.device_put(cap_suffix, _sh(mesh, "wl", None))
        harness.note_place_seconds(_time.perf_counter() - t0p)
        harness.note_bucket(
            "megaloop_kernel",
            (
                queues_np["cells"].shape, plan.n_segments, plan.n_steps,
                chunk_cycles, max_rounds,
            ),
            mesh,
        )
    else:
        if resident is not None:
            tree, paths, usage_in = resident.refresh(snapshot)
        else:
            tree, paths, _ = tree_arrays(snapshot)
            usage_in = jnp.asarray(snapshot.local_usage)
        queues = DrainQueues(
            **{k: jnp.asarray(v) for k, v in queues_np.items()}
        )
        cap_in = jnp.asarray(cap_suffix)
    flat_dev = solve_drain_megaloop_packed_jit(
        tree,
        usage_in,
        queues,
        paths,
        cap_in,
        n_segments=plan.n_segments,
        n_steps=plan.n_steps,
        chunk_cycles=int(chunk_cycles),
        max_rounds=int(max_rounds),
    )
    return MegaloopLaunch(
        plan=plan,
        queues_np=queues_np,
        flat_dev=flat_dev,
        usage_shape=tuple(snapshot.local_usage.shape),
        start_usage=np.asarray(snapshot.local_usage),
        pending=list(pending),
        chunk_cycles=int(chunk_cycles),
        max_rounds=int(max_rounds),
    )


def run_drain_megaloop_host(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    timestamp_fn=None,
    chunk_cycles: int = 16,
    max_rounds: int = 8,
    policy=None,
    now: float = 0.0,
) -> MegaloopLog:
    """The megaloop's numpy HOST AUTHORITY twin over the identical
    plan tensors (ops/megaloop_np.solve_megaloop_np — which IS the
    serial chunked loop over suffix-trimmed queues), decoded through
    the same ``_map_megaloop_rounds``. Bit-for-bit the device log's
    decisions, property-tested in tests/test_megaloop.py."""
    from kueue_tpu.core.encode import encode_snapshot
    from kueue_tpu.ops.assign_kernel import build_paths
    from kueue_tpu.ops.megaloop_np import solve_megaloop_np

    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        policy=policy, now=now,
    )
    cap_suffix = _cap_suffix_of(plan)
    enc = encode_snapshot(snapshot)
    paths_np = build_paths(enc.parent, enc.max_depth)
    host = solve_megaloop_np(
        enc.parent,
        enc.level_mask,
        enc.nominal.astype(np.int64, copy=False),
        enc.lending_limit.astype(np.int64, copy=False),
        enc.borrowing_limit.astype(np.int64, copy=False),
        enc.local_usage.astype(np.int64, copy=False),
        plan.queues_np,
        paths_np,
        enc.max_depth,
        int(chunk_cycles),
        int(max_rounds),
        cap_suffix,
    )
    rounds = _map_megaloop_rounds(
        plan, plan.queues_np, host.admitted_k, host.admitted_cycle,
        host.admitted_round, host.round_cursor, host.round_stuck,
        host.round_cycles, host.round_usage, host.rounds,
        np.asarray(snapshot.local_usage), host.round_gstart,
    )
    return MegaloopLog(
        rounds=rounds,
        n_rounds=host.rounds,
        cycles=host.cycles,
        truncated=bool(rounds and rounds[-1].undecided),
    )


def run_drain(
    snapshot: Snapshot,
    pending: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 4,
    timestamp_fn=None,
    max_cycles: Optional[int] = None,
    mesh=None,  # jax.sharding.Mesh: shard the Q axis across devices
    fair_sharing: bool = False,
    use_device: bool = True,
    policy=None,  # kueue_tpu/policy AdmissionPolicy (scored admission)
    now: float = 0.0,
) -> DrainOutcome:
    """Plan + solve + map back, with one device round trip.

    ``max_cycles`` overrides the computed backstop (operators capping
    device time; tests exercising truncation routing). With ``mesh``
    the per-queue tensors are sharded along the mesh's ``wl`` axis
    (each device owns a slice of the ClusterQueues). With
    ``fair_sharing`` the cycle's admission order is the fair-sharing
    cohort tournament run ON DEVICE (ops/drain_kernel.solve_drain_fair)
    instead of the (borrowing, priority, FIFO) sort; preempt-capable
    ClusterQueues route to ``fallback`` in fair mode (use
    run_drain_fair_preempt for fair preemption in the drain). With
    ``mesh`` the per-queue tensors (and the fair DRS chain work) are
    sharded along ``wl``; node-space tensors stay replicated — separate
    root cohorts are independent subproblems, so the tournament's
    segment reductions parallelize and GSPMD resolves the node-space
    scatters.

    ``use_device=False`` solves the IDENTICAL plan on the numpy host
    mirror (ops/drain_np.solve_drain_np) — bit-for-bit the same
    decisions, property-tested across seeded random snapshots
    (tests/test_drain_parity.py). Plain scope only: the fair
    tournament keeps the kernel as its single implementation."""
    from kueue_tpu._jax import jnp
    from kueue_tpu.ops.drain_kernel import DrainQueues, solve_drain_packed_jit

    if not use_device and (fair_sharing or mesh is not None):
        raise ValueError(
            "use_device=False covers the plain drain only (no fair "
            "tournament, no mesh sharding)"
        )
    plan = plan_drain(
        snapshot, pending, flavors, max_candidates, max_cells, timestamp_fn,
        policy=policy, now=now,
    )
    extra_fb_entries: List[Tuple[Workload, str]] = []
    if fair_sharing:
        # the in-kernel tournament orders admissions; fair PREEMPTION
        # stays on the per-cycle batched path, so preempt-capable CQs
        # fall back to the cycle loop
        from kueue_tpu.models.constants import (
            PreemptionPolicy,
            ReclaimWithinCohortPolicy,
        )

        for qi, cq_name in enumerate(plan.cq_order):
            prem = snapshot.cq_models[cq_name].preemption
            capable = (
                prem.within_cluster_queue != PreemptionPolicy.NEVER
                or (
                    snapshot.has_cohort(cq_name)
                    and prem.reclaim_within_cohort
                    != ReclaimWithinCohortPolicy.NEVER
                )
            )
            if not capable:
                continue
            plan.queues_np["qlen"][qi] = 0
            plan.queues_np["cq_rows"][qi] = -1
            plan.queues_np["seg_id"][qi] = -1
            for pos in range(plan.queues_np["cells"].shape[1]):
                i = plan.head_of.pop((qi, pos), None)
                if i is not None:
                    extra_fb_entries.append(
                        (plan.lowered.heads[i], plan.lowered.cq_names[i])
                    )
    if max_cycles is not None:
        plan.max_cycles = max_cycles
    if not use_device:
        # the numpy twin over the identical plan tensors — the guard's
        # host-authority drain and the parity property-test surface
        from kueue_tpu.core.encode import encode_snapshot
        from kueue_tpu.ops.assign_kernel import build_paths
        from kueue_tpu.ops.drain_np import solve_drain_np

        enc = encode_snapshot(snapshot)
        paths_np = build_paths(enc.parent, enc.max_depth)
        host = solve_drain_np(
            enc.parent,
            enc.level_mask,
            enc.nominal.astype(np.int64, copy=False),
            enc.lending_limit.astype(np.int64, copy=False),
            enc.borrowing_limit.astype(np.int64, copy=False),
            enc.local_usage.astype(np.int64, copy=False),
            plan.queues_np,
            paths_np,
            enc.max_depth,
            plan.max_cycles,
        )
        return _map_drain_result(
            plan,
            host.admitted_k,
            host.admitted_cycle,
            host.cursor,
            host.stuck,
            int(host.cycles),
            plan.queues_np,
            extra_fb_entries=[],
            final_usage=np.asarray(host.local_usage),
            g_start=host.g_start,
        )
    tree, paths, _ = tree_arrays(snapshot)
    queues_np = plan.queues_np
    if mesh is not None:
        import time as _time

        from kueue_tpu.parallel import harness
        from kueue_tpu.parallel.sharded_solver import (
            pad_queue_arrays,
            place_drain_inputs,
        )

        t0p = _time.perf_counter()
        queues_np = pad_queue_arrays(queues_np, mesh.shape["wl"])
        # numpy -> device_put straight onto the shards (one transfer)
        tree, usage_in, queues, paths = place_drain_inputs(
            mesh, tree, snapshot.local_usage, DrainQueues(**queues_np), paths
        )
        harness.note_place_seconds(_time.perf_counter() - t0p)
        harness.note_bucket(
            "drain_kernel",
            (
                queues_np["cells"].shape, plan.n_segments, plan.n_steps,
                plan.max_cycles, "fair" if fair_sharing else "plain",
            ),
            mesh,
        )
    else:
        usage_in = jnp.asarray(snapshot.local_usage)
        queues = DrainQueues(**{k: jnp.asarray(v) for k, v in queues_np.items()})

    if fair_sharing:
        from kueue_tpu.features import enabled as _feature_enabled
        from kueue_tpu.ops.drain_kernel import solve_drain_fair_packed_jit

        n_res = len(snapshot.resource_names)
        depth_of, lendable, res_of_fr = _fair_lendable(
            snapshot, np.asarray(paths)
        )
        if mesh is not None:
            from kueue_tpu.parallel.sharded_solver import (
                place_fair_drain_extras,
            )

            depth_in, weight_in, lendable_in, res_in = (
                place_fair_drain_extras(
                    mesh, depth_of, snapshot.weight_milli, lendable,
                    res_of_fr,
                )
            )
        else:
            depth_in = jnp.asarray(depth_of)
            weight_in = jnp.asarray(snapshot.weight_milli)
            lendable_in = jnp.asarray(lendable)
            res_in = jnp.asarray(res_of_fr)
        flat_dev = solve_drain_fair_packed_jit(
            tree,
            usage_in,
            queues,
            paths,
            depth_in,
            weight_in,
            lendable_in,
            res_in,
            n_segments=plan.n_segments,
            n_steps=plan.n_steps,
            max_cycles=plan.max_cycles,
            n_res=n_res,
            prio_tie=bool(_feature_enabled("PrioritySortingWithinCohort")),
        )
    else:
        flat_dev = solve_drain_packed_jit(
            tree,
            usage_in,
            queues,
            paths,
            n_segments=plan.n_segments,
            n_steps=plan.n_steps,
            max_cycles=plan.max_cycles,
        )
    return DrainLaunch(
        plan=plan,
        queues_np=queues_np,
        flat_dev=flat_dev,
        usage_shape=tuple(snapshot.local_usage.shape),
        extra_fb_entries=extra_fb_entries,
        pending=list(pending),
        max_cycles=plan.max_cycles,
    ).fetch()


def _map_drain_result(
    plan: DrainPlan,
    adm_k,
    adm_cycle,
    cursor,
    stuck_q,
    cycles: int,
    queues_np: dict,
    extra_fb_entries: List[Tuple[Workload, str]],
    final_usage: np.ndarray,
    g_start: np.ndarray,
) -> DrainOutcome:
    """Map a plain drain's per-queue result tensors back onto workloads
    — ONE definition shared by the device fetch and the numpy host
    mirror, so the two paths cannot disagree on outcome decoding."""
    # stuck-frozen queues are terminal no-decisions, not truncation
    truncated = bool(np.any((cursor < queues_np["qlen"]) & ~stuck_q))

    lowered = plan.lowered
    admitted: List[Tuple[Workload, str, Dict[str, str], int]] = []
    parked: List[Tuple[Workload, str]] = []
    extra_fallback: List[Tuple[Workload, str]] = []
    undecided: List[Tuple[Workload, str]] = []
    for (qi, pos), i in plan.head_of.items():
        wl = lowered.heads[i]
        cq_name = lowered.cq_names[i]
        kk = int(adm_k[qi, pos, 0])
        if kk >= 0:
            admitted.append(
                (wl, cq_name, _admitted_flavors(lowered, i, adm_k[qi, pos]),
                 int(adm_cycle[qi, pos]))
            )
        elif pos >= int(cursor[qi]):
            # never processed (max_cycles backstop hit): not a decision.
            # Entries of stuck-frozen queues are terminal no-decisions
            # (a rerun cannot resolve them); the rest are undecided and
            # a follow-up chunk from the post-apply state decides them.
            extra_fallback.append((wl, cq_name))
            if not bool(stuck_q[qi]):
                undecided.append((wl, cq_name))
        else:
            parked.append((wl, cq_name))
    admitted.sort(key=lambda t: t[3])
    fb = (
        [(lowered.heads[i], lowered.cq_names[i]) for i in plan.fallback]
        + extra_fb_entries
        + extra_fallback
    )
    return DrainOutcome(
        admitted=admitted, parked=parked, fallback=fb, cycles=cycles,
        truncated=truncated, undecided=undecided, final_usage=final_usage,
        resume=_resume_of(plan, cursor, stuck_q, g_start) if undecided else [],
        resumed=lowered.resumed,
    )
