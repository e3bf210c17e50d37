"""Multi-cycle admission drain — the whole backlog on the device.

The interactive scheduler ping-pongs one cycle at a time: pop heads,
solve, fetch, admit, repeat. Every fetch pays a full host<->device
round trip, which can dwarf the solve itself. For the
bulk scenario the north star describes (a large pending backlog drained
to quiescence with no arrivals in between — BASELINE.md: 50k pending
over 1k ClusterQueues), the TPU-native formulation is to keep the WHOLE
drain on device: per-CQ pending queues become dense tensors, the
pop-head/solve/advance loop becomes a ``lax.while_loop`` over cycles,
and ONE fetch returns every admission decision.

Per cycle this reproduces exactly the reference's semantics
(``pkg/scheduler/scheduler.go:176-310``) for preemption-free drains:

- heads: each CQ's queue front (one head per CQ per cycle, matching
  queue.Manager.Heads);
- nomination: phase-1 flavor classification against cycle-start usage
  (ops/assign_kernel.phase1_classify);
- conflict resolution: the segmented phase-2 scan in the reference's
  entry order (scheduler.go:575-599), independent root cohorts in
  parallel;
- queue motion: admitted heads leave; NoFit heads park forever (in a
  drain no capacity is ever released, so the reference's
  inadmissible-parking reactivation can never fire — the cursor just
  advances); heads that fit at nomination but lost the in-cycle
  conflict stay at the front and retry next cycle (BestEffortFIFO
  immediate requeue, cluster_queue.go:402-407);
- capacity reservation: blocked preempt-mode heads with
  reclaimWithinCohort != Any reserve capacity WITHIN their cycle
  (scheduler.go:228-242); reservations drop at cycle end because the
  reserving head parks — rebuilding the usage tree from leaf rows each
  cycle makes this exact.

Decision parity with the sequential host scheduler is asserted in
tests/test_drain.py.
"""

from __future__ import annotations

from typing import NamedTuple

from kueue_tpu._jax import jax, jnp, lax
from kueue_tpu.ops.assign_kernel import (
    _avail_along_path,
    _gather_cells,
    segmented_rank,
)
from kueue_tpu.ops.quota import (
    DRS_MAX,
    NO_LIMIT,
    QuotaTree,
    subtree_quota,
    usage_tree,
)


class DrainQueues(NamedTuple):
    """Per-ClusterQueue pending queues, densely packed.

    Q queues, L max queue length, P podsets, K flavor candidates,
    C cells per candidate. Per-entry tensors carry a podset axis:
    cells/qty int[Q,L,P,K,C], valid bool[Q,L,P,K], gidx/glast
    [Q,L,P,K,G], cgrp [Q,L,P,K,C]; n_podsets int32[Q,L] counts the
    REAL podsets (pad podsets are inert).

    cq_rows:  int32[Q]     — tree row of each queue's ClusterQueue.
    seg_id:   int32[Q]     — compact root-cohort id (segmented phase 2).
    qlen:     int32[Q]     — live entries in each queue.
    cells:    int32[Q,L,K,C] / qty: int64[Q,L,K,C] / valid: bool[Q,L,K]
              — each entry's lowered flavor candidates (core/solver.py
              lower_heads layout).
    gidx:     int32[Q,L,K,G] — candidate k's flavor index within each
              of the entry's G resource-group walks (pad groups 0).
    glast:    bool[Q,L,K,G] — that flavor is the LAST of its group's
              walk (host cursor semantics store -1 there: the resumed
              walk restarts that group at flavor 0). Together these
              carry the per-group LastAssignment vector: a
              conflict-skipped head's next attempt admits exactly the
              candidates whose every group index is >= the resumed
              per-group start — the same set a host-side template
              rebuilt from the stored cursors would enumerate.
    cgrp:     int8[Q,L,K,C] — resource-group index of each candidate
              cell (-1 pad), for the per-group walks.
    ffb/ffp:  bool[Q] — the ClusterQueue's flavorFungibility policy
              bits: whenCanBorrow == Borrow / whenCanPreempt == Preempt
              (clusterqueue_types.go:379-401), consumed by the
              policy-aware group walk.
    retry_cap: int32[Q] — PendingFlavors retry budget: the queue's max
              joint cursor-odometer size (prod over podsets and groups
              of walk length + 1). A CONVERGENT retry sequence cannot
              exceed it, so exceeding it proves a divergent spin.
    priority: int64[Q,L] / timestamp: int64[Q,L] — entry order keys,
              already sorted within each queue (priority desc, ts asc —
              the pending-heap order, cluster_queue.go:413-426).
    no_reclaim: bool[Q]    — CQ reserves capacity when blocked.
    """

    cq_rows: jnp.ndarray
    seg_id: jnp.ndarray
    qlen: jnp.ndarray
    cells: jnp.ndarray
    qty: jnp.ndarray
    valid: jnp.ndarray
    gidx: jnp.ndarray
    glast: jnp.ndarray
    cgrp: jnp.ndarray
    n_podsets: jnp.ndarray
    ffb: jnp.ndarray
    ffp: jnp.ndarray
    retry_cap: jnp.ndarray
    priority: jnp.ndarray
    timestamp: jnp.ndarray
    no_reclaim: jnp.ndarray
    # int64[Q,L,P,K] admission-policy candidate scores
    # (kueue_tpu/policy): the group walk's candidate choice becomes a
    # masked score-argmax with ties keeping the walk order — an
    # all-zero tensor (the default first-fit policy) reproduces the
    # earliest-flavor choice bit-for-bit. None (kernel-level tests)
    # is identical to all-zero; plan_drain always ships an array.
    score: jnp.ndarray = None
    # int32[Q,P,G] each queue's first flavor walk: its first head's
    # resume cursor per podset and group (-1 where it has none), read by
    # every drain kernel. None (kernel-level tests) starts every walk at
    # the first flavor.
    g_start0: jnp.ndarray = None


class DrainResult(NamedTuple):
    """admitted_k: int32[Q,L] chosen candidate per queue entry (-1 =
    never admitted); admitted_cycle: int32[Q,L] cycle index of the
    admission (-1 = never); cursor: int32[Q] final queue position —
    entries at pos >= cursor were never processed (max_cycles hit);
    cycles: int32 scalar — cycles executed; local_usage: int64[N,FR]
    final leaf usage; g_start: int32[Q,P,G] the flavor walk each
    queue's head resumes from (its LastAssignment), so a drain cut at
    max_cycles continues where the host cycle loop would."""

    admitted_k: jnp.ndarray
    admitted_cycle: jnp.ndarray
    cursor: jnp.ndarray
    cycles: jnp.ndarray
    local_usage: jnp.ndarray
    stuck: jnp.ndarray  # bool[Q] — frozen PendingFlavors spinners
    g_start: jnp.ndarray


def _group_walk(
    gid, gl, gmask, head_valid, fit_cells, pot_cells, reclaim_cells,
    borrow_cells, ffb, ffp, score=None,
):
    """Policy-aware emulation of the host's per-group flavor walk
    (flavor_assigner._find_flavor_for_resource + _should_try_next_flavor
    + the reclaim-oracle upgrade), vectorized over queues.

    Each resource group walks its flavors (ascending index, restricted
    by the per-group cursor already folded into ``head_valid``):

    - a flavor STOPS the walk when it fits and is non-borrowing, when
      it fits and whenCanBorrow=Borrow (``ffb``), or — under
      whenCanPreempt=Preempt (``ffp``) — when it is preempt/reclaim
      eligible (subject to the same borrow condition);
    - otherwise the walk runs to the group's end and the best granular
      mode seen wins (FIT > RECLAIM > PREEMPT, earliest flavor of it);
    - the stored cursor is the stop index (-1 when the stop was the
      group's last flavor or the walk ran to the end), and the podset's
      LastAssignment is pending iff any group stored a real index.

    With ``score`` (int64[Q,K], kueue_tpu/policy) the per-group choice
    is a masked score-argmax: among stop-eligible candidates the
    highest score wins, ties keep the earliest flavor index; the
    best-mode fallback (walks that ran to the end) scores identically
    within the best granular mode. All-zero scores (or score=None)
    reduce every reduction to the earliest-flavor choice — the default
    first-fit walk, bit-for-bit.

    Returns (chosen int32[Q], pre_k int32[Q], pending bool[Q],
    next_start int32[Q,G]): the representative candidate for FIT heads,
    for preempt-mode heads, the PendingFlavors flag, and the per-group
    resume starts used by conflict-loss and pending retries alike."""
    g = gid.shape[-1]
    inf = jnp.int32(2**30)
    neg = jnp.int64(-(2**62))
    sc = (score if score is not None else jnp.zeros_like(head_valid, jnp.int64))[
        :, :, None
    ]  # [Q,K,1]
    valid3 = head_valid[:, :, None]  # [Q,K,1]
    # per-candidate per-group aggregates
    cellmode = jnp.where(
        fit_cells,
        3,
        jnp.where(pot_cells & reclaim_cells, 2, jnp.where(pot_cells, 1, 0)),
    ).astype(jnp.int32)
    gmode = jnp.min(
        jnp.where(gmask, cellmode[..., None], 3), axis=2
    )  # [Q,K,G]
    gborrow = jnp.any(
        jnp.where(gmask, borrow_cells[..., None], False), axis=2
    )  # [Q,K,G]
    borrow_ok = ~gborrow | ffb[:, None, None]
    stop = valid3 & (
        ((gmode == 3) & borrow_ok)
        | ((gmode == 1) | (gmode == 2)) & ffp[:, None, None] & borrow_ok
    )
    stop_sc = jnp.where(stop, sc, neg)  # [Q,K,G]
    stop_best = jnp.max(stop_sc, axis=1)  # [Q,G]
    stop_sel = stop & (stop_sc == stop_best[:, None, :])
    stop_idx = jnp.min(jnp.where(stop_sel, gid, inf), axis=1)  # [Q,G]
    stopped = stop_idx < inf
    best_mode = jnp.max(jnp.where(valid3, gmode, -1), axis=1)  # [Q,G]
    bm_sel = valid3 & (gmode == best_mode[:, None, :])
    bm_sc = jnp.where(bm_sel, sc, neg)
    bm_best = jnp.max(bm_sc, axis=1)  # [Q,G]
    best_idx = jnp.min(
        jnp.where(bm_sel & (bm_sc == bm_best[:, None, :]), gid, inf), axis=1
    )
    choice_idx = jnp.where(stopped, stop_idx, best_idx)  # [Q,G]
    at_choice = valid3 & (gid == choice_idx[:, None, :])
    choice_mode = jnp.max(jnp.where(at_choice, gmode, -1), axis=1)  # [Q,G]
    have = (choice_idx < inf) & (choice_mode >= 1)
    head_mode = jnp.min(jnp.where(have, choice_mode, 0), axis=1)  # [Q]
    match = head_valid & jnp.all(gid == choice_idx[:, None, :], axis=-1)
    has_rep = jnp.any(match, axis=1)
    k_rep = jnp.argmax(match, axis=1).astype(jnp.int32)
    chosen = jnp.where((head_mode == 3) & has_rep, k_rep, -1)
    pre_k = jnp.where(
        ((head_mode == 1) | (head_mode == 2)) & has_rep, k_rep, -1
    )
    # stored cursor: the stop index unless it was the group's last
    # flavor; best-mode (non-stop) walks ran to the end and store -1
    is_last = jnp.any(at_choice & gl, axis=1)
    tried = jnp.where(stopped & ~is_last, choice_idx, -1)
    pending = jnp.any(tried >= 0, axis=1)
    next_start = (tried + 1).astype(jnp.int32)
    return chosen, pre_k, pending, next_start


def _nominate_multi(
    tree, subtree, guaranteed, local, usage0, queues, q_idx, cur, active,
    g_start, potential, vcells_q=None, elig_v=None, pwb=None,
):
    """Sequential multi-podset nomination for the current heads.

    The host nominates a workload's podsets IN ORDER; podset p's flavor
    walk evaluates quantities inflated by the usage accumulated by
    podsets < p at shared (flavor, resource) cells (assignment_usage —
    cell-level coupling only, never through the tree). A podset with no
    choices fails the whole workload (later podsets unprocessed, cursor
    cleared); preempt-mode podsets keep accumulating.

    Returns (is_fit, is_pre, pending, head_borrow, rep_k [Q,P],
    next_start [Q,P,G], mcells [Q,P*C], mqty [Q,P*C], mneed [Q,P*C])
    where mcells/mqty are the merged representative cells with per-fr
    quantities SUMMED onto the first occurrence (duplicates zeroed), so
    fits checks, usage deltas and reservations each count shared cells
    once; mneed marks the merged cells whose resource was classified
    preempt-mode (the host's frs_need_preemption — any podset whose
    choice at that flavor-resource did not Fit)."""
    from kueue_tpu.ops.assign_kernel import available_all, cell_masks

    q, l, pmax, k, c = queues.cells.shape
    # tree-wide availability once per cycle (NOT per podset): every
    # podset's masks read the same cycle-start snapshot
    avail0 = available_all(tree, subtree, guaranteed, usage0)
    g = queues.gidx.shape[-1]
    n_fr = local.shape[1]
    head_cq = jnp.where(active, queues.cq_rows, -1).astype(jnp.int32)

    veto = None
    if vcells_q is not None:
        # reclaim-oracle victim check (preemption_oracle.go emulation):
        # a flavor-resource cell carrying an ELIGIBLE same-CQ victim
        # cannot be upgraded to RECLAIM. Scattered once per cycle into
        # a dense [Q, FR] mask, then gathered per podset below.
        vq3 = vcells_q.shape
        qq3 = jnp.broadcast_to(jnp.arange(q)[:, None, None], vq3)
        veto = (
            jnp.zeros((q, n_fr + 1), dtype=bool)
            .at[qq3, jnp.where(vcells_q >= 0, vcells_q, n_fr)]
            .max(elig_v[:, :, None] & (vcells_q >= 0))[:, :n_fr]
        )

    accum = jnp.zeros((q, n_fr), dtype=jnp.int64)
    processed = jnp.ones(q, dtype=bool)
    head_mode = jnp.full(q, 3, dtype=jnp.int32)
    head_borrow = jnp.zeros(q, dtype=bool)
    pending = jnp.zeros(q, dtype=bool)
    rep_list, nstart_list, cells_list, qty_list, need_list = [], [], [], [], []
    npod = queues.n_podsets[q_idx, cur]  # [Q]

    for p in range(pmax):
        real = active & (p < npod)
        cells_p = queues.cells[q_idx, cur, p]  # [Q,K,C]
        qty_p = queues.qty[q_idx, cur, p]
        if p == 0:
            infl = qty_p  # nothing accumulated yet (static fast path)
        else:
            accum_at = accum[q_idx[:, None, None], jnp.maximum(cells_p, 0)]
            infl = qty_p + jnp.where(
                (cells_p >= 0) & (qty_p > 0), accum_at, 0
            )
        fit_cells, pot_cells, reclaim_cells, borrow_cells, cell_need = (
            cell_masks(
                tree, subtree, guaranteed, local, head_cq, cells_p, infl,
                usage=usage0, avail=avail0, potential=potential, pwb=pwb,
            )
        )
        if veto is not None:
            victim_on_cell = veto[
                q_idx[:, None, None], jnp.maximum(cells_p, 0)
            ] & (cells_p >= 0)
            reclaim_cells = reclaim_cells & ~victim_on_cell
        gid_p = queues.gidx[q_idx, cur, p]
        gl_p = queues.glast[q_idx, cur, p]
        cg_p = queues.cgrp[q_idx, cur, p]
        gmask_p = cg_p[..., None] == jnp.arange(g)[None, None, None, :]
        k_mask_p = jnp.all(gid_p >= g_start[:, p][:, None, :], axis=-1)
        valid_p = queues.valid[q_idx, cur, p] & real[:, None] & k_mask_p
        score_p = (
            queues.score[q_idx, cur, p] if queues.score is not None else None
        )
        chosen_p, pre_p, pending_p, nstart_p = _group_walk(
            gid_p, gl_p, gmask_p, valid_p, fit_cells, pot_cells,
            reclaim_cells, borrow_cells, queues.ffb, queues.ffp,
            score=score_p,
        )
        live = real & processed
        mode_p = jnp.where(
            chosen_p >= 0, 3, jnp.where(pre_p >= 0, 1, 0)
        )
        mode_p = jnp.where(live, mode_p, 3)  # pads/unprocessed inert
        rep_p = jnp.where(chosen_p >= 0, chosen_p, pre_p)
        use_p = live & (rep_p >= 0)
        rep_safe = jnp.maximum(rep_p, 0)
        cells_rep = jnp.take_along_axis(
            cells_p, rep_safe[:, None, None], axis=1
        )[:, 0]  # [Q,C]
        qty_rep = jnp.take_along_axis(
            qty_p, rep_safe[:, None, None], axis=1
        )[:, 0]
        cells_rep = jnp.where(use_p[:, None] & (cells_rep >= 0), cells_rep, -1)
        qty_rep = jnp.where(cells_rep >= 0, qty_rep, 0)
        # cells of this podset's choice that did NOT fit at cycle-start
        # usage = its frs_need_preemption contribution (the host reads
        # choice.mode == Preempt per resource; cellmode < 3 is the same
        # predicate at the representative candidate)
        fit_rep = jnp.take_along_axis(
            fit_cells, rep_safe[:, None, None], axis=1
        )[:, 0]  # [Q,C]
        need_rep = (cells_rep >= 0) & (qty_rep > 0) & ~fit_rep
        if p < pmax - 1:
            # assignment_usage grows for fit AND preempt choices alike
            # (skipped after the last podset: nobody reads it)
            accum = accum.at[
                q_idx[:, None], jnp.maximum(cells_rep, 0)
            ].add(jnp.where(cells_rep >= 0, qty_rep, 0))
        borrow_rep = jnp.any(
            jnp.take_along_axis(
                borrow_cells, rep_safe[:, None, None], axis=1
            )[:, 0]
            & (cells_rep >= 0),
            axis=1,
        )
        head_borrow = head_borrow | (borrow_rep & use_p)
        pending = pending | (pending_p & live)
        head_mode = jnp.minimum(head_mode, mode_p)
        processed = processed & (mode_p >= 1)
        rep_list.append(jnp.where(use_p, rep_p, -1))
        nstart_list.append(jnp.where(live[:, None], nstart_p, 0))
        cells_list.append(cells_rep)
        qty_list.append(qty_rep)
        need_list.append(need_rep)

    rep_k = jnp.stack(rep_list, axis=1)  # [Q,P]
    next_start = jnp.stack(nstart_list, axis=1)  # [Q,P,G]
    mcells = jnp.concatenate(cells_list, axis=1)  # [Q,P*C]
    mqty = jnp.concatenate(qty_list, axis=1)
    mneed = jnp.concatenate(need_list, axis=1)
    if pmax > 1:
        # merge duplicate frs: sum onto the first occurrence, zero the
        # rest (the host fits()/reserve vectors are per-fr sums); a
        # single candidate's cells are distinct frs by construction, so
        # P=1 skips this entirely
        pc = pmax * c
        pos = jnp.arange(pc)
        same = (mcells[:, None, :] == mcells[:, :, None]) & (mcells >= 0)[:, None, :]
        summed = jnp.sum(jnp.where(same, mqty[:, None, :], 0), axis=2)
        first = ~jnp.any(
            same & (pos[None, None, :] < pos[None, :, None]), axis=2
        )
        # frs_need is a SET union across podsets: any podset's preempt-
        # mode choice at the fr marks the merged cell
        mneed = jnp.any(same & mneed[:, None, :], axis=2) & first
        mqty = jnp.where(first & (mcells >= 0), summed, 0)
        mcells = jnp.where(first, mcells, -1)

    is_fit = active & (head_mode == 3)
    is_pre = active & (head_mode >= 1) & (head_mode < 3)
    pend = pending & is_pre  # NoFit nominations clear the cursor
    return (is_fit, is_pre, pend, head_borrow, rep_k, next_start,
            mcells, mqty, mneed)


def _cursor_queue_motion(
    queues, q_idx, cur, active, is_fit, pend, admitted, rep_k, walk_next,
    retries, stuck, no_prog, adm_k, adm_cycle, g_start, cursor, cycle,
):
    """Cursor-based end-of-cycle queue motion, shared by solve_drain
    and solve_drain_fair.

    Admitted heads leave; non-Fit heads park (advance) unless a podset
    walk stored a pending flavor cursor (PendingFlavors); in-cycle
    conflict losers stay, resuming every podset from its stored
    per-group cursors. Non-converging PendingFlavors loops: the
    reference's immediate-requeue can oscillate forever when
    podset/group cursors alternately advance and reset — the live
    scheduler spins until cluster events change the state, but a drain
    has no events. A queue whose head retried more times than its joint
    cursor odometer has states (queues.retry_cap — no convergent walk
    can need more) is provably cycling and is marked STUCK: its head
    keeps re-nominating with a frozen cursor every remaining cycle — so
    its per-cycle capacity reservations keep shaping other queues'
    decisions exactly like the host's spin — but the queue stops
    counting toward termination and its undecided entries are reported
    as fallback (no decision). A stuck head whose frozen nomination
    later RESOLVES un-sticks. Global stagnation guard: with no queue
    advancing for 2x the retry budget the per-cycle state is provably
    cyclic, so every remaining non-advancing queue is marked stuck."""
    over_budget = retries >= queues.retry_cap
    stuck = stuck | (active & (~is_fit) & pend & over_budget)
    resolve = active & (admitted | ((~is_fit) & ~pend))
    stuck = stuck & ~resolve
    retrying = active & (~is_fit) & pend & ~stuck
    advance = resolve
    retries = jnp.where(
        advance | ~active, 0, jnp.where(retrying, retries + 1, retries)
    )
    any_advance = jnp.any(advance)
    no_prog = jnp.where(any_advance, 0, no_prog + 1)
    stuck = stuck | (
        (no_prog >= 2 * jnp.max(queues.retry_cap)) & active & ~advance
    )
    adm_k = adm_k.at[q_idx, cur].set(
        jnp.where((admitted & active)[:, None], rep_k, adm_k[q_idx, cur])
    )
    adm_cycle = adm_cycle.at[q_idx, cur].set(
        jnp.where(admitted & active, cycle, adm_cycle[q_idx, cur])
    )
    lost = active & is_fit & (~admitted)
    # a queue's next head starts its walk at -1 (masks like 0): the
    # host tells a head the drain never nominated (keeps its stored
    # LastAssignment) from one whose walk restarted at 0
    g_start = jnp.where(
        advance[:, None, None],
        -1,
        jnp.where((lost | retrying)[:, None, None], walk_next, g_start),
    ).astype(jnp.int32)
    cursor = cursor + advance.astype(jnp.int32)
    return cursor, g_start, retries, stuck, no_prog, adm_k, adm_cycle


def _plain_cycle(
    tree,
    subtree,
    guaranteed,
    potential,
    queues: DrainQueues,
    paths,
    n_segments: int,
    n_steps: int,
    state,
    alive=None,
):
    """ONE plain drain cycle over the 9-tuple loop state — the body of
    ``solve_drain``'s while_loop, extracted so the megaloop kernel
    (ops/megaloop_kernel.py) can run the identical cycle inside its
    fused multi-round loop. ``alive`` masks out queues a megaloop round
    boundary retired (a serial re-plan would not include them). The
    state's cycle slot doubles as the admission stamp, so the megaloop
    passes its IN-ROUND cycle there — matching what a per-round serial
    launch records — and keeps its own total-cycle counter outside."""
    max_depth = tree.max_depth
    q, l, pmax, k, c = queues.cells.shape
    q_idx = jnp.arange(q)

    avail_v = jax.vmap(
        _avail_along_path, in_axes=(0, 0, None, None, None, None, None)
    )

    (local, cursor, g_start, retries, stuck, no_prog, adm_k,
     adm_cycle, cycle) = state

    active = cursor < queues.qlen  # [Q]
    if alive is not None:
        active = active & alive
    cur = jnp.minimum(cursor, l - 1)
    usage0 = usage_tree(tree, guaranteed, local)
    (is_fit, is_pre, pend, head_borrow, rep_k, walk_next,
     cells_eff, qty_eff, _mneed) = _nominate_multi(
        tree, subtree, guaranteed, local, usage0, queues, q_idx, cur,
        active, g_start, potential,
    )
    nofit = ~(is_fit | is_pre)

    prio = queues.priority[q_idx, cur]
    ts = queues.timestamp[q_idx, cur]
    order = jnp.lexsort(
        (
            ts,
            -prio,
            head_borrow.astype(jnp.int64),
            nofit.astype(jnp.int64),
        )
    )
    seg = jnp.maximum(queues.seg_id, 0)[order]
    valid_sorted = active[order] & (queues.seg_id[order] >= 0) & (~nofit[order])
    rank = segmented_rank(seg, valid_sorted)
    rank_scatter = jnp.where(valid_sorted, rank, n_steps)
    mat = (
        jnp.full((n_steps, n_segments), -1, dtype=jnp.int32)
        .at[rank_scatter, seg]
        .set(order.astype(jnp.int32), mode="drop")
    )

    cq = jnp.maximum(queues.cq_rows, 0)

    def step(usage, s):
        idx = mat[s]  # [G]
        act = idx >= 0
        hidx = jnp.maximum(idx, 0)
        cqs = cq[hidx]
        path = paths[cqs]
        cells_ = cells_eff[hidx]
        qty_ = qty_eff[hidx]
        ccells = jnp.maximum(cells_, 0)
        cell_valid = (cells_ >= 0) & (qty_ > 0) & act[:, None]

        avail = avail_v(
            path, cells_, usage, subtree, guaranteed,
            tree.borrowing_limit, max_depth,
        )
        fits = jnp.all(jnp.where(cell_valid, avail >= qty_, True), axis=1)
        admit = act & is_fit[hidx] & fits
        reserve = act & is_pre[hidx] & queues.no_reclaim[hidx]
        nominal_c = tree.nominal[cqs[:, None], ccells]
        bl_c = tree.borrowing_limit[cqs[:, None], ccells]
        leaf_usage_c = usage[cqs[:, None], ccells]
        borrow_cap = jnp.where(
            bl_c < NO_LIMIT,
            jnp.minimum(qty_, nominal_c + bl_c - leaf_usage_c),
            qty_,
        )
        nominal_cap = jnp.maximum(
            0, jnp.minimum(qty_, nominal_c - leaf_usage_c)
        )
        reserve_qty = jnp.where(
            head_borrow[hidx][:, None], borrow_cap, nominal_cap
        )
        delta = jnp.where(
            cell_valid & admit[:, None],
            qty_,
            jnp.where(cell_valid & reserve[:, None], reserve_qty, 0),
        )
        for d in range(0, max_depth + 1):
            node = jnp.maximum(path[:, d], 0)
            node_valid = (path[:, d] >= 0)[:, None]
            old = usage[node[:, None], ccells]
            gg = guaranteed[node[:, None], ccells]
            new = old + delta
            usage = usage.at[node[:, None], ccells].add(
                jnp.where(node_valid, delta, 0)
            )
            over_old = jnp.maximum(0, old - gg)
            over_new = jnp.maximum(0, new - gg)
            delta = jnp.where(node_valid, over_new - over_old, delta)
        return usage, admit

    _, admit_sn = lax.scan(step, usage0, jnp.arange(n_steps))

    flat_idx = mat.reshape(-1)
    safe_idx = jnp.where(flat_idx >= 0, flat_idx, q)
    admitted = (
        jnp.zeros(q, dtype=bool)
        .at[safe_idx]
        .set(admit_sn.reshape(-1), mode="drop")
    )

    # leaf usage adds for admissions only — the cycle's reservations
    # die with the cycle (the reserving head parks), and rebuilding
    # the interior rows from leaves next cycle makes that exact
    cell_valid = (cells_eff >= 0) & (qty_eff > 0)
    add = jnp.where(cell_valid & admitted[:, None], qty_eff, 0)
    local = local.at[cq[:, None], jnp.maximum(cells_eff, 0)].add(add)

    (cursor, g_start, retries, stuck, no_prog, adm_k, adm_cycle) = (
        _cursor_queue_motion(
            queues, q_idx, cur, active, is_fit, pend, admitted,
            rep_k, walk_next, retries, stuck, no_prog, adm_k,
            adm_cycle, g_start, cursor, cycle,
        )
    )
    return (local, cursor, g_start, retries, stuck, no_prog, adm_k,
            adm_cycle, cycle + 1)


def first_walks(queues: DrainQueues) -> jnp.ndarray:
    """int32[Q,P,G] the walk each queue's first head starts from."""
    if queues.g_start0 is not None:
        return queues.g_start0.astype(jnp.int32)
    q, _, pmax = queues.cells.shape[:3]
    return jnp.zeros((q, pmax, queues.gidx.shape[-1]), dtype=jnp.int32)


def solve_drain(
    tree: QuotaTree,
    local_usage: jnp.ndarray,  # int64[N, FR] starting leaf usage
    queues: DrainQueues,
    paths: jnp.ndarray,  # int32[N, D+1]
    n_segments: int,
    n_steps: int,
    max_cycles: int,
) -> DrainResult:
    subtree, guaranteed = subtree_quota(tree)
    from kueue_tpu.ops.assign_kernel import potential_available_all

    potential = potential_available_all(tree, subtree, guaranteed)

    q, l, pmax, k, c = queues.cells.shape

    def cycle_body(state):
        return _plain_cycle(
            tree, subtree, guaranteed, potential, queues, paths,
            n_segments, n_steps, state,
        )

    def cond(state):
        _, cursor, _, _, stuck, _, _, _, cycle = state
        return jnp.any((cursor < queues.qlen) & ~stuck) & (cycle < max_cycles)

    init = (
        local_usage,
        jnp.zeros(q, dtype=jnp.int32),
        first_walks(queues),
        jnp.zeros(q, dtype=jnp.int32),
        jnp.zeros(q, dtype=bool),
        jnp.int32(0),
        jnp.full((q, l, pmax), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.int32(0),
    )
    (local_f, cursor_f, g_start_f, _, stuck_f, _, adm_k, adm_cycle,
     cycles) = lax.while_loop(cond, cycle_body, init)
    return DrainResult(
        admitted_k=adm_k,
        admitted_cycle=adm_cycle,
        cursor=cursor_f,
        cycles=cycles,
        local_usage=local_f,
        stuck=stuck_f,
        g_start=g_start_f,
    )


class TASHeads(NamedTuple):
    """Per-queue TAS lowering for solve_drain_tas over a MERGED domain
    forest: every in-scope TAS flavor's topology concatenated into one
    disjoint forest, aligned at the LEAF level (a flavor with fewer
    levels gets structural dummy top levels so seg_ids/parent chains
    stay rectangular; dummies are unreachable — ``t_top`` clamps the
    preferred-mode relax-up at each flavor's real top).

    t_is:    bool[Q]         — the queue's entries are TAS workloads.
    t_req:   int64[Q, L, Rt] — per-ENTRY per-pod request vector on the
             UNION topology resource axis (pods slot included as 1).
    t_count: int32[Q, L]     — gang size per entry.
    t_level: int32[Q, L]     — requested topology level index in GLOBAL
             (merged) level space; leaf level for unconstrained mode.
    t_mode:  int32[Q, L]     — 0 Required, 1 Preferred, 2 Unconstrained
             (tas_flavor_snapshot.go:513-568 search modes).
    t_top:   int32[Q]        — the queue's flavor's top level in global
             space (= D_global - D_flavor); relax-up stops here.
    t_flavor: int32[Q]       — the queue's flavor index.
    leaf_flavor: int32[Lf]   — owning flavor per merged-forest leaf
             (placement masks every other flavor's leaves to state 0).
    parent_map: int32[D_t, ND] — domain -> parent domain index at the
             level above (row 0 unused, zero; ordering owned by the
             merged-forest lowering), ND = max domains/level.
    """

    t_is: jnp.ndarray
    t_req: jnp.ndarray  # int64[Q, L, Rt]
    t_count: jnp.ndarray  # int32[Q, L]
    t_level: jnp.ndarray  # int32[Q, L]
    t_mode: jnp.ndarray  # int32[Q, L]
    t_top: jnp.ndarray  # int32[Q]
    t_flavor: jnp.ndarray  # int32[Q]
    leaf_flavor: jnp.ndarray  # int32[Lf]
    parent_map: jnp.ndarray  # int32[D_t, ND]
    # bool[Q, L] — entry requests a topology on a ClusterQueue whose
    # flavor doesn't support TAS: the host rejects the flavor and PARKS
    # the head ("does not support TopologyAwareScheduling",
    # tas/manager.py check); forcing NoFit reproduces that park at the
    # exact same cycle instead of dropping the whole queue to fallback
    t_bad: jnp.ndarray


def _tas_fit_and_place(
    topo_free,  # int64[Lf, Rt]
    tas_u,  # int64[Lf, Rt] current TAS usage
    seg_ids,  # int32[D_t, Lf]
    n_domains,  # static tuple per level
    parent_map,  # int32[D_t, ND]
    req,  # int64[Rt] per-pod request
    count,  # int32 gang size
    level,  # int32 requested level index (global level space)
    place: bool,
    mode=None,  # int32: 0 Required, 1 Preferred, 2 Unconstrained
    top_level=None,  # int32: the flavor's real top level (relax floor)
    leaf_sel=None,  # bool[Lf]: the flavor's leaves in the merged forest
):
    """Phase-1 counts + the reference's phase-2 greedy (BestFit default
    profile) for ONE podset against the current TAS state
    (tas_flavor_snapshot.go:394-444,494-621), all three search modes:

    - Required: the requested level must hold ONE fitting domain;
    - Preferred: relax upward (level-1, ..., the flavor's top) looking
      for a single fit, then fall back to a multi-domain greedy take at
      the top level (:443-465);
    - Unconstrained: single fit at the lowest level, else the
      multi-domain take AT that level (no upward relaxation).

    Returns (fits bool, taken int64[Lf]) — ``taken`` is all-zero unless
    ``place`` and the request fits. ``mode``/``top_level`` default to
    Required at level with no floor; ``leaf_sel`` masks the counts to
    the entry's own flavor in a merged multi-flavor forest."""
    n_lf = topo_free.shape[0]
    d_t = len(n_domains)
    nd_max = parent_map.shape[1]
    INF = jnp.int64(1 << 62)
    if mode is None:
        mode = jnp.int32(0)
    if top_level is None:
        top_level = jnp.int32(0)

    remaining = topo_free - tas_u
    per_res = jnp.sign(remaining) * (
        jnp.abs(remaining) // jnp.maximum(req[None, :], 1)
    )
    per_res = jnp.where((req > 0)[None, :], per_res, MAX_COUNT_TAS)
    counts = jnp.clip(jnp.min(per_res, axis=-1), None, MAX_COUNT_TAS)
    counts = jnp.maximum(counts, jnp.int64(-(1 << 40)))  # keep sums sane
    if leaf_sel is not None:
        # other flavors' leaves are invisible: their domains total 0
        # and can never be picked (gang counts are >= 1)
        counts = jnp.where(leaf_sel, counts, 0)

    # per-level domain totals, padded to ND
    states = []
    for d in range(d_t):
        s = jax.ops.segment_sum(
            counts, seg_ids[d], num_segments=n_domains[d]
        )
        s = jnp.pad(s, (0, nd_max - n_domains[d]), constant_values=-1)
        states.append(s)

    cnt = count.astype(jnp.int64)

    def pick_single(s, valid):
        """BestFit: the domain with the smallest state >= count
        (first in (-state, values) order among equal states)."""
        fit = valid & (s >= cnt)
        mval = jnp.min(jnp.where(fit, s, INF))
        idx = jnp.argmax(fit & (s == mval))
        return jnp.any(fit), idx.astype(jnp.int32)

    alloc = jnp.zeros((d_t, nd_max), dtype=jnp.int64)
    fits_lvl = []
    pick_lvl = []
    total_lvl = []
    for d in range(d_t):
        valid = jnp.arange(nd_max) < n_domains[d]
        ok, idx = pick_single(states[d], valid)
        fits_lvl.append(ok)
        pick_lvl.append(idx)
        # the multi-domain take walks positive-state domains only
        # (:453); its capacity is their sum
        total_lvl.append(
            jnp.sum(jnp.where(valid, jnp.maximum(states[d], 0), 0))
        )
    ok_vec = jnp.stack(fits_lvl)  # [D]
    idx_vec = jnp.stack(pick_lvl)  # [D]
    total_vec = jnp.stack(total_lvl)  # [D]
    lvl_idx = jnp.arange(d_t)

    ok_at_l = jnp.take(ok_vec, jnp.clip(level, 0, d_t - 1))
    total_at_l = jnp.take(total_vec, jnp.clip(level, 0, d_t - 1))
    total_at_top = jnp.take(total_vec, jnp.clip(top_level, 0, d_t - 1))
    # preferred: FIRST single fit walking up from the requested level
    # (:446-448) = the deepest fitting level in [top_level, level]
    in_range = (lvl_idx <= level) & (lvl_idx >= top_level)
    pref_ok = ok_vec & in_range
    pref_found = jnp.any(pref_ok)
    pref_level = jnp.max(jnp.where(pref_ok, lvl_idx, -1)).astype(jnp.int32)

    is_pref = mode == 1
    is_unc = mode == 2
    fits = jnp.where(
        is_pref,
        pref_found | (total_at_top >= cnt),
        jnp.where(is_unc, ok_at_l | (total_at_l >= cnt), ok_at_l),
    )
    multi = jnp.where(
        is_pref, ~pref_found, jnp.where(is_unc, ~ok_at_l, False)
    )
    fit_level = jnp.where(
        is_pref,
        jnp.where(pref_found, pref_level, top_level),
        level,
    ).astype(jnp.int32)

    if not place:
        return fits, jnp.zeros(n_lf, dtype=jnp.int64)

    def split(s, child_ok):
        """Greedy desc-order fill of ``cnt`` over the masked domains
        with the BestFit jump (tas_flavor_snapshot.go:468-511).

        The prefix sum runs in int32 on values clamped to ``cnt``: the
        positions at/before the covering domain all have state <
        remaining <= cnt, so clamping changes nothing there, and later
        positions are never read (argmax takes the FIRST covered). An
        s64 cumsum lowers to a u32-pair variadic reduce-window on TPU
        whose scoped-vmem footprint blows the 16M limit at wide domain
        axes (observed at [100, 1024]); i32 halves it. Exact given the
        lowering's count/domain caps (MAX_TAS_COUNT x MAX_TAS_DOMAINS
        < 2^31)."""
        sm = jnp.where(child_ok, s, jnp.int64(-1))
        order = jnp.lexsort((jnp.arange(nd_max), -sm))
        ss = sm[order]
        ss_c = jnp.minimum(jnp.maximum(ss, 0), cnt).astype(jnp.int32)
        prefix = (jnp.cumsum(ss_c) - ss_c).astype(jnp.int64)
        remaining = cnt - prefix
        # the host walk never evaluates a position with remaining <= 0
        # (the covering take returns first), so pads/zero-state domains
        # can never be picked
        covered = (remaining > 0) & (ss >= remaining)
        k = jnp.argmax(covered)
        rem_k = jnp.maximum(remaining[k], 0)
        fitmask = (jnp.arange(nd_max) >= k) & (ss >= rem_k) & (rem_k > 0)
        mval = jnp.min(jnp.where(fitmask, ss, INF))
        jstar = jnp.argmax(fitmask & (ss == mval))
        take = jnp.where(jnp.arange(nd_max) < k, jnp.maximum(ss, 0), 0)
        take = take.at[jstar].set(rem_k)
        # scatter back to value order
        out = jnp.zeros(nd_max, dtype=jnp.int64).at[order].set(take)
        return jnp.where(child_ok, out, 0)

    # seed the allocation at the fit level — one best-fit domain capped
    # at count, or the multi-domain greedy take (:450-465) — then
    # descend with the pooled greedy split (update_counts_to_minimum,
    # BestFit jumps)
    for d in range(d_t):
        valid = jnp.arange(nd_max) < n_domains[d]
        single_seed = (
            jnp.zeros(nd_max, dtype=jnp.int64)
            .at[idx_vec[d]]
            .set(jnp.where(fits & ~multi, cnt, 0))
        )
        seed = jnp.where(
            multi & fits, split(states[d], valid), single_seed
        )
        alloc = alloc.at[d].set(jnp.where(fit_level == d, seed, alloc[d]))

    for d in range(1, d_t):
        # children (at level d) of domains picked at level d-1
        pm = jnp.maximum(parent_map[d], 0)
        picked_above = alloc[d - 1][pm] > 0
        child_ok = picked_above & (jnp.arange(nd_max) < n_domains[d])
        lower = jnp.where(
            (fit_level < d) & fits, split(states[d], child_ok), alloc[d]
        )
        alloc = alloc.at[d].set(lower)

    # leaf-level taken counts
    leaf_alloc = alloc[d_t - 1]
    taken = leaf_alloc[seg_ids[d_t - 1]]  # [Lf] via leaf->domain id
    # a leaf-level domain maps 1:1 onto leaves in this lowering, but
    # gather defensively through seg_ids anyway
    taken = jnp.where(fits, taken, 0)
    return fits, taken


MAX_COUNT_TAS = (1 << 31) - 1


class TASDrainResult(NamedTuple):
    """DrainResult plus TAS outputs: adm_step int32[Q,L] (intra-cycle
    admission sequence — the host replay orders placements by
    (admitted_cycle, adm_step)); tas_usage int64[Lf,Rt] final TAS leaf
    usage (the host replay asserts it reproduces this exactly)."""

    admitted_k: jnp.ndarray
    admitted_cycle: jnp.ndarray
    adm_step: jnp.ndarray
    cursor: jnp.ndarray
    cycles: jnp.ndarray
    local_usage: jnp.ndarray
    tas_usage: jnp.ndarray
    stuck: jnp.ndarray


def solve_drain_tas(
    tree: QuotaTree,
    local_usage: jnp.ndarray,  # int64[N, FR]
    queues: DrainQueues,
    paths: jnp.ndarray,  # int32[N, D+1]
    topo_free: jnp.ndarray,  # int64[Lf, Rt]
    tas_usage0: jnp.ndarray,  # int64[Lf, Rt]
    seg_ids: jnp.ndarray,  # int32[D_t, Lf]
    theads: TASHeads,
    n_domains,  # static tuple
    n_steps: int,  # TOTAL sequential steps per cycle (global order)
    max_cycles: int,
) -> TASDrainResult:
    """Multi-cycle drain with Topology-Aware Scheduling heads decided
    IN KERNEL. A shared topology couples every ClusterQueue using the
    flavor — across cohorts — so phase 2 is one GLOBAL sequential scan
    in the scheduler's entry order (the reference admits sequentially
    too; cross-cohort TAS contention resolves by that order), not the
    per-root-cohort parallel scan of solve_drain. Per cycle:

    - nomination: the normal quota walk, then each quota-Fit TAS head
      checks placement feasibility against CYCLE-START TAS state (the
      host's Assignment.WorkloadsTopologyRequests degrade-to-NoFit,
      tas_flavorassigner.go:31-50): infeasible heads park;
    - phase 2: one head per step in global (borrowing, priority, FIFO)
      order; TAS heads re-fit AND place against the LIVE TAS state
      (the admit-time re-validation) with the reference's phase-2
      greedy — REQUIRED mode, BestFit profile
      (tas_flavor_snapshot.go:394-444,494-621) — and charge the
      assigned leaves immediately; losers stay pending and re-park
      next cycle once nomination sees the new state.

    Scope (host lowering enforces): single-podset Required-mode heads
    on one shared taint-free topology, no preemption, default TAS
    profile. The host replays admitted placements in (cycle, step)
    order to reconstruct TopologyAssignments and asserts the final
    leaf usage matches ``tas_usage``.
    """
    max_depth = tree.max_depth
    subtree, guaranteed = subtree_quota(tree)
    from kueue_tpu.ops.assign_kernel import potential_available_all

    potential = potential_available_all(tree, subtree, guaranteed)

    q, l, pmax, k, c = queues.cells.shape
    q_idx = jnp.arange(q)
    cq = jnp.maximum(queues.cq_rows, 0)

    # per-queue flavor leaf mask over the merged forest
    leaf_sel_q = (
        theads.leaf_flavor[None, :] == theads.t_flavor[:, None]
    )  # [Q, Lf]
    tas_place_v = jax.vmap(
        lambda req, count, level, mode, top, lsel, tas_u: (
            _tas_fit_and_place(
                topo_free, tas_u, seg_ids, n_domains, theads.parent_map,
                req, count, level, place=True, mode=mode, top_level=top,
                leaf_sel=lsel,
            )
        ),
        in_axes=(0, 0, 0, 0, 0, 0, None),
    )

    def cycle_body(state):
        (local, tas_u, cursor, g_start, retries, stuck, no_prog, adm_k,
         adm_cycle, adm_step, cycle) = state

        active = cursor < queues.qlen  # [Q]
        cur = jnp.minimum(cursor, l - 1)
        usage0 = usage_tree(tree, guaranteed, local)
        (is_fit, is_pre, pend, head_borrow, rep_k, walk_next,
         cells_eff, qty_eff, _mneed) = _nominate_multi(
            tree, subtree, guaranteed, local, usage0, queues, q_idx, cur,
            active, g_start, potential,
        )
        # TAS placement at NOMINATION against cycle-start TAS state
        # (Assignment.WorkloadsTopologyRequests); the admit-time check
        # below only re-validates THESE assigned leaves — the host does
        # not re-place in-cycle (tas/manager.py fits())
        t_req = theads.t_req[q_idx, cur]  # [Q, Rt]
        t_count = theads.t_count[q_idx, cur]
        t_level = theads.t_level[q_idx, cur]
        t_mode = theads.t_mode[q_idx, cur]
        tas_head = theads.t_is & active
        tas_nom_ok, taken0 = tas_place_v(
            t_req, t_count, t_level, t_mode, theads.t_top, leaf_sel_q,
            tas_u,
        )
        tas_parked = tas_head & is_fit & ~tas_nom_ok
        # topology request on a non-TAS flavor: the host rejects the
        # flavor at nomination and parks the head
        t_bad_h = theads.t_bad[q_idx, cur]
        tas_parked = tas_parked | (t_bad_h & active)
        is_fit = is_fit & ~tas_parked
        is_pre = is_pre & ~(t_bad_h & active)
        pend = pend & ~tas_parked  # degrade-to-NoFit clears the cursor
        pend = pend & ~(t_bad_h & active)
        nofit = ~(is_fit | is_pre)

        prio = queues.priority[q_idx, cur]
        ts = queues.timestamp[q_idx, cur]
        order = jnp.lexsort(
            (
                ts,
                -prio,
                head_borrow.astype(jnp.int64),
                nofit.astype(jnp.int64),
            )
        )
        valid_sorted = active[order] & (queues.cq_rows[order] >= 0) & (~nofit[order])
        rank = jnp.cumsum(valid_sorted.astype(jnp.int32)) - 1
        dest = jnp.where(valid_sorted & (rank < n_steps), rank, n_steps)
        mat1 = (
            jnp.full(n_steps + 1, -1, dtype=jnp.int32)
            .at[dest]
            .set(order.astype(jnp.int32))[:n_steps]
        )

        cell_valid_all = (cells_eff >= 0) & (qty_eff > 0)
        cells_c = jnp.maximum(cells_eff, 0)

        def step(carry, s):
            usage, tas_u_s = carry
            hq = mat1[s]
            act = hq >= 0
            hh = jnp.maximum(hq, 0)
            path = paths[cq[hh]]
            cells_ = cells_eff[hh]
            qty_ = qty_eff[hh]
            ccells = jnp.maximum(cells_, 0)
            cell_valid = cell_valid_all[hh] & act

            avail = _avail_along_path(
                path, cells_, usage, subtree, guaranteed,
                tree.borrowing_limit, max_depth,
            )
            fits_q = jnp.all(jnp.where(cell_valid, avail >= qty_, True))
            # admit-time TAS re-validation: every NOMINATED leaf must
            # still hold its assigned count against LIVE usage
            taken_h = taken0[hh]  # [Lf]
            rem = topo_free - tas_u_s
            per_res = jnp.sign(rem) * (
                jnp.abs(rem) // jnp.maximum(t_req[hh][None, :], 1)
            )
            per_res = jnp.where(
                (t_req[hh] > 0)[None, :], per_res, MAX_COUNT_TAS
            )
            counts_now = jnp.min(per_res, axis=-1)
            t_ok = jnp.all((taken_h == 0) | (counts_now >= taken_h))
            tas_gate = jnp.where(tas_head[hh], t_ok, True)
            admit = act & is_fit[hh] & fits_q & tas_gate
            # charge the nominated leaves for admitted TAS heads
            tas_u_s = tas_u_s + jnp.where(
                admit & tas_head[hh],
                t_req[hh][None, :] * taken_h[:, None],
                0,
            )
            reserve = act & is_pre[hh] & queues.no_reclaim[hh]
            nominal_c = tree.nominal[cq[hh], ccells]
            bl_c = tree.borrowing_limit[cq[hh], ccells]
            leaf_usage_c = usage[cq[hh], ccells]
            borrow_cap = jnp.where(
                bl_c < NO_LIMIT,
                jnp.minimum(qty_, nominal_c + bl_c - leaf_usage_c),
                qty_,
            )
            nominal_cap = jnp.maximum(
                0, jnp.minimum(qty_, nominal_c - leaf_usage_c)
            )
            reserve_qty = jnp.where(head_borrow[hh], borrow_cap, nominal_cap)
            delta = jnp.where(
                cell_valid & admit,
                qty_,
                jnp.where(cell_valid & reserve, reserve_qty, 0),
            )
            for d in range(0, max_depth + 1):
                node = jnp.maximum(path[d], 0)
                node_valid = path[d] >= 0
                old = usage[node, ccells]
                gg = guaranteed[node, ccells]
                new = old + delta
                usage = usage.at[node, ccells].add(
                    jnp.where(node_valid, delta, 0)
                )
                delta = jnp.where(
                    node_valid,
                    jnp.maximum(0, new - gg) - jnp.maximum(0, old - gg),
                    delta,
                )
            return (usage, tas_u_s), admit

        (_, tas_u), admit_sn = lax.scan(
            step, (usage0, tas_u), jnp.arange(n_steps, dtype=jnp.int32)
        )
        safe_idx = jnp.where(mat1 >= 0, mat1, q)
        admitted = (
            jnp.zeros(q, dtype=bool)
            .at[safe_idx]
            .set(admit_sn, mode="drop")
        )
        step_of = (
            jnp.full(q + 1, -1, dtype=jnp.int32)
            .at[safe_idx]
            .set(
                jnp.where(admit_sn, jnp.arange(n_steps, dtype=jnp.int32), -1),
                mode="drop",
            )[:q]
        )

        add = jnp.where(cell_valid_all & admitted[:, None], qty_eff, 0)
        local = local.at[cq[:, None], cells_c].add(add)
        adm_step = adm_step.at[q_idx, cur].set(
            jnp.where(admitted & active, step_of, adm_step[q_idx, cur])
        )
        (cursor, g_start, retries, stuck, no_prog, adm_k, adm_cycle) = (
            _cursor_queue_motion(
                queues, q_idx, cur, active, is_fit, pend, admitted,
                rep_k, walk_next, retries, stuck, no_prog, adm_k,
                adm_cycle, g_start, cursor, cycle,
            )
        )
        return (local, tas_u, cursor, g_start, retries, stuck, no_prog,
                adm_k, adm_cycle, adm_step, cycle + 1)

    def cond(state):
        cursor = state[2]
        stuck = state[5]
        cycle = state[10]
        return jnp.any((cursor < queues.qlen) & ~stuck) & (cycle < max_cycles)

    init = (
        local_usage,
        tas_usage0,
        jnp.zeros(q, dtype=jnp.int32),
        first_walks(queues),
        jnp.zeros(q, dtype=jnp.int32),
        jnp.zeros(q, dtype=bool),
        jnp.int32(0),
        jnp.full((q, l, pmax), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.int32(0),
    )
    (local_f, tas_f, cursor_f, _, _, stuck_f, _, adm_k, adm_cycle,
     adm_step, cycles) = lax.while_loop(cond, cycle_body, init)
    return TASDrainResult(
        admitted_k=adm_k,
        admitted_cycle=adm_cycle,
        adm_step=adm_step,
        cursor=cursor_f,
        cycles=cycles,
        local_usage=local_f,
        tas_usage=tas_f,
        stuck=stuck_f,
    )


def _solve_drain_tas_packed(
    tree, local_usage, queues, paths, topo_free, tas_usage0, seg_ids,
    theads, n_domains, n_steps: int, max_cycles: int,
):
    r = solve_drain_tas(
        tree, local_usage, queues, paths, topo_free, tas_usage0, seg_ids,
        theads, n_domains, n_steps, max_cycles,
    )
    return jnp.concatenate(
        [
            r.admitted_k.reshape(-1),
            r.admitted_cycle.reshape(-1),
            r.adm_step.reshape(-1),
            r.cursor,
            r.stuck.astype(jnp.int32),
            r.tas_usage.reshape(-1),
            r.cycles[None],
        ]
    )


solve_drain_tas_packed_jit = jax.jit(
    _solve_drain_tas_packed,
    static_argnames=("n_domains", "n_steps", "max_cycles"),
)


def _fair_chain(
    usage, borrowed_base, paths_q, mcells, mqty, subtree, guaranteed,
    lendable, weight, parent, res_of, n_res: int, max_depth: int,
):
    """Per-head fair-sharing DRS chain (fair_sharing_iterator.py
    path_drs, vectorized): for each queue q and path level d, the
    DominantResourceShare of path node d with q's representative usage
    added at its CQ row. Only the head's cells change, so the node's
    per-resource borrowed total is borrowed_base plus the head-cell
    delta; lendable depends on quota alone and is precomputed.

    usage: int64[N,FR]; borrowed_base: int64[N,R] (max(0, usage -
    subtree) summed per resource); paths_q: int32[Q,D+1]; mcells/mqty:
    [Q,C']; lendable: int64[N,R]; weight: int64[N]; res_of: int32[C']
    per queue -> resource bucket of each head cell (n_res = pad).
    Returns int64[Q, D+1]."""
    qn, cdim = mcells.shape
    cells_c = jnp.maximum(mcells, 0)
    cell_ok = (mcells >= 0) & (mqty > 0)
    delta = jnp.where(cell_ok, mqty, 0)  # [Q,C']
    chains = []
    for d in range(max_depth + 1):
        node = jnp.maximum(paths_q[:, d], 0)  # [Q]
        node_valid = paths_q[:, d] >= 0
        u_at = usage[node[:, None], cells_c]  # [Q,C']
        sub_at = subtree[node[:, None], cells_c]
        g_at = guaranteed[node[:, None], cells_c]
        new = u_at + delta
        bdelta = jnp.maximum(0, new - sub_at) - jnp.maximum(0, u_at - sub_at)
        qq = jnp.broadcast_to(jnp.arange(qn)[:, None], res_of.shape)
        badd = (
            jnp.zeros((qn, n_res + 1), dtype=jnp.int64)
            .at[qq, res_of]
            .add(jnp.where(cell_ok, bdelta, 0))[:, :n_res]
        )
        borrowed = borrowed_base[node] + badd  # [Q,R]
        lend = lendable[node]
        ratio = jnp.where(
            (borrowed > 0) & (lend > 0),
            borrowed * 1000 // jnp.maximum(lend, 1),
            -1,
        )
        drs = jnp.max(ratio, axis=1)
        has_parent = parent[node] >= 0
        active = jnp.any(borrowed > 0, axis=1) & has_parent & node_valid
        w = weight[node]
        num = drs * 1000
        trunc = jnp.sign(num) * (jnp.abs(num) // jnp.maximum(w, 1))
        dws = jnp.where(active, jnp.where(w == 0, DRS_MAX, trunc), 0)
        chains.append(dws)
        # bubble the head usage to the next level (over-guaranteed)
        delta = jnp.where(
            node_valid[:, None],
            jnp.maximum(0, new - g_at) - jnp.maximum(0, u_at - g_at),
            delta,
        )
    return jnp.stack(chains, axis=1)  # [Q, D+1]


def _fair_tournament(
    chain, remaining, paths_q, cq_rows, depth_of, parent, prio, ts,
    n_nodes: int, max_tree_depth: int, prio_tie: bool,
):
    """One fair-sharing pop per root cohort (fair_sharing_iterator.py
    tournament, vectorized over the whole forest): every cohort node
    picks the best of its children's winners, compared by the child's
    recorded DRS at that node (chain value at the child's position on
    the winner's path), tie-broken by priority (behind the
    PrioritySortingWithinCohort gate), FIFO timestamp, then queue index.
    Returns bool[Q]: this queue's head wins its root's tournament."""
    INF = jnp.int64(1 << 62)
    qn = remaining.shape[0]
    cqr = jnp.maximum(cq_rows, 0)
    head_depth = depth_of[cqr]  # [Q]

    # per-node winner state, initialized at the CQ leaves
    win_q = jnp.full(n_nodes, -1, dtype=jnp.int32).at[
        jnp.where(remaining, cqr, n_nodes)
    ].set(jnp.arange(qn, dtype=jnp.int32), mode="drop")

    tie1 = jnp.where(prio_tie, -prio, 0)  # [Q]
    tie2 = ts

    for d in range(max_tree_depth, 0, -1):
        at_d = (depth_of == d) & (win_q >= 0)
        wq = jnp.maximum(win_q, 0)
        # key at the parent = winner's chain value at THIS node's
        # position on its path: level = head_depth[q] - d
        lvl = jnp.clip(head_depth[wq] - d, 0, chain.shape[1] - 1)
        k_dws = jnp.where(at_d, chain[wq, lvl], INF)
        k_t1 = jnp.where(at_d, tie1[wq], INF)
        k_t2 = jnp.where(at_d, tie2[wq], INF)
        k_qi = jnp.where(at_d, wq.astype(jnp.int64), INF)
        seg = jnp.where(at_d & (parent >= 0), parent, n_nodes)
        m1 = jax.ops.segment_min(k_dws, seg, num_segments=n_nodes + 1)[:n_nodes]
        s1 = at_d & (k_dws == m1[jnp.maximum(parent, 0)])
        m2 = jax.ops.segment_min(
            jnp.where(s1, k_t1, INF), seg, num_segments=n_nodes + 1
        )[:n_nodes]
        s2 = s1 & (k_t1 == m2[jnp.maximum(parent, 0)])
        m3 = jax.ops.segment_min(
            jnp.where(s2, k_t2, INF), seg, num_segments=n_nodes + 1
        )[:n_nodes]
        s3 = s2 & (k_t2 == m3[jnp.maximum(parent, 0)])
        m4 = jax.ops.segment_min(
            jnp.where(s3, k_qi, INF), seg, num_segments=n_nodes + 1
        )[:n_nodes]
        parent_win = jnp.where(m4 < INF, m4, -1).astype(jnp.int32)
        # only overwrite nodes that RECEIVED proposals this round
        got = m1 < INF
        win_q = jnp.where(got, parent_win, win_q)

    # root of each queue = last valid node on its path
    root_pos = jnp.sum((paths_q >= 0).astype(jnp.int32), axis=1) - 1
    root_row = paths_q[jnp.arange(qn), jnp.maximum(root_pos, 0)]
    return remaining & (win_q[jnp.maximum(root_row, 0)] == jnp.arange(qn))


def solve_drain_fair(
    tree: QuotaTree,
    local_usage: jnp.ndarray,  # int64[N, FR]
    queues: DrainQueues,
    paths: jnp.ndarray,  # int32[N, D+1]
    depth_of: jnp.ndarray,  # int32[N] tree depth (roots 0)
    weight: jnp.ndarray,  # int64[N] fairSharing weight_milli
    lendable: jnp.ndarray,  # int64[N, R] (quota-only, precomputed)
    res_of_fr: jnp.ndarray,  # int32[FR] cell -> resource bucket
    n_segments: int,
    n_steps: int,
    max_cycles: int,
    n_res: int,
    prio_tie: bool,
) -> DrainResult:
    """Multi-cycle drain under FAIR-SHARING admission ordering — the
    whole fair tournament on the device. Each cycle pops heads via the
    lazy cohort tournament (fair_sharing_iterator.go:33-120): one pop
    per root cohort per step, every pop re-evaluating
    DominantResourceShare against the usage as mutated by the cycle's
    earlier admissions and reservations, exactly like the host
    iterator. Preemption stays out of scope (the host lowering routes
    preempt-capable CQs to fallback in fair mode); preempt-classified
    heads of never-preempting CQs pop, reserve (no_reclaim) and park
    as in solve_drain.
    """
    max_depth = tree.max_depth
    subtree, guaranteed = subtree_quota(tree)
    from kueue_tpu.ops.assign_kernel import potential_available_all

    potential = potential_available_all(tree, subtree, guaranteed)

    q, l, pmax, k, c = queues.cells.shape
    n_nodes = tree.parent.shape[0]
    q_idx = jnp.arange(q)
    cq = jnp.maximum(queues.cq_rows, 0)
    paths_q = paths[cq]  # [Q, D+1]

    avail_v = jax.vmap(
        _avail_along_path, in_axes=(0, 0, None, None, None, None, None)
    )

    def cycle_body(state):
        (local, cursor, g_start, retries, stuck, no_prog, adm_k,
         adm_cycle, cycle) = state

        active = cursor < queues.qlen  # [Q]
        cur = jnp.minimum(cursor, l - 1)
        usage0 = usage_tree(tree, guaranteed, local)
        (is_fit, is_pre, pend, head_borrow, rep_k, walk_next,
         cells_eff, qty_eff, _mneed) = _nominate_multi(
            tree, subtree, guaranteed, local, usage0, queues, q_idx, cur,
            active, g_start, potential,
        )
        nofit = ~(is_fit | is_pre)
        prio = queues.priority[q_idx, cur]
        ts = queues.timestamp[q_idx, cur]
        cells_c = jnp.maximum(cells_eff, 0)
        cell_valid_all = (cells_eff >= 0) & (qty_eff > 0)
        res_of_q = jnp.where(
            cell_valid_all, res_of_fr[cells_c], n_res
        ).astype(jnp.int32)

        def step(carry, s):
            usage, remaining = carry
            # borrowed per resource for every node, against live usage
            nn = jnp.broadcast_to(
                jnp.arange(n_nodes)[:, None], usage.shape
            )
            bb = (
                jnp.zeros((n_nodes, n_res + 1), dtype=jnp.int64)
                .at[nn, res_of_fr[None, :].repeat(n_nodes, axis=0)]
                .add(jnp.maximum(0, usage - subtree))[:, :n_res]
            )
            chain = _fair_chain(
                usage, bb, paths_q, cells_eff, qty_eff, subtree,
                guaranteed, lendable, weight, tree.parent, res_of_q,
                n_res, max_depth,
            )
            win = _fair_tournament(
                chain, remaining, paths_q, queues.cq_rows, depth_of,
                tree.parent, prio, ts, n_nodes, max_depth, prio_tie,
            )
            avail = avail_v(
                paths_q, cells_eff, usage, subtree, guaranteed,
                tree.borrowing_limit, max_depth,
            )
            cell_valid = cell_valid_all & win[:, None]
            fits = jnp.all(
                jnp.where(cell_valid, avail >= qty_eff, True), axis=1
            )
            admit = win & is_fit & fits
            reserve = win & is_pre & queues.no_reclaim
            nominal_c = tree.nominal[cq[:, None], cells_c]
            bl_c = tree.borrowing_limit[cq[:, None], cells_c]
            leaf_usage_c = usage[cq[:, None], cells_c]
            borrow_cap = jnp.where(
                bl_c < NO_LIMIT,
                jnp.minimum(qty_eff, nominal_c + bl_c - leaf_usage_c),
                qty_eff,
            )
            nominal_cap = jnp.maximum(
                0, jnp.minimum(qty_eff, nominal_c - leaf_usage_c)
            )
            reserve_qty = jnp.where(
                head_borrow[:, None], borrow_cap, nominal_cap
            )
            delta = jnp.where(
                cell_valid & admit[:, None],
                qty_eff,
                jnp.where(cell_valid & reserve[:, None], reserve_qty, 0),
            )
            # winners are one per root cohort: their paths are disjoint,
            # so the per-level scatters cannot collide
            for d in range(0, max_depth + 1):
                node = jnp.maximum(paths_q[:, d], 0)
                node_valid = (paths_q[:, d] >= 0)[:, None]
                old = usage[node[:, None], cells_c]
                gg = guaranteed[node[:, None], cells_c]
                new = old + delta
                usage = usage.at[node[:, None], cells_c].add(
                    jnp.where(node_valid, delta, 0)
                )
                delta = jnp.where(
                    node_valid,
                    jnp.maximum(0, new - gg) - jnp.maximum(0, old - gg),
                    delta,
                )
            remaining = remaining & ~win
            return (usage, remaining), admit

        participants = active & ~nofit & (queues.cq_rows >= 0)
        (_, _), admit_sn = lax.scan(
            step, (usage0, participants), jnp.arange(n_steps)
        )
        admitted = jnp.any(admit_sn, axis=0)  # [Q]

        # leaf usage adds for admissions only — reservations die with
        # the cycle (the reserving head parks)
        add = jnp.where(cell_valid_all & admitted[:, None], qty_eff, 0)
        local = local.at[cq[:, None], cells_c].add(add)

        (cursor, g_start, retries, stuck, no_prog, adm_k, adm_cycle) = (
            _cursor_queue_motion(
                queues, q_idx, cur, active, is_fit, pend, admitted,
                rep_k, walk_next, retries, stuck, no_prog, adm_k,
                adm_cycle, g_start, cursor, cycle,
            )
        )
        return (local, cursor, g_start, retries, stuck, no_prog, adm_k,
                adm_cycle, cycle + 1)

    def cond(state):
        _, cursor, _, _, stuck, _, _, _, cycle = state
        return jnp.any((cursor < queues.qlen) & ~stuck) & (cycle < max_cycles)

    init = (
        local_usage,
        jnp.zeros(q, dtype=jnp.int32),
        first_walks(queues),
        jnp.zeros(q, dtype=jnp.int32),
        jnp.zeros(q, dtype=bool),
        jnp.int32(0),
        jnp.full((q, l, pmax), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.int32(0),
    )
    (local_f, cursor_f, g_start_f, _, stuck_f, _, adm_k, adm_cycle,
     cycles) = lax.while_loop(cond, cycle_body, init)
    return DrainResult(
        admitted_k=adm_k,
        admitted_cycle=adm_cycle,
        cursor=cursor_f,
        cycles=cycles,
        local_usage=local_f,
        stuck=stuck_f,
        g_start=g_start_f,
    )


def _solve_drain_fair_packed(
    tree, local_usage, queues, paths, depth_of, weight, lendable,
    res_of_fr, n_segments: int, n_steps: int, max_cycles: int,
    n_res: int, prio_tie: bool,
):
    r = solve_drain_fair(
        tree, local_usage, queues, paths, depth_of, weight, lendable,
        res_of_fr, n_segments, n_steps, max_cycles, n_res, prio_tie,
    )
    # same layout as _solve_drain_packed so run_drain unpacks both
    # scopes with one decoder
    return _pack_drain_result(r)


solve_drain_fair_packed_jit = jax.jit(
    _solve_drain_fair_packed,
    static_argnames=(
        "n_segments", "n_steps", "max_cycles", "n_res", "prio_tie"
    ),
)


class SegVictims(NamedTuple):
    """Per-root-cohort (segment) candidate pools + per-queue search
    config for the preemption-enabled drain.

    S segments, V pool slots per segment, Cv cells per victim, M local
    nodes per segment (the segment's CQs + interior cohorts + root),
    D+1 global path length, Q queues, L entries per queue.

    Pool slots come in two parts. Part A: workloads already admitted in
    the snapshot — their cells/qty are static. Part B: one slot per
    pending queue entry of the segment — invalid until the drain admits
    the entry, at which point the kernel fills the slot with the
    admitted cells/qty so the entry becomes a live reclaim candidate
    for later cycles (the host cycle loop sees drain-admitted workloads
    in its snapshot the same way; preemption.go:480-524).

    scells/sqty: int32/int64[S,V,Cv] — GLOBAL flavor-resource cells of
            the slot's admitted usage (-1 pads; part B -1 until filled).
    sprio/sts: int64[S,V] — priority and queue-order timestamp (the
            LowerOrNewerEqualPriority rule compares the preemptor's
            timestamp against the candidate's).
    svalid0: bool[S,V] — slot live at drain start (part A only).
    sowner: int32[S,V] — owner ClusterQueue's global tree row (-1 pad).
    sowner_local: int32[S,V] — owner CQ's segment-local node id.
    sslot_q/sslot_l: int32[S,V] — part B: the (queue, position) of the
            entry occupying this slot (-1 for part A).
    seg_nodes: int32[S,M] — global rows of the segment's nodes (-1 pad).
    lpaths: int32[S,M,D+1] — each local node's ancestor path expressed
            in LOCAL node ids (leaf first, -1 beyond the root).
    hlocal: int32[Q] — each queue's CQ as a local node id.
    perm: int32[Q,V] — the queue's candidate order over its segment's
            slots (preemption.go:591-618: evicted first, other-CQ
            first, lowest priority, most recently reserved; in-drain
            admissions all share one reservation instant).
    ptier: int32[Q,V] — per ord position, the rank of its (evicted,
            other-CQ, cost, priority) tier; with ``ordered_stamps``
            the kernel re-ranks part B within a tier by admission.
    entry_slot: int32[Q,L] — part-B pool slot of each entry (-1 none).
    same_enabled: bool[Q] — withinClusterQueue != Never.
    same_prio_ok: bool[Q] — policy == LowerOrNewerEqualPriority.
    reclaim_enabled: bool[Q] — reclaimWithinCohort != Never (w/ cohort).
    only_lower: bool[Q] — reclaimWithinCohort == LowerPriority.
    bwc: bool[Q] — borrowWithinCohort.policy != Never.
    bwc_thr1: int64[Q] — maxPriorityThreshold+1 (NO_LIMIT when unset);
            the runtime threshold is min(head priority, bwc_thr1)
            (preemption.go:194-204).
    """

    scells: jnp.ndarray
    sqty: jnp.ndarray
    sprio: jnp.ndarray
    sts: jnp.ndarray
    svalid0: jnp.ndarray
    sowner: jnp.ndarray
    sowner_local: jnp.ndarray
    sslot_q: jnp.ndarray
    sslot_l: jnp.ndarray
    seg_nodes: jnp.ndarray
    lpaths: jnp.ndarray
    hlocal: jnp.ndarray
    perm: jnp.ndarray
    ptier: jnp.ndarray
    entry_slot: jnp.ndarray
    same_enabled: jnp.ndarray
    same_prio_ok: jnp.ndarray
    reclaim_enabled: jnp.ndarray
    only_lower: jnp.ndarray
    bwc: jnp.ndarray
    bwc_thr1: jnp.ndarray


# SegVictims fields indexed per QUEUE (the [Q, ...] axis) — the mesh
# path shards exactly these along ``wl`` and pads them with inert
# queues; everything else is per-segment / topology, replicated.
SEG_VICTIM_Q_FIELDS = (
    "hlocal", "perm", "ptier", "entry_slot", "same_enabled", "same_prio_ok",
    "reclaim_enabled", "only_lower", "bwc", "bwc_thr1",
)

# bwc_thr1 sentinel meaning "no maxPriorityThreshold configured"
NO_BWC_THRESHOLD = 1 << 60


class PreemptDrainResult(NamedTuple):
    """status: int32[Q,L] final entry state (0 pending=never decided
    before max_cycles, 1 parked, 2 admitted); admitted_k / admitted_cycle
    as DrainResult; evicted: bool[S,V] pool slot was preempted (part-A
    snapshot victims AND part-B drain-admitted entries);
    evicted_cycle: int32[S,V]; evicted_by: int32[S,V] queue index of the
    evicting head (-1 where not evicted) — each victim is removed by
    exactly one head (the overlap guard plus the live mask forbid a
    second eviction), so the attribution is exact; cycles; local_usage.

    overflowed: bool scalar — some head's eligible-candidate list
    overflowed the ``search_width`` panel AND its search missed
    (inconclusive truncation) at least once. While False the panel
    truncation was EXACT everywhere (every search either succeeded
    inside the window — minimalPreemptions stops at the first fitting
    prefix — or failed with the full eligible list in-window), so the
    whole drain's decisions are identical to any wider panel's. The
    host uses it as the escalation trigger of the two-tier panel
    ladder (core/drain.run_drain_preempt panel_widths).

    admitted_order: int32[Q,L] an admitted entry's position in its
    cycle's entry order (-1 where not admitted; the fair kernel gives
    its tournament step, an order within each root cohort), so the host applies — and stamps — a cycle's admissions
    in the order the host cycle loop would."""

    status: jnp.ndarray
    admitted_k: jnp.ndarray
    admitted_cycle: jnp.ndarray
    admitted_order: jnp.ndarray  # int32[Q,L] rank in its cycle's entry order
    evicted: jnp.ndarray
    evicted_cycle: jnp.ndarray
    evicted_by: jnp.ndarray
    stuck: jnp.ndarray  # bool[Q] — frozen PendingFlavors spinners
    cycles: jnp.ndarray
    local_usage: jnp.ndarray
    overflowed: jnp.ndarray


def _recency_order(perm, ptier, part_b, fill_cycle, fill_pos):
    """Candidate order with part B re-ranked by admission: on a
    running clock the host stamps each admission when it applies it,
    so among drain admissions of one tier the later cycle — and within
    a cycle the later entry-order position — is more recently reserved
    and comes first (preemption.go:591-618). Part A keeps its static
    order after part B, as every snapshot reservation is older.

    perm/ptier: int32[Q,V] static order and its tiers; part_b: bool,
    fill_cycle/fill_pos: int32[Q,V] per SLOT. Returns int32[Q,V]."""
    j = jnp.broadcast_to(jnp.arange(perm.shape[1], dtype=jnp.int32), perm.shape)
    b = jnp.take_along_axis(part_b, perm, axis=1)
    k1 = jnp.where(b, -jnp.take_along_axis(fill_cycle, perm, axis=1), 1 << 30)
    k2 = jnp.where(b, -jnp.take_along_axis(fill_pos, perm, axis=1), j)
    return lax.sort(
        (ptier, k1, k2, perm), dimension=1, is_stable=True, num_keys=3
    )[-1]


def _compact_candidates(cand_ord: jnp.ndarray, width: int):
    """Pack the True positions of ``cand_ord`` (bool[Q,V], already in
    per-queue candidate order) into the first ``width`` slots.

    Returns (comp int32[Q,width] of ord indices, -1 pads; overflow
    bool[Q]). minimalPreemptions stops at the first fitting prefix, so
    truncating the candidate list is exact whenever the search succeeds
    within the window or fails without overflow; a failed search WITH
    overflow is inconclusive and the caller must freeze the queue as a
    no-decision (host fallback) rather than park it."""
    qn, v = cand_ord.shape
    rank = jnp.cumsum(cand_ord.astype(jnp.int32), axis=1) - 1
    dest = jnp.where(cand_ord & (rank < width), rank, width)
    qq = jnp.broadcast_to(jnp.arange(qn)[:, None], (qn, v))
    src = jnp.broadcast_to(jnp.arange(v, dtype=jnp.int32)[None, :], (qn, v))
    comp = (
        jnp.full((qn, width + 1), -1, dtype=jnp.int32)
        .at[qq, dest]
        .set(src)[:, :width]
    )
    overflow = jnp.any(cand_ord & (rank >= width), axis=1)
    return comp, overflow


def _ladder_search_one(
    enabled,  # bool scalar — run this attempt at all
    ab_init,  # bool scalar — attempt's starting allowBorrowing
    thr_on,  # bool scalar — borrowWithinCohort threshold active
    thr,  # int64 scalar — allowBorrowingBelowPriority
    comp,  # int32[Ve] compacted ord indices (-1 pads)
    vq_ord,  # int64[V,C] candidate usage at head cells, per-queue order
    same_ord,  # bool[V]
    prio_ord,  # int64[V]
    olocal_ord,  # int32[V] owner CQ local node id
    u0_sub,  # int64[M,C] cycle-start bubbled usage on segment nodes
    lf0_sub,  # int64[M,C] cycle-start leaf usage
    g_sub,  # int64[M,C] guaranteed
    sub_sub,  # int64[M,C] subtree quota
    bl_sub,  # int64[M,C] borrowing limit
    nom_sub,  # int64[M,C] nominal
    lpaths_q,  # int32[M,D+1] local ancestor paths
    hlocal_q,  # int32 head CQ local id
    qty,  # int64[C] head request (merged podsets)
    cell_need,  # bool[C]
    need_pre,  # bool[C] cells in frs_need_preemption
    max_depth: int,
):
    """One minimalPreemptions attempt for one head over its segment's
    candidate pool (preemption.go:275-342), on segment-local panels —
    the drain twin of preempt_kernel._solve_one, with the same in-loop
    semantics: other-CQ candidates only count while their CQ still
    borrows in a cell needing preemption (preemption.go:300), the
    borrowWithinCohort priority threshold permanently disables
    borrowing (:307-312), fit = available() along the head's path plus
    the nominal cap when borrowing is disallowed (:552-574), and
    fill-back re-adds candidates in reverse (:318-338).

    Returns (removed bool[Ve] in STEP space, found bool)."""
    from kueue_tpu.ops.preempt_kernel import _avail_local, _bubble_local

    ve = comp.shape[0]
    hl = jnp.maximum(hlocal_q, 0)
    hpath = lpaths_q[hl]

    def fits(u, lf, ab):
        avail = _avail_local(hpath, u, sub_sub, g_sub, bl_sub, max_depth)
        ok = jnp.all(jnp.where(cell_need, avail >= qty, True))
        nb_ok = jnp.all(
            jnp.where(cell_need, lf[hl] + qty <= nom_sub[hl], True)
        )
        return ok & (ab | nb_ok)

    def rm_body(carry, j):
        u, lf, ab, done, fit_at, removed = carry
        v = comp[j]
        vv = jnp.maximum(v, 0)
        same = same_ord[vv]
        ol = jnp.maximum(olocal_ord[vv], 0)
        # other-CQ candidates only while their CQ still borrows (in the
        # simulated state) in a cell needing preemption
        ob = jnp.any((lf[ol] > nom_sub[ol]) & need_pre)
        act = (v >= 0) & ~done & enabled & (same | ob)
        flip = act & (~same) & thr_on & (prio_ord[vv] >= thr)
        ab = ab & ~flip
        u = _bubble_local(lpaths_q[ol], -vq_ord[vv], u, g_sub, max_depth, act)
        lf = lf.at[ol].add(jnp.where(act, -vq_ord[vv], 0))
        removed = removed.at[j].set(act)
        now_fits = act & fits(u, lf, ab)
        fit_at = jnp.where(now_fits & ~done, j, fit_at)
        done = done | now_fits
        return (u, lf, ab, done, fit_at, removed), None

    init = (
        u0_sub,
        lf0_sub,
        ab_init & enabled,
        ~enabled,
        jnp.int32(-1),
        jnp.zeros(ve, dtype=bool),
    )
    (u, lf, ab, done, fit_at, removed), _ = lax.scan(
        rm_body, init, jnp.arange(ve, dtype=jnp.int32)
    )
    found = done & enabled

    def fb_body(carry, j):
        u, lf, removed = carry
        v = comp[j]
        vv = jnp.maximum(v, 0)
        ol = jnp.maximum(olocal_ord[vv], 0)
        act = found & removed[j] & (j != fit_at)
        u2 = _bubble_local(lpaths_q[ol], vq_ord[vv], u, g_sub, max_depth, act)
        lf2 = lf.at[ol].add(jnp.where(act, vq_ord[vv], 0))
        keep = act & fits(u2, lf2, ab)
        u = jnp.where(keep, u2, u)
        lf = jnp.where(keep, lf2, lf)
        removed = removed.at[j].set(removed[j] & ~keep)
        return (u, lf, removed), None

    (u, lf, removed), _ = lax.scan(
        fb_body, (u, lf, removed), jnp.arange(ve - 1, -1, -1, dtype=jnp.int32)
    )
    return removed & found, found


def solve_drain_preempt(
    tree: QuotaTree,
    local_usage: jnp.ndarray,  # int64[N, FR]
    queues: DrainQueues,
    victims: SegVictims,
    paths: jnp.ndarray,  # int32[N, D+1]
    n_segments: int,
    n_steps: int,
    max_cycles: int,
    search_width: int = 32,
    ordered_stamps: bool = False,
) -> PreemptDrainResult:
    """Multi-cycle drain with classic preemption on the device —
    within-ClusterQueue AND cross-CQ cohort reclamation. Per cycle:

    - phase 1: flavor classification (Fit / Preempt / NoFit) against
      cycle-start usage, plus a batched minimalPreemptions strategy
      ladder (preemption.go:144-191) for preempt-classified heads over
      their segment's candidate pool: same-CQ candidates under the
      withinClusterQueue priority rule plus candidates from borrowing
      member CQs under reclaimWithinCohort / borrowWithinCohort
      (preemption.go:480-524, :194-204);
    - phase 2: segmented scan in entry order; preempting entries remove
      their victims (exact cross-CQ propagation: the usage tree is
      recomputed from leaf rows each step), re-check fits
      (scheduler.go:211-292), and charge their usage; heads whose
      targets overlap an earlier eviction this cycle are SKIPPED and
      retry (the scheduler's overlapping-preemption-targets guard);
    - cycle end: admitted heads leave, charge leaf usage, and fill
      their part-B pool slot so they become live reclaim candidates
      for later cycles (the host cycle loop sees drain-admitted
      workloads in its snapshot); evicted victims release their usage
      at their OWNER row, and any eviction in a root cohort reactivates
      that cohort's parked entries
      (queue.Manager.QueueAssociatedInadmissibleWorkloadsAfter).

    Entry state is per-(queue, position): pending(0)/parked(1)/
    admitted(2); each queue's head is its first pending entry in heap
    order. A drain-admitted entry later reclaimed keeps status 2 and is
    additionally reported evicted — the caller applies admissions and
    evictions in cycle order. Scope (host lowering enforces):
    multi-podset heads, any flavorFungibility policy, any number of
    resource groups, all withinClusterQueue / reclaimWithinCohort /
    borrowWithinCohort policies. Remaining exclusions routed to host
    fallback: TAS topology requests, fair sharing, candidate/cell/pool
    caps. A head whose eligible-candidate list overflows
    ``search_width`` and whose search fails is frozen as a no-decision
    (truncation is only exact when the search succeeds in-window or
    fails without overflow — see _compact_candidates).

    ``ordered_stamps``: the runtime's clock runs, so its admissions are
    stamped one after another; drain admissions are then ranked as
    candidates by (cycle, entry order) — _recency_order — instead of
    sharing one reservation instant.
    """
    max_depth = tree.max_depth
    subtree, guaranteed = subtree_quota(tree)
    from kueue_tpu.ops.assign_kernel import potential_available_all

    potential = potential_available_all(tree, subtree, guaranteed)

    q, l, pmax, k, c = queues.cells.shape
    s_dim, v, cv = victims.scells.shape
    q_idx = jnp.arange(q)
    l_idx = jnp.arange(l)
    sq = jnp.maximum(queues.seg_id, 0)  # [Q]
    cq = jnp.maximum(queues.cq_rows, 0)
    can_search = victims.same_enabled | victims.reclaim_enabled
    seg_rows = jnp.maximum(victims.seg_nodes, 0)  # [S, M]

    avail_v = jax.vmap(
        _avail_along_path, in_axes=(0, 0, None, None, None, None, None)
    )
    ladder_v = jax.vmap(_ladder_search_one, in_axes=(0,) * 20 + (None,))

    part_b_q = (victims.sslot_q >= 0)[sq]  # [Q,V]

    def cycle_body(state):
        (local, status, g_start, retries, stuck, no_prog, adm_k,
         adm_cycle, pcells, pqty, pvalid, vevicted, evict_cycle,
         evict_by, ovf, cycle, adm_order, fill_cycle, fill_pos) = state

        # head of each queue = first pending entry in heap order
        entry_pending = status == 0  # [Q,L]
        pos_cand = jnp.where(entry_pending, l_idx[None, :], l)
        cur_raw = jnp.min(pos_cand, axis=1)  # [Q]
        active = (cur_raw < l) & (cur_raw < queues.qlen)
        cur = jnp.minimum(cur_raw, l - 1)

        prio = queues.priority[q_idx, cur]
        ts = queues.timestamp[q_idx, cur]

        # ---- per-queue views of the segment candidate pool ----
        live_q = (pvalid & ~vevicted)[sq]  # [Q,V]
        sprio_q = victims.sprio[sq]
        sts_q = victims.sts[sq]
        olocal_q = jnp.maximum(victims.sowner_local[sq], 0)  # [Q,V]
        slot_ok = victims.sowner[sq] >= 0  # [Q,V]
        same_q = slot_ok & (
            victims.sowner_local[sq] == victims.hlocal[:, None]
        )

        # same-CQ victim-eligibility (preemption.go:480-524 priority
        # rule) — shared by the reclaim-oracle emulation inside the
        # nomination and the ladder search below
        lower = sprio_q < prio[:, None]
        newer_eq = (
            victims.same_prio_ok[:, None]
            & (sprio_q == prio[:, None])
            & (ts[:, None] < sts_q)
        )
        elig_same = (
            live_q & same_q & victims.same_enabled[:, None]
            & (lower | newer_eq)
        )

        usage0 = usage_tree(tree, guaranteed, local)
        pcells_q = pcells[sq]  # [Q,V,Cv]
        pqty_q = pqty[sq]
        (is_fit, is_pre, pend_flavors, head_borrow, rep_k, walk_next,
         cells_eff, qty_eff, need_pre) = _nominate_multi(
            tree, subtree, guaranteed, local, usage0, queues, q_idx, cur,
            active, g_start, potential, vcells_q=pcells_q,
            elig_v=elig_same, pwb=victims.bwc,
        )
        nofit = ~(is_fit | is_pre)
        cell_need = (cells_eff >= 0) & (qty_eff > 0)  # [Q,C']
        cells_c = jnp.maximum(cells_eff, 0)

        # ---- segment-local panels at this cycle's head cells ----
        rows_q = seg_rows[sq]  # [Q, M] global rows
        u0_sub = usage0[rows_q[:, :, None], cells_c[:, None, :]]
        lf0_sub = local[rows_q[:, :, None], cells_c[:, None, :]]
        g_sub = guaranteed[rows_q[:, :, None], cells_c[:, None, :]]
        sub_sub = subtree[rows_q[:, :, None], cells_c[:, None, :]]
        bl_sub = tree.borrowing_limit[
            rows_q[:, :, None], cells_c[:, None, :]
        ]
        nom_sub = tree.nominal[rows_q[:, :, None], cells_c[:, None, :]]
        lpaths_q = victims.lpaths[sq]  # [Q, M, D+1]

        # victim usage gathered at head cells
        match = pcells_q[:, :, :, None] == cells_c[:, None, None, :]
        match = match & (pcells_q >= 0)[:, :, :, None]
        vq_at = jnp.sum(
            jnp.where(match, pqty_q[:, :, :, None], 0), axis=2
        )  # [Q, V, C']

        # ---- candidate eligibility (preemption.go:480-524) ----
        # candidates must use a flavor-resource needing preemption
        uses = jnp.any(
            vq_at * need_pre[:, None, :].astype(jnp.int64) > 0, axis=2
        )
        # other-CQ candidates: their CQ borrows at cycle start in a
        # cell needing preemption (discovery-time _cq_is_borrowing)
        borrow_by_local = jnp.any(
            (lf0_sub > nom_sub) & need_pre[:, None, :], axis=2
        )  # [Q, M]
        owner_borrow0 = jnp.take_along_axis(borrow_by_local, olocal_q, axis=1)
        oth_prio_ok = (~victims.only_lower[:, None]) | lower
        elig_other = (
            live_q & ~same_q & slot_ok
            & victims.reclaim_enabled[:, None]
            & oth_prio_ok & owner_borrow0
        )
        elig = uses & (elig_same | elig_other)

        # ---- the strategy ladder (preemption.go:144-191) ----
        hl = jnp.maximum(victims.hlocal, 0)
        lf0_h = lf0_sub[q_idx, hl]  # [Q, C']
        nom_h = nom_sub[q_idx, hl]
        under_nominal = jnp.all(
            jnp.where(need_pre, lf0_h < nom_h, True), axis=1
        )
        other_exists = jnp.any(elig & ~same_q, axis=1)
        thr = jnp.minimum(prio, victims.bwc_thr1)  # [Q]
        case_a = ~other_exists
        case_b = other_exists & victims.bwc
        case_c = other_exists & ~victims.bwc & under_nominal
        # remaining: straight to the same-queue fallback attempt
        cand1 = jnp.where(
            case_b[:, None],
            elig
            & (same_q | (sprio_q < thr[:, None]) | under_nominal[:, None]),
            jnp.where((case_a | case_c)[:, None], elig, elig & same_q),
        )
        ab1 = ~case_c  # reclaim-without-borrowing attempt disallows it
        thr_on1 = case_b
        run2 = case_c  # failed attempt C falls back to same-queue
        cand2 = elig & same_q

        enabled1 = active & is_pre & can_search
        # [Q,V] slot ids in candidate order
        ord_of = (
            _recency_order(
                victims.perm, victims.ptier, part_b_q, fill_cycle[sq],
                fill_pos[sq],
            )
            if ordered_stamps
            else victims.perm
        )

        def to_ord(x):
            return jnp.take_along_axis(x, ord_of, axis=1)

        vq_ord = jnp.take_along_axis(vq_at, ord_of[:, :, None], axis=1)
        same_ord = to_ord(same_q)
        prio_ord = to_ord(sprio_q)
        olocal_ord = to_ord(olocal_q)
        comp1, over1 = _compact_candidates(to_ord(cand1), search_width)
        comp2, over2 = _compact_candidates(to_ord(cand2), search_width)

        rm1, found1 = ladder_v(
            enabled1, ab1, thr_on1, thr, comp1, vq_ord, same_ord,
            prio_ord, olocal_ord, u0_sub, lf0_sub, g_sub, sub_sub,
            bl_sub, nom_sub, lpaths_q, victims.hlocal, qty_eff,
            cell_need, need_pre, max_depth,
        )
        rm2, found2 = ladder_v(
            enabled1 & run2, jnp.ones(q, dtype=bool),
            jnp.zeros(q, dtype=bool), thr, comp2, vq_ord, same_ord,
            prio_ord, olocal_ord, u0_sub, lf0_sub, g_sub, sub_sub,
            bl_sub, nom_sub, lpaths_q, victims.hlocal, qty_eff,
            cell_need, need_pre, max_depth,
        )
        # inconclusive truncated attempts freeze the head as a
        # no-decision. An attempt-1 overflow-and-miss is inconclusive
        # REGARDLESS of attempt 2: the untruncated host ladder may have
        # succeeded at attempt 1 with different (cross-CQ) targets, so
        # a fallback attempt-2 success must not mask it.
        p1_bad = over1 & ~found1
        p2_bad = run2 & over2 & ~found2
        untrusted = enabled1 & (p1_bad | (~found1 & p2_bad))
        # inconclusive truncation anywhere taints the WHOLE drain for
        # the panel ladder: the host discards this result and re-solves
        # at the next wider width instead of shipping the freeze
        ovf = ovf | jnp.any(untrusted)
        psuccess = is_pre & ~untrusted & (found1 | found2)

        def to_slots(rm, comp, on):
            # step space -> ord space -> slot space
            slot_idx = jnp.take_along_axis(
                ord_of, jnp.maximum(comp, 0), axis=1
            )
            valid = (comp >= 0) & rm & on[:, None]
            slot_w = jnp.where(valid, slot_idx, v)
            qq2 = jnp.broadcast_to(q_idx[:, None], slot_w.shape)
            return (
                jnp.zeros((q, v + 1), dtype=bool)
                .at[qq2, slot_w]
                .max(valid)[:, :v]
            )

        targets = jnp.where(
            found1[:, None],
            to_slots(rm1, comp1, found1),
            to_slots(rm2, comp2, found2 & run2),
        )  # [Q, V] slot space

        # ---- entry order: preempt-classified heads participate like
        # the host admit loop (successful searches charge usage +
        # evict; failed ones reserve) ----
        order = jnp.lexsort(
            (
                ts,
                -prio,
                head_borrow.astype(jnp.int64),
                nofit.astype(jnp.int64),
            )
        )
        pos_of = jnp.zeros(q, dtype=jnp.int32).at[order].set(
            jnp.arange(q, dtype=jnp.int32)
        )
        seg = jnp.maximum(queues.seg_id, 0)[order]
        valid_sorted = active[order] & (queues.seg_id[order] >= 0) & (~nofit[order])
        rank = segmented_rank(seg, valid_sorted)
        rank_scatter = jnp.where(valid_sorted, rank, n_steps)
        mat = (
            jnp.full((n_steps, n_segments), -1, dtype=jnp.int32)
            .at[rank_scatter, seg]
            .set(order.astype(jnp.int32), mode="drop")
        )

        def step(carry, s):
            leaf, usage_c, ev_now, ev_by_now = carry  # invariant:
            #                           usage_c == usage_tree(leaf)
            idx = mat[s]  # [G]
            act = idx >= 0
            hidx = jnp.maximum(idx, 0)
            cqs = cq[hidx]
            path = paths[cqs]
            cells_ = cells_eff[hidx]
            qty_ = qty_eff[hidx]
            ccells = jnp.maximum(cells_, 0)
            cell_valid = cell_need[hidx] & act[:, None]
            sq_h = sq[hidx]  # [G]
            htarg = targets[hidx] & act[:, None]  # [G, V]
            # overlapping-preemption-targets guard: an earlier head
            # this cycle already evicted one of our victims -> skip
            overlap = jnp.any(htarg & ev_now[sq_h], axis=1)
            do_pre = psuccess[hidx] & act & ~overlap

            # remove victims at their OWNER leaf rows; on removal steps
            # the usage tree is rebuilt from leaves, which propagates
            # the removal through the victims' own ancestors exactly
            # (usage is a deterministic function of leaf usage). Steps
            # without removals — the common case — skip the rebuild and
            # keep the incrementally-maintained tree.
            pc_h = pcells[sq_h]  # [G, V, Cv]
            pq_h = pqty[sq_h]
            vrows = jnp.maximum(victims.sowner[sq_h], 0)  # [G, V]
            rm_mask = htarg & do_pre[:, None]
            rm_qty = jnp.where(rm_mask[:, :, None] & (pc_h >= 0), pq_h, 0)
            rows_b = jnp.broadcast_to(vrows[:, :, None], pc_h.shape)
            cols_b = jnp.maximum(pc_h, 0)
            any_rm = jnp.any(rm_mask)
            leaf2 = leaf.at[
                rows_b.reshape(-1), cols_b.reshape(-1)
            ].add(-rm_qty.reshape(-1))

            usage = lax.cond(
                any_rm,
                lambda _: usage_tree(tree, guaranteed, leaf2),
                lambda _: usage_c,
                None,
            )
            avail = avail_v(
                path, cells_, usage, subtree, guaranteed,
                tree.borrowing_limit, max_depth,
            )
            fits = jnp.all(
                jnp.where(cell_valid, avail >= qty_, True), axis=1
            )
            admit = act & is_fit[hidx] & fits
            pre_ok = do_pre & fits
            # revert failed preempters' removals
            revert = do_pre & ~fits
            revert_qty = jnp.where(revert[:, None, None], rm_qty, 0)
            leaf2 = leaf2.at[
                rows_b.reshape(-1), cols_b.reshape(-1)
            ].add(revert_qty.reshape(-1))

            reserve = (
                act
                & is_pre[hidx]
                & ~psuccess[hidx]
                & queues.no_reclaim[hidx]
            )
            nominal_c = tree.nominal[cqs[:, None], ccells]
            bl_c = tree.borrowing_limit[cqs[:, None], ccells]
            leaf_usage_c = leaf2[cqs[:, None], ccells]
            borrow_cap = jnp.where(
                bl_c < NO_LIMIT,
                jnp.minimum(qty_, nominal_c + bl_c - leaf_usage_c),
                qty_,
            )
            nominal_cap = jnp.maximum(
                0, jnp.minimum(qty_, nominal_c - leaf_usage_c)
            )
            reserve_qty = jnp.where(
                head_borrow[hidx][:, None], borrow_cap, nominal_cap
            )
            # charge admitted + successful preempters (AddUsage runs
            # for both — scheduler.go:211-292), reserve blocked
            # no-reclaim heads
            delta = jnp.where(
                cell_valid & (admit | pre_ok)[:, None],
                qty_,
                jnp.where(cell_valid & reserve[:, None], reserve_qty, 0),
            )
            leaf2 = leaf2.at[cqs[:, None], ccells].add(
                jnp.where(cell_valid, delta, 0)
            )

            def charge_inc(_):
                # bubble the charges up the head paths (lanes are
                # distinct root cohorts, so their paths are disjoint
                # and the per-level scatters cannot collide)
                u = usage
                d = delta
                for dep in range(0, max_depth + 1):
                    node = jnp.maximum(path[:, dep], 0)
                    node_valid = (path[:, dep] >= 0)[:, None]
                    gq = guaranteed[node[:, None], ccells]
                    old = u[node[:, None], ccells]
                    new = old + d
                    u = u.at[node[:, None], ccells].add(
                        jnp.where(node_valid, d, 0)
                    )
                    d = jnp.where(
                        node_valid,
                        jnp.maximum(0, new - gq) - jnp.maximum(0, old - gq),
                        d,
                    )
                return u

            usage_n = lax.cond(
                jnp.any(revert),
                lambda _: usage_tree(tree, guaranteed, leaf2),
                charge_inc,
                None,
            )
            ev_now = ev_now.at[jnp.where(act, sq_h, s_dim)].max(
                htarg & pre_ok[:, None], mode="drop"
            )
            # evictor attribution: at most one head ever evicts a given
            # slot (live mask + overlap guard), so max over a -1 init
            # records exactly the evicting queue's index
            ev_by_now = ev_by_now.at[jnp.where(act, sq_h, s_dim)].max(
                jnp.where(
                    htarg & pre_ok[:, None],
                    hidx[:, None].astype(jnp.int32),
                    -1,
                ),
                mode="drop",
            )
            return (leaf2, usage_n, ev_now, ev_by_now), (admit, pre_ok)

        (_, _, ev_now_f, ev_by_f), (admit_sn, pre_ok_sn) = lax.scan(
            step,
            (
                local,
                usage0,
                jnp.zeros((s_dim, v), dtype=bool),
                jnp.full((s_dim, v), -1, dtype=jnp.int32),
            ),
            jnp.arange(n_steps),
        )

        flat_idx = mat.reshape(-1)
        safe_idx = jnp.where(flat_idx >= 0, flat_idx, q)
        admitted = (
            jnp.zeros(q, dtype=bool)
            .at[safe_idx]
            .set(admit_sn.reshape(-1), mode="drop")
        )
        preempt_ok = (
            jnp.zeros(q, dtype=bool)
            .at[safe_idx]
            .set(pre_ok_sn.reshape(-1), mode="drop")
        )

        # ---- cycle end: leaf usage ----
        add = jnp.where(cell_need & admitted[:, None], qty_eff, 0)
        local = local.at[cq[:, None], cells_c].add(add)
        # evict: release each victim's FULL usage from its OWNER row
        newly = ev_now_f  # [S, V] this cycle's evictions
        ev_qty = jnp.where(newly[:, :, None] & (pcells >= 0), pqty, 0)
        owner_b = jnp.broadcast_to(
            jnp.maximum(victims.sowner, 0)[:, :, None], pcells.shape
        )
        local = local.at[
            owner_b.reshape(-1), jnp.maximum(pcells, 0).reshape(-1)
        ].add(-ev_qty.reshape(-1))
        vevicted = vevicted | newly
        evict_cycle = jnp.where(newly, cycle, evict_cycle)
        evict_by = jnp.where(newly, ev_by_f, evict_by)

        # admitted entries fill their part-B pool slot: they are live
        # reclaim candidates from the next cycle on
        slot_w = victims.entry_slot[q_idx, cur]  # [Q]
        fill = admitted & active & (slot_w >= 0)
        sq_w = jnp.where(fill, sq, s_dim)
        sl_w = jnp.maximum(slot_w, 0)
        pad = cv - cells_eff.shape[1]
        mc_w = jnp.pad(cells_eff, ((0, 0), (0, pad)), constant_values=-1)
        mq_w = jnp.pad(qty_eff, ((0, 0), (0, pad)))
        pcells = pcells.at[sq_w, sl_w].set(
            mc_w.astype(pcells.dtype), mode="drop"
        )
        pqty = pqty.at[sq_w, sl_w].set(mq_w, mode="drop")
        pvalid = pvalid.at[sq_w, sl_w].max(fill, mode="drop")
        fill_cycle = fill_cycle.at[sq_w, sl_w].set(cycle, mode="drop")
        fill_pos = fill_pos.at[sq_w, sl_w].set(pos_of, mode="drop")

        # ---- queue motion ----
        adm_k = adm_k.at[q_idx, cur].set(
            jnp.where(
                (admitted & active)[:, None], rep_k, adm_k[q_idx, cur]
            )
        )
        adm_cycle = adm_cycle.at[q_idx, cur].set(
            jnp.where(admitted & active, cycle, adm_cycle[q_idx, cur])
        )
        adm_order = adm_order.at[q_idx, cur].set(
            jnp.where(admitted & active, pos_of, adm_order[q_idx, cur])
        )
        # park only NOT_NOMINATED outcomes (NoFit, or preempt search
        # found no victim set — the reserve branch). Heads SKIPPED in
        # the admit loop — a successful search losing the in-cycle
        # fits() re-check or overlapping an earlier eviction — requeue
        # immediately (FAILED_AFTER_NOMINATION) and stay pending.
        pre_skipped = psuccess & ~preempt_ok
        over_budget = retries >= queues.retry_cap
        stuck = stuck | untrusted
        stuck = stuck | (
            active & (~is_fit) & ~preempt_ok & ~pre_skipped & pend_flavors
            & over_budget
        )
        retrying = (
            active & (~is_fit) & ~preempt_ok & ~pre_skipped & pend_flavors
            & ~stuck
        )
        new_entry_status = jnp.where(
            admitted,
            2,
            jnp.where(
                active
                & (~is_fit)
                & ~preempt_ok
                & ~pre_skipped
                & ~pend_flavors,
                1,
                0,
            ),
        )  # per-queue head status
        head_advanced = active & (new_entry_status != 0)
        # a resolving head (admit/park) un-sticks its queue — the host
        # spinner would pick up the same state change
        stuck = stuck & ~head_advanced
        retries = jnp.where(
            head_advanced | ~active,
            0,
            jnp.where(retrying, retries + 1, retries),
        )
        # global stagnation guard (see solve_drain): starved heads that
        # never advance behind frozen reservations are no-decisions
        any_prog = jnp.any(head_advanced) | jnp.any(newly)
        no_prog = jnp.where(any_prog, 0, no_prog + 1)
        stuck = stuck | (
            (no_prog >= 2 * jnp.max(queues.retry_cap))
            & active
            & ~head_advanced
        )
        status = status.at[q_idx, cur].set(
            jnp.where(active, new_entry_status, status[q_idx, cur])
        )
        # reactivate parked entries in root cohorts where usage released
        seg_released = jnp.any(newly, axis=1)  # [S]
        q_released = seg_released[sq] & (queues.seg_id >= 0)
        status = jnp.where(q_released[:, None] & (status == 1), 0, status)

        lost = active & is_fit & (~admitted)
        walk_reset = (
            admitted | (active & (~is_fit) & ~retrying) | preempt_ok
        )
        g_start = jnp.where(
            walk_reset[:, None, None],
            0,
            jnp.where((lost | retrying)[:, None, None], walk_next, g_start),
        ).astype(jnp.int32)
        return (
            local, status, g_start, retries, stuck, no_prog, adm_k,
            adm_cycle, pcells, pqty, pvalid, vevicted, evict_cycle,
            evict_by, ovf, cycle + 1, adm_order, fill_cycle, fill_pos,
        )

    def cond(state):
        status = state[1]
        stuck = state[4]
        cycle = state[15]
        has_pending = jnp.any(
            (status == 0)
            & (l_idx[None, :] < queues.qlen[:, None])
            & ~stuck[:, None]
        )
        return has_pending & (cycle < max_cycles)

    init = (
        local_usage,
        jnp.zeros((q, l), dtype=jnp.int32),
        first_walks(queues),
        jnp.zeros(q, dtype=jnp.int32),
        jnp.zeros(q, dtype=bool),
        jnp.int32(0),
        jnp.full((q, l, pmax), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        victims.scells,
        victims.sqty,
        victims.svalid0,
        jnp.zeros((s_dim, v), dtype=bool),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
        jnp.zeros((), dtype=bool),
        jnp.int32(0),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
    )
    (local_f, status_f, _, _, stuck_f, _, adm_k, adm_cycle, _, _, _,
     vevicted, evict_cycle, evict_by, ovf_f, cycles, adm_order, _,
     _) = lax.while_loop(cond, cycle_body, init)
    return PreemptDrainResult(
        status=status_f,
        admitted_k=adm_k,
        admitted_cycle=adm_cycle,
        admitted_order=adm_order,
        evicted=vevicted,
        evicted_cycle=evict_cycle,
        evicted_by=evict_by,
        cycles=cycles,
        local_usage=local_f,
        stuck=stuck_f,
        overflowed=ovf_f,
    )


class FairSegPanels(NamedTuple):
    """Per-root-cohort local panels for the IN-DRAIN fair-sharing
    victim search (the drain twin of core/preempt_batch.py's
    lower_fair_preemption panels, shapes shared with SegVictims).

    S segments, M local nodes, Cu panel cells (the segment's ACTIVE
    cell universe: every flavor-resource with quota or usage anywhere
    in the root cohort, plus every queued entry's candidate cells —
    DRS aggregates borrowed/lendable per RESOURCE over all of them,
    fair_sharing.go:49-104), V pool slots.

    seg_cells:    int32[S,Cu] — global FR cell ids (-1 pads).
    parent_local: int32[S,M] — local parent (-1 root / pads).
    depth_local:  int32[S,M] — local depth (segment root = 0).
    is_cq_local:  bool[S,M]; node_valid: bool[S,M].
    weight_local: int64[S,M] — fairSharing weight_milli per node.
    res_of_cell:  int32[S,Cu] — panel cell -> resource bucket; pads
                  point at the inert extra bucket (n_res).
    svqty_cu:     int64[S,V,Cu] — pool-slot usage at PANEL cell
                  positions (part A static; part B zero until the
                  drain admits the entry and fills the slot).
    """

    seg_cells: jnp.ndarray
    parent_local: jnp.ndarray
    depth_local: jnp.ndarray
    is_cq_local: jnp.ndarray
    node_valid: jnp.ndarray
    weight_local: jnp.ndarray
    res_of_cell: jnp.ndarray
    svqty_cu: jnp.ndarray


def solve_drain_fair_preempt(
    tree: QuotaTree,
    local_usage: jnp.ndarray,  # int64[N, FR]
    queues: DrainQueues,
    victims: SegVictims,
    fair: FairSegPanels,
    paths: jnp.ndarray,  # int32[N, D+1]
    depth_of: jnp.ndarray,  # int32[N] tree depth (roots 0)
    weight: jnp.ndarray,  # int64[N] fairSharing weight_milli
    lendable: jnp.ndarray,  # int64[N, R] (quota-only, precomputed)
    res_of_fr: jnp.ndarray,  # int32[FR] cell -> resource bucket
    n_segments: int,
    n_steps: int,
    max_cycles: int,
    n_res: int,
    prio_tie: bool,
    strategy1: int,
    has_second: bool,
    ordered_stamps: bool = False,
) -> PreemptDrainResult:
    """Multi-cycle drain with FAIR-SHARING admission ordering AND
    fair-sharing preemption, fully on the device — the production
    fair-cohort configuration (keps/1714-fair-sharing) in one dispatch.

    Per cycle, matching the host scheduler with fair_sharing enabled:

    - phase 1: flavor classification against cycle-start usage, then
      the fair victim TOURNAMENT (preemption.go:372-463 — highest-DRS
      subtree walk, almost-LCA strategy gates, both strategies) for
      every preempt-classified head, vmapped over heads via
      fair_preempt_kernel._solve_one_fair on per-segment local panels
      constructed in-kernel from live usage + the live candidate pool
      (part-A snapshot victims and part-B drain-admitted entries);
    - phase 2: admissions pop via the in-kernel fair-sharing cohort
      tournament (one pop per root per step, DRS re-evaluated against
      usage as mutated by earlier pops). A popped preempt head with a
      victim set is checked for target overlap with this cycle's
      earlier evictions, then re-checked for fit with EVERY accepted
      victim removed (the host's non-incremental fits-after-removals:
      the fair iterator reads usage with victims still present, so
      removals live only inside the fit check); on success it charges
      its usage (scheduler.go:211-292) and its victims are evicted at
      cycle end while the head retries next cycle — exactly the host's
      PENDING_PREEMPTION round trip compressed to the cycle boundary;
    - a popped preempt head with NO victim set reserves capacity for
      the rest of the cycle unless reclaimWithinCohort=Any, then parks;
      evictions reactivate the root cohort's parked entries.
    """
    from kueue_tpu.ops.assign_kernel import potential_available_all
    from kueue_tpu.ops.fair_preempt_kernel import FairProblem, _solve_one_fair

    max_depth = tree.max_depth
    subtree, guaranteed = subtree_quota(tree)
    potential = potential_available_all(tree, subtree, guaranteed)

    q, l, pmax, k, c = queues.cells.shape
    s_dim, v, cv = victims.scells.shape
    m_dim = victims.seg_nodes.shape[1]
    cu = fair.seg_cells.shape[1]
    dmax = victims.lpaths.shape[2]
    q_idx = jnp.arange(q)
    l_idx = jnp.arange(l)
    sq = jnp.maximum(queues.seg_id, 0)  # [Q]
    cq = jnp.maximum(queues.cq_rows, 0)
    can_search = victims.same_enabled | victims.reclaim_enabled
    seg_rows = jnp.maximum(victims.seg_nodes, 0)  # [S, M]
    n_nodes = tree.parent.shape[0]
    paths_q = paths[cq]  # [Q, D+1]
    pwb_fair = victims.bwc | victims.reclaim_enabled

    avail_v = jax.vmap(
        _avail_along_path, in_axes=(0, 0, None, None, None, None, None)
    )
    fair_search_v = jax.vmap(
        lambda row: _solve_one_fair(
            row, dmax - 1, v, m_dim, n_res + 1, strategy1, has_second
        )
    )

    # static per-queue panel gathers
    segcells_q = fair.seg_cells[sq]  # [Q, Cu]
    cu_valid = segcells_q >= 0
    scc = jnp.maximum(segcells_q, 0)
    lpaths_qs = victims.lpaths[sq]  # [Q, M, D+1]
    parent_loc_q = fair.parent_local[sq]
    depth_loc_q = fair.depth_local[sq]
    is_cq_q = fair.is_cq_local[sq]
    nvalid_q = fair.node_valid[sq]
    weight_q = fair.weight_local[sq]
    res_of_cu_q = fair.res_of_cell[sq]
    hl = jnp.maximum(victims.hlocal, 0)
    hpath_l = lpaths_qs[q_idx, hl]  # [Q, D+1] local head path
    anc_of_head_q = jnp.any(
        (hpath_l[:, 1:, None] == jnp.arange(m_dim)[None, None, :])
        & (hpath_l[:, 1:, None] >= 0),
        axis=1,
    )  # [Q, M]

    part_b_q = (victims.sslot_q >= 0)[sq]  # [Q,V]

    def cycle_body(state):
        (local, status, g_start, retries, stuck, no_prog, adm_k,
         adm_cycle, pcells, pqty, pq_cu, pvalid, vevicted, evict_cycle,
         evict_by, cycle, adm_order, fill_cycle, fill_pos) = state

        # head of each queue = first pending entry in heap order
        entry_pending = status == 0  # [Q,L]
        pos_cand = jnp.where(entry_pending, l_idx[None, :], l)
        cur_raw = jnp.min(pos_cand, axis=1)  # [Q]
        active = (cur_raw < l) & (cur_raw < queues.qlen)
        cur = jnp.minimum(cur_raw, l - 1)

        prio = queues.priority[q_idx, cur]
        ts = queues.timestamp[q_idx, cur]

        # ---- per-queue views of the segment candidate pool ----
        live_q = (pvalid & ~vevicted)[sq]  # [Q,V]
        sprio_q = victims.sprio[sq]
        sts_q = victims.sts[sq]
        olocal_q = jnp.maximum(victims.sowner_local[sq], 0)  # [Q,V]
        slot_ok = victims.sowner[sq] >= 0  # [Q,V]
        same_q = slot_ok & (
            victims.sowner_local[sq] == victims.hlocal[:, None]
        )

        # same-CQ victim eligibility (preemption.go:480-524 — identical
        # for fair sharing: _find_candidates is shared)
        lower = sprio_q < prio[:, None]
        newer_eq = (
            victims.same_prio_ok[:, None]
            & (sprio_q == prio[:, None])
            & (ts[:, None] < sts_q)
        )
        elig_same = (
            live_q & same_q & victims.same_enabled[:, None]
            & (lower | newer_eq)
        )

        usage0 = usage_tree(tree, guaranteed, local)
        pcells_q = pcells[sq]  # [Q,V,Cv]
        pqty_q = pqty[sq]
        (is_fit, is_pre, pend_flavors, head_borrow, rep_k, walk_next,
         cells_eff, qty_eff, need_pre) = _nominate_multi(
            tree, subtree, guaranteed, local, usage0, queues, q_idx, cur,
            active, g_start, potential, vcells_q=pcells_q,
            elig_v=elig_same, pwb=pwb_fair,
        )
        nofit = ~(is_fit | is_pre)
        cell_need = (cells_eff >= 0) & (qty_eff > 0)  # [Q,C']
        cells_c = jnp.maximum(cells_eff, 0)

        # ---- candidate eligibility (shared with classic) ----
        match = pcells_q[:, :, :, None] == cells_c[:, None, None, :]
        match = match & (pcells_q >= 0)[:, :, :, None]
        vq_at = jnp.sum(
            jnp.where(match, pqty_q[:, :, :, None], 0), axis=2
        )  # [Q, V, C']
        uses = jnp.any(
            vq_at * need_pre[:, None, :].astype(jnp.int64) > 0, axis=2
        )
        rows_q = seg_rows[sq]  # [Q, M]
        lf0_sub = local[rows_q[:, :, None], cells_c[:, None, :]]
        nom_sub = tree.nominal[rows_q[:, :, None], cells_c[:, None, :]]
        borrow_by_local = jnp.any(
            (lf0_sub > nom_sub) & need_pre[:, None, :], axis=2
        )  # [Q, M]
        owner_borrow0 = jnp.take_along_axis(borrow_by_local, olocal_q, axis=1)
        oth_prio_ok = (~victims.only_lower[:, None]) | lower
        elig_other = (
            live_q & ~same_q & slot_ok
            & victims.reclaim_enabled[:, None]
            & oth_prio_ok & owner_borrow0
        )
        elig = uses & (elig_same | elig_other)

        # ---- fair victim tournament, vmapped over heads ----
        enabled1 = active & is_pre & can_search
        # [Q,V] slot ids in candidate order (see solve_drain_preempt)
        ord_of = (
            _recency_order(
                victims.perm, victims.ptier, part_b_q, fill_cycle[sq],
                fill_pos[sq],
            )
            if ordered_stamps
            else victims.perm
        )

        def to_ord(x):
            return jnp.take_along_axis(x, ord_of, axis=1)

        # head request mapped onto panel cell positions
        match_h = (
            (cells_c[:, :, None] == scc[:, None, :])
            & cell_need[:, :, None]
            & cu_valid[:, None, :]
        )  # [Q, C', Cu]
        need_qty_cu = jnp.sum(
            jnp.where(match_h, qty_eff[:, :, None], 0), axis=1
        )  # [Q, Cu]

        # live usage panels (pad cells/rows zeroed so DRS buckets stay
        # inert — the host lowering zero-fills the same way)
        pmask = (cu_valid[:, None, :] & nvalid_q[:, :, None])
        pu0 = jnp.where(
            pmask, usage0[rows_q[:, :, None], scc[:, None, :]], 0
        )  # [Q, M, Cu]
        psub = jnp.where(pmask, subtree[rows_q[:, :, None], scc[:, None, :]], 0)
        pg = jnp.where(
            pmask, guaranteed[rows_q[:, :, None], scc[:, None, :]], 0
        )
        pbl = jnp.where(
            pmask,
            tree.borrowing_limit[rows_q[:, :, None], scc[:, None, :]],
            NO_LIMIT,
        )
        # the head's usage is part of the simulated state
        # (preemption.go:394-395 AddUsage before DRS)
        from kueue_tpu.ops.fair_preempt_kernel import _bubble as _fp_bubble

        pu0 = jax.vmap(
            lambda pths, hr, qty_row, u, g: _fp_bubble(
                pths, hr, qty_row, u, g, dmax - 1, True
            )
        )(lpaths_qs, victims.hlocal, need_qty_cu, pu0, pg)

        pq_cu_q = pq_cu[sq]  # [Q, V, Cu]
        problem = FairProblem(
            paths=lpaths_qs,
            usage0=pu0,
            subtree_q=psub,
            guaranteed=pg,
            borrow_lim=pbl,
            weight=weight_q,
            parent_loc=parent_loc_q,
            depth_s=depth_loc_q,
            is_cq=is_cq_q,
            svalid=nvalid_q,
            anc_of_head=anc_of_head_q,
            hrow=victims.hlocal,
            need_qty=need_qty_cu,
            res_of=res_of_cu_q,
            crow=to_ord(olocal_q).astype(jnp.int32),
            cqty=jnp.take_along_axis(pq_cu_q, ord_of[:, :, None], axis=1),
            cvalid=to_ord(live_q & elig) & enabled1[:, None],
            row_valid=enabled1,
        )
        targets_ord, search_fits = fair_search_v(problem)  # [Q,V], [Q]
        psuccess = enabled1 & search_fits
        # ord space -> slot space
        qq2 = jnp.broadcast_to(q_idx[:, None], ord_of.shape)
        targets = (
            jnp.zeros((q, v), dtype=bool)
            .at[qq2, ord_of]
            .max(targets_ord & psuccess[:, None])
        )  # [Q, V] slot space

        # ---- phase 2: the admission tournament with dispositions ----
        res_of_q = jnp.where(
            cell_need, res_of_fr[cells_c], n_res
        ).astype(jnp.int32)
        participants = active & ~nofit & (queues.cq_rows >= 0)
        owner_rows_b = jnp.broadcast_to(
            jnp.maximum(victims.sowner, 0)[:, :, None], pcells.shape
        )
        pc_cols = jnp.maximum(pcells, 0)

        def step(carry, s):
            usage, leaf_c, remaining, ev_now, ev_by_now = carry
            nn = jnp.broadcast_to(jnp.arange(n_nodes)[:, None], usage.shape)
            bb = (
                jnp.zeros((n_nodes, n_res + 1), dtype=jnp.int64)
                .at[nn, res_of_fr[None, :].repeat(n_nodes, axis=0)]
                .add(jnp.maximum(0, usage - subtree))[:, :n_res]
            )
            chain = _fair_chain(
                usage, bb, paths_q, cells_eff, qty_eff, subtree,
                guaranteed, lendable, weight, tree.parent, res_of_q,
                n_res, max_depth,
            )
            win = _fair_tournament(
                chain, remaining, paths_q, queues.cq_rows, depth_of,
                tree.parent, prio, ts, n_nodes, max_depth, prio_tie,
            )
            own_t = targets & (win & psuccess)[:, None]  # [Q,V]
            overlap = jnp.any(own_t & ev_now[sq], axis=1)
            do_pre = win & is_pre & psuccess & ~overlap
            # winners are one per root cohort: scatter own targets to
            # segment space without collision
            sq_w = jnp.where(do_pre, sq, s_dim)
            own_t_seg = (
                jnp.zeros((s_dim + 1, v), dtype=bool)
                .at[sq_w]
                .max(own_t)[:s_dim]
            )
            # fits with EVERY accepted victim removed (the host's
            # non-incremental fits-after-removals); each winner's path
            # only sees its own segment's removals, so applying all
            # segments at once is exact
            rm_all = ev_now | own_t_seg
            rm_qty = jnp.where(rm_all[:, :, None] & (pcells >= 0), pqty, 0)
            leaf_fits = leaf_c.at[
                owner_rows_b.reshape(-1), pc_cols.reshape(-1)
            ].add(-rm_qty.reshape(-1))
            usage_fits = usage_tree(tree, guaranteed, leaf_fits)
            avail = avail_v(
                paths_q, cells_eff, usage_fits, subtree, guaranteed,
                tree.borrowing_limit, max_depth,
            )
            cell_valid = cell_need & win[:, None]
            fits = jnp.all(
                jnp.where(cell_valid, avail >= qty_eff, True), axis=1
            )
            admit = win & is_fit & fits
            pre_ok = do_pre & fits
            reserve = win & is_pre & ~psuccess & queues.no_reclaim
            nominal_c = tree.nominal[cq[:, None], cells_c]
            bl_c = tree.borrowing_limit[cq[:, None], cells_c]
            leaf_usage_c = leaf_c[cq[:, None], cells_c]
            borrow_cap = jnp.where(
                bl_c < NO_LIMIT,
                jnp.minimum(qty_eff, nominal_c + bl_c - leaf_usage_c),
                qty_eff,
            )
            nominal_cap = jnp.maximum(
                0, jnp.minimum(qty_eff, nominal_c - leaf_usage_c)
            )
            reserve_qty = jnp.where(
                head_borrow[:, None], borrow_cap, nominal_cap
            )
            # charge admitted heads, successful preemptors (AddUsage
            # runs for both — scheduler.go:211-292) and reservations;
            # victims stay present in the tournament's usage
            delta = jnp.where(
                cell_valid & (admit | pre_ok)[:, None],
                qty_eff,
                jnp.where(cell_valid & reserve[:, None], reserve_qty, 0),
            )
            leaf_c = leaf_c.at[cq[:, None], cells_c].add(
                jnp.where(cell_valid, delta, 0)
            )
            # winners' paths are disjoint: per-level scatters can't
            # collide
            d = delta
            for dep in range(0, max_depth + 1):
                node = jnp.maximum(paths_q[:, dep], 0)
                node_valid = (paths_q[:, dep] >= 0)[:, None]
                gg = guaranteed[node[:, None], cells_c]
                old = usage[node[:, None], cells_c]
                new = old + d
                usage = usage.at[node[:, None], cells_c].add(
                    jnp.where(node_valid, d, 0)
                )
                d = jnp.where(
                    node_valid,
                    jnp.maximum(0, new - gg) - jnp.maximum(0, old - gg),
                    d,
                )
            # only commit the winner's targets when ITS fit held; at
            # most one head ever evicts a given slot (live mask +
            # overlap guard), so max over the -1 init records exactly
            # the evicting queue's index
            sq_ok = jnp.where(pre_ok, sq, s_dim)
            ev_commit = (
                jnp.zeros((s_dim + 1, v), dtype=bool)
                .at[sq_ok]
                .max(own_t)[:s_dim]
            )
            ev_now = ev_now | ev_commit
            ev_by_now = ev_by_now.at[sq_ok].max(
                jnp.where(
                    own_t & pre_ok[:, None],
                    q_idx[:, None].astype(jnp.int32),
                    -1,
                ),
                mode="drop",
            )
            remaining = remaining & ~win
            return (usage, leaf_c, remaining, ev_now, ev_by_now), (
                admit, pre_ok,
            )

        init_ev_by = jnp.full((s_dim, v), -1, dtype=jnp.int32)
        (_, _, _, ev_now_f, ev_by_f), (admit_sn, pre_ok_sn) = lax.scan(
            step,
            (
                usage0,
                local,
                participants,
                jnp.zeros((s_dim, v), dtype=bool),
                init_ev_by,
            ),
            jnp.arange(n_steps),
        )
        admitted = jnp.any(admit_sn, axis=0)  # [Q]
        preempt_ok = jnp.any(pre_ok_sn, axis=0)
        # a root cohort's admissions, in the order its tournament took
        # them (one winner per segment and step)
        pos_of = jnp.argmax(admit_sn, axis=0).astype(jnp.int32)

        # ---- cycle end: leaf usage ----
        add = jnp.where(cell_need & admitted[:, None], qty_eff, 0)
        local = local.at[cq[:, None], cells_c].add(add)
        newly = ev_now_f  # [S, V] this cycle's evictions
        ev_qty = jnp.where(newly[:, :, None] & (pcells >= 0), pqty, 0)
        local = local.at[
            owner_rows_b.reshape(-1), pc_cols.reshape(-1)
        ].add(-ev_qty.reshape(-1))
        vevicted = vevicted | newly
        evict_cycle = jnp.where(newly, cycle, evict_cycle)
        evict_by = jnp.where(newly, ev_by_f, evict_by)

        # admitted entries fill their part-B pool slot
        slot_w = victims.entry_slot[q_idx, cur]  # [Q]
        fill = admitted & active & (slot_w >= 0)
        sq_w2 = jnp.where(fill, sq, s_dim)
        sl_w = jnp.maximum(slot_w, 0)
        pad = cv - cells_eff.shape[1]
        mc_w = jnp.pad(cells_eff, ((0, 0), (0, pad)), constant_values=-1)
        mq_w = jnp.pad(qty_eff, ((0, 0), (0, pad)))
        pcells = pcells.at[sq_w2, sl_w].set(
            mc_w.astype(pcells.dtype), mode="drop"
        )
        pqty = pqty.at[sq_w2, sl_w].set(mq_w, mode="drop")
        pq_cu = pq_cu.at[sq_w2, sl_w].set(need_qty_cu, mode="drop")
        pvalid = pvalid.at[sq_w2, sl_w].max(fill, mode="drop")
        fill_cycle = fill_cycle.at[sq_w2, sl_w].set(cycle, mode="drop")
        fill_pos = fill_pos.at[sq_w2, sl_w].set(pos_of, mode="drop")

        # ---- queue motion (as solve_drain_preempt) ----
        adm_k = adm_k.at[q_idx, cur].set(
            jnp.where(
                (admitted & active)[:, None], rep_k, adm_k[q_idx, cur]
            )
        )
        adm_cycle = adm_cycle.at[q_idx, cur].set(
            jnp.where(admitted & active, cycle, adm_cycle[q_idx, cur])
        )
        adm_order = adm_order.at[q_idx, cur].set(
            jnp.where(admitted & active, pos_of, adm_order[q_idx, cur])
        )
        pre_skipped = psuccess & ~preempt_ok
        over_budget = retries >= queues.retry_cap
        stuck = stuck | (
            active & (~is_fit) & ~preempt_ok & ~pre_skipped & pend_flavors
            & over_budget
        )
        retrying = (
            active & (~is_fit) & ~preempt_ok & ~pre_skipped & pend_flavors
            & ~stuck
        )
        new_entry_status = jnp.where(
            admitted,
            2,
            jnp.where(
                active
                & (~is_fit)
                & ~preempt_ok
                & ~pre_skipped
                & ~pend_flavors,
                1,
                0,
            ),
        )
        head_advanced = active & (new_entry_status != 0)
        stuck = stuck & ~head_advanced
        retries = jnp.where(
            head_advanced | ~active,
            0,
            jnp.where(retrying, retries + 1, retries),
        )
        any_prog = jnp.any(head_advanced) | jnp.any(newly)
        no_prog = jnp.where(any_prog, 0, no_prog + 1)
        stuck = stuck | (
            (no_prog >= 2 * jnp.max(queues.retry_cap))
            & active
            & ~head_advanced
        )
        status = status.at[q_idx, cur].set(
            jnp.where(active, new_entry_status, status[q_idx, cur])
        )
        seg_released = jnp.any(newly, axis=1)  # [S]
        q_released = seg_released[sq] & (queues.seg_id >= 0)
        status = jnp.where(q_released[:, None] & (status == 1), 0, status)

        lost = active & is_fit & (~admitted)
        walk_reset = (
            admitted | (active & (~is_fit) & ~retrying) | preempt_ok
        )
        g_start = jnp.where(
            walk_reset[:, None, None],
            0,
            jnp.where((lost | retrying)[:, None, None], walk_next, g_start),
        ).astype(jnp.int32)
        return (
            local, status, g_start, retries, stuck, no_prog, adm_k,
            adm_cycle, pcells, pqty, pq_cu, pvalid, vevicted, evict_cycle,
            evict_by, cycle + 1, adm_order, fill_cycle, fill_pos,
        )

    def cond(state):
        status = state[1]
        stuck = state[4]
        cycle = state[15]
        has_pending = jnp.any(
            (status == 0)
            & (l_idx[None, :] < queues.qlen[:, None])
            & ~stuck[:, None]
        )
        return has_pending & (cycle < max_cycles)

    init = (
        local_usage,
        jnp.zeros((q, l), dtype=jnp.int32),
        first_walks(queues),
        jnp.zeros(q, dtype=jnp.int32),
        jnp.zeros(q, dtype=bool),
        jnp.int32(0),
        jnp.full((q, l, pmax), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        victims.scells,
        victims.sqty,
        fair.svqty_cu,
        victims.svalid0,
        jnp.zeros((s_dim, v), dtype=bool),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
        jnp.int32(0),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
        jnp.full((s_dim, v), -1, dtype=jnp.int32),
    )
    (local_f, status_f, _, _, stuck_f, _, adm_k, adm_cycle, _, _, _, _,
     vevicted, evict_cycle, evict_by, cycles, adm_order, _,
     _) = lax.while_loop(cond, cycle_body, init)
    return PreemptDrainResult(
        status=status_f,
        admitted_k=adm_k,
        admitted_cycle=adm_cycle,
        admitted_order=adm_order,
        evicted=vevicted,
        evicted_cycle=evict_cycle,
        evicted_by=evict_by,
        cycles=cycles,
        local_usage=local_f,
        stuck=stuck_f,
        # the fair tournament searches the whole pool (panels carry the
        # full active-cell universe) — no truncation to escalate from
        overflowed=jnp.zeros((), dtype=bool),
    )


def _solve_drain_fair_preempt_packed(
    tree, local_usage, queues, victims, fair, paths, depth_of, weight,
    lendable, res_of_fr, n_segments: int, n_steps: int, max_cycles: int,
    n_res: int, prio_tie: bool, strategy1: int, has_second: bool,
    ordered_stamps: bool = False,
):
    r = solve_drain_fair_preempt(
        tree, local_usage, queues, victims, fair, paths, depth_of,
        weight, lendable, res_of_fr, n_segments, n_steps, max_cycles,
        n_res, prio_tie, strategy1, has_second, ordered_stamps,
    )
    return jnp.concatenate(
        [
            r.status.reshape(-1),
            r.admitted_k.reshape(-1),
            r.admitted_cycle.reshape(-1),
            r.admitted_order.reshape(-1),
            r.evicted.astype(jnp.int32).reshape(-1),
            r.evicted_cycle.reshape(-1),
            r.evicted_by.reshape(-1),
            r.stuck.astype(jnp.int32),
            r.overflowed.astype(jnp.int32)[None],
            r.cycles[None],
        ]
    )


solve_drain_fair_preempt_packed_jit = jax.jit(
    _solve_drain_fair_preempt_packed,
    static_argnames=(
        "n_segments", "n_steps", "max_cycles", "n_res", "prio_tie",
        "strategy1", "has_second", "ordered_stamps",
    ),
)


def _solve_drain_preempt_packed(
    tree, local_usage, queues, victims, paths,
    n_segments: int, n_steps: int, max_cycles: int, search_width: int,
    ordered_stamps: bool = False,
):
    r = solve_drain_preempt(
        tree, local_usage, queues, victims, paths, n_segments, n_steps,
        max_cycles, search_width, ordered_stamps,
    )
    return jnp.concatenate(
        [
            r.status.reshape(-1),
            r.admitted_k.reshape(-1),
            r.admitted_cycle.reshape(-1),
            r.admitted_order.reshape(-1),
            r.evicted.astype(jnp.int32).reshape(-1),
            r.evicted_cycle.reshape(-1),
            r.evicted_by.reshape(-1),
            r.stuck.astype(jnp.int32),
            r.overflowed.astype(jnp.int32)[None],
            r.cycles[None],
        ]
    )


solve_drain_preempt_packed_jit = jax.jit(
    _solve_drain_preempt_packed,
    static_argnames=(
        "n_segments", "n_steps", "max_cycles", "search_width",
        "ordered_stamps",
    ),
)


def _pack_drain_result(r):
    """(int32 decision vector, int64 final leaf usage, int32 cycles):
    admitted_k, admitted_cycle, cursor, stuck and the heads' resumed
    flavor walks (g_start) flattened into one vector so the host
    retrieves the whole drain in one fetch. The
    usage stays its own int64 output: packing it into the same vector
    makes the partitioner gather an int64 concatenation across a mesh,
    which the TPU compiler cannot lower."""
    decisions = jnp.concatenate(
        [
            r.admitted_k.reshape(-1),
            r.admitted_cycle.reshape(-1),
            r.cursor,
            r.stuck.astype(jnp.int32),
            r.g_start.reshape(-1),
        ]
    )
    return decisions, r.local_usage, r.cycles


def _solve_drain_packed(
    tree, local_usage, queues, paths, n_segments: int, n_steps: int, max_cycles: int
):
    """solve_drain packed for a single fetch (``_pack_drain_result``).
    The final leaf usage rides along: the pipelined drain loop launches
    round t+1's solve against it as the speculative post-apply snapshot
    while the host still applies round t (core/pipeline.py)."""
    r = solve_drain(
        tree, local_usage, queues, paths, n_segments, n_steps, max_cycles
    )
    return _pack_drain_result(r)


solve_drain_packed_jit = jax.jit(
    _solve_drain_packed, static_argnames=("n_segments", "n_steps", "max_cycles")
)
