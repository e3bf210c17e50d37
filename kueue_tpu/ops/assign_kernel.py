"""Batched admission solver — the TPU hot path.

Re-expresses one scheduling cycle's nomination + conflict resolution
(reference: ``pkg/scheduler/scheduler.go:176-310`` +
``pkg/scheduler/flavorassigner/flavorassigner.go:499-726``) as two jit
stages over dense tensors:

Phase 1 (embarrassingly parallel, vmapped over heads x candidates):
  classify every (head workload, flavor candidate) pair against the
  snapshot's availability — the per-workload greedy flavor walk becomes
  "first candidate index whose every requested cell fits", with the
  borrowing bit computed alongside (flavorassigner.go:692-726).

Phase 2 (lax.scan over admission order):
  the reference admits entries one-by-one, re-checking quota because
  each admission changes cohort availability (scheduler.go:211-292).
  Instead of re-snapshotting, the scan maintains the usage tree
  incrementally: each step recomputes availability only along the
  head's ancestor path (depth <= D, static) and, on admission, bubbles
  the usage delta up the same path — O(D x C) work per step where C is
  the (small, static) number of requested cells, independent of the
  number of nodes. This mirrors addUsage's bubble-up
  (pkg/cache/resource_node.go:123-144) exactly.

The reference's "no more than one workload admitted by a borrowing
cohort" property (scheduler.go:204-208) is emergent from the fit
re-check against updated usage, not an explicit gate — the scan
reproduces exactly that re-check, so the property carries over.

Shapes (all static; pad + mask for ragged reality):
  N  nodes (CQs then cohorts), FR flavor-resource cells,
  W  heads (<= number of ClusterQueues: one head per CQ per cycle),
  K  flavor candidates per head, C requested cells per candidate,
  D  max tree depth.

Preemption-mode nomination and TAS stay on the host authority path
(core/scheduler.py); this kernel resolves the Fit/NoFit majority in one
device dispatch, which is what the 50k-pending x 1k-CQ north star
measures.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

from kueue_tpu._jax import jax, jnp, lax
from kueue_tpu.ops.quota import (
    NO_LIMIT,
    QuotaTree,
    available_all,
    potential_available_all,
    subtree_quota,
    usage_tree,
)


class HeadsBatch(NamedTuple):
    """One cycle's nominated heads, densely packed.

    cq_row:    int32[W]   — head's ClusterQueue row, -1 for padding.
    cells:     int32[W,K,C] — FR cell indices requested by candidate k,
                              -1 for unused cell slots.
    qty:       int64[W,K,C] — requested quantity per cell.
    valid:     bool[W,K]  — candidate slot is populated.
    priority:  int64[W]
    timestamp: int64[W]   — queue-order timestamp (ns); lower = older.
    no_reclaim: bool[W]   — CQ cannot always reclaim
                            (reclaimWithinCohort != Any): blocked
                            preempt-mode heads RESERVE capacity.
    """

    cq_row: jnp.ndarray
    cells: jnp.ndarray
    qty: jnp.ndarray
    valid: jnp.ndarray
    priority: jnp.ndarray
    timestamp: jnp.ndarray
    no_reclaim: jnp.ndarray
    # int64[W,K] admission-policy candidate scores (kueue_tpu/policy):
    # the flavor choice is a masked score-argmax with ties keeping the
    # walk order, so an all-zero tensor — the default first-fit policy
    # — reproduces the boolean first-fit argmax bit-for-bit. None (the
    # default; kernel-level tests build batches without one) is
    # identical to all-zero.
    score: jnp.ndarray = None
    # int8[W,K,C] whenCanBorrow TryNextFlavor (flavorassigner.go:620-638):
    # for such a head, each candidate cell's resource-group bit. A
    # candidate's penalty is the OR of the bits of its cells that
    # borrow, and the choice among fitting candidates minimizes it
    # before the walk order: each group takes its first flavor that
    # fits without borrowing, else its first that fits. Zeros (every
    # other head) or None keep the first fit.
    try_next: jnp.ndarray = None


class SolveResult(NamedTuple):
    """chosen: int32[W] candidate index (-1 = no fit in phase 1).
    admitted: bool[W]; borrows: bool[W] (of the chosen candidate);
    reserved: bool[W] — blocked preempt-mode head reserved capacity;
    usage: int64[N,FR] final leaf usage after all admissions;
    order: int32[W] — the admission entry order used by phase 2
    (scheduler.go:575-599), so the host can replay bookkeeping in the
    same sequence; skipped: bool[W] — the chosen candidate is not the
    first that fits: a whenCanBorrow TryNextFlavor head walked past a
    flavor where it fits only by borrowing."""

    chosen: jnp.ndarray
    admitted: jnp.ndarray
    borrows: jnp.ndarray
    reserved: jnp.ndarray
    usage: jnp.ndarray
    order: jnp.ndarray
    skipped: jnp.ndarray = None


def build_paths(parent, max_depth: int):
    """int32[N, D+1] ancestor paths: row i = [i, parent(i), ..., root,
    -1 pads]. Host-side helper (numpy-compatible)."""
    import numpy as np

    n = parent.shape[0]
    paths = np.full((n, max_depth + 1), -1, dtype=np.int32)
    for i in range(n):
        cur, d = i, 0
        while cur >= 0 and d <= max_depth:
            paths[i, d] = cur
            cur = int(parent[cur])
            d += 1
    return paths


def build_roots(parent):
    """int32[N] root node of every node (itself when parentless).
    Host-side helper; segments of the segmented phase-2 resolver."""
    import numpy as np

    n = parent.shape[0]
    roots = np.empty(n, dtype=np.int32)
    for i in range(n):
        cur = i
        while parent[cur] >= 0:
            cur = int(parent[cur])
        roots[i] = cur
    return roots


def _gather_cells(mat: jnp.ndarray, rows: jnp.ndarray, cells: jnp.ndarray) -> jnp.ndarray:
    """mat[rows[d], cells[c]] -> [D+1, C] with negative indices clamped
    (callers mask)."""
    r = jnp.maximum(rows, 0)[:, None]
    c = jnp.maximum(cells, 0)[None, :]
    return mat[r, c]


def cell_masks(
    tree: QuotaTree,
    subtree: jnp.ndarray,
    guaranteed: jnp.ndarray,
    local_usage: jnp.ndarray,
    cq_row: jnp.ndarray,  # int32[W]
    cells: jnp.ndarray,  # int32[W,K,C]
    qty: jnp.ndarray,  # int64[W,K,C] (already inflated by any
    #                     accumulated same-nomination usage)
    usage=None,  # precomputed usage_tree, or None to build it
    avail=None,  # precomputed available_all (once per cycle)
    potential=None,  # precomputed potential_available_all (constant)
    pwb=None,  # bool[W] canPreemptWhileBorrowing: the CQ's preempt mode
    #            also covers requests above nominal
    #            (flavorassigner.py:425-441, borrowWithinCohort != Never)
):
    """Per-cell classification masks against the cycle-start snapshot
    (zero/pad cells are permissive): fit, preempt-eligible, the reclaim
    upgrade's leaf condition, and borrowing. The quantity compared is
    the caller's ``qty`` — multi-podset nominations inflate it with the
    usage accumulated by earlier podsets of the same workload
    (flavor_assigner's assignment_usage), which couples podsets only at
    the cell level, never through the tree."""
    if usage is None:
        usage = usage_tree(tree, guaranteed, local_usage)
    if avail is None:
        avail = available_all(tree, subtree, guaranteed, usage)  # [N, FR]
    if potential is None:
        potential = potential_available_all(tree, subtree, guaranteed)

    cq = jnp.maximum(cq_row, 0)
    cell_need = (cells >= 0) & (qty > 0)
    cc = jnp.maximum(cells, 0)
    avail_wkc = avail[cq[:, None, None], cc]
    potential_wkc = potential[cq[:, None, None], cc]
    local_wkc = local_usage[cq[:, None, None], cc]
    subtree_wkc = subtree[cq[:, None, None], cc]
    nominal_wkc = tree.nominal[cq[:, None, None], cc]
    has_cohort = (tree.parent[cq] >= 0)[:, None]

    fit_cells = jnp.where(cell_need, avail_wkc >= qty, True)
    nominal_ok = qty <= nominal_wkc
    if pwb is not None:
        nominal_ok = nominal_ok | pwb[:, None, None]
    pot_cells = jnp.where(
        cell_need, (qty <= potential_wkc) & nominal_ok, True
    )
    reclaim_cells = jnp.where(cell_need, local_wkc + qty <= nominal_wkc, True)
    borrow_cells = (
        jnp.where(cell_need, local_wkc + qty > subtree_wkc, False)
        & has_cohort[..., None]
    )
    return fit_cells, pot_cells, reclaim_cells, borrow_cells, cell_need


def phase1_classify(
    tree: QuotaTree,
    subtree: jnp.ndarray,
    guaranteed: jnp.ndarray,
    local_usage: jnp.ndarray,
    heads: HeadsBatch,
) -> Tuple[jnp.ndarray, ...]:
    """Pick each head's first fitting candidate against the cycle-start
    snapshot. Returns (chosen int32[W], borrows bool[W,K],
    preempt_k int32[W], skipped bool[W]).

    Equivalent to running FlavorAssigner.assign for every head before
    any admission mutates usage: the default fungibility policy stops at
    the first Fit, and whenCanBorrow TryNextFlavor (``heads.try_next``)
    walks on past a Fit that borrows (flavorassigner.go:620-638);
    ``skipped`` marks the heads whose choice lies past such a Fit.
    ``preempt_k`` is the representative preempt-mode candidate for
    unfit heads: the first candidate whose request fits within the
    cohort's potentialAvailable (flavorassigner.go:692-726 classifies
    such candidates Preempt/Reclaim rather than NoFit).
    """
    usage = usage_tree(tree, guaranteed, local_usage)
    avail = available_all(tree, subtree, guaranteed, usage)  # [N, FR]
    potential = potential_available_all(tree, subtree, guaranteed)  # [N, FR]

    cq = jnp.maximum(heads.cq_row, 0)  # [W]
    # Zero-quantity cells never constrain the fit: the host path masks
    # usage_vec > 0 and clamps available() to >= 0, so a request of 0
    # fits even when availability is negative (over-admitted root).
    cell_need = (heads.cells >= 0) & (heads.qty > 0)  # [W,K,C]
    cells = jnp.maximum(heads.cells, 0)

    # avail/subtree/local rows per head, gathered at candidate cells
    avail_wkc = avail[cq[:, None, None], cells]  # [W,K,C]
    subtree_wkc = subtree[cq[:, None, None], cells]
    local_wkc = local_usage[cq[:, None, None], cells]
    potential_wkc = potential[cq[:, None, None], cells]

    fits = jnp.all(
        jnp.where(cell_need, avail_wkc >= heads.qty, True), axis=-1
    )  # [W,K]
    # default-policy PREEMPT per cell: request <= potentialAvailable
    # AND request <= nominal (flavorassigner.go:692-726; the
    # preempt-while-borrowing policies stay on the host path)
    nominal_wkc = tree.nominal[cq[:, None, None], cells]
    pot_fits = jnp.all(
        jnp.where(
            cell_need,
            (heads.qty <= potential_wkc) & (heads.qty <= nominal_wkc),
            True,
        ),
        axis=-1,
    )  # [W,K]
    has_cohort = (tree.parent[cq] >= 0)[:, None]  # [W,1]
    borrow_cells = (
        jnp.where(cell_need, local_wkc + heads.qty > subtree_wkc, False)
        & has_cohort[..., None]
    )  # [W,K,C]
    borrows = jnp.any(borrow_cells, axis=-1)  # [W,K]

    # masked score-argmax (kueue_tpu/policy): among eligible candidates
    # pick the highest score; jnp.argmax's first-max tie-break keeps
    # the walk order, so the default all-zero scores (or score=None)
    # reproduce the boolean first-fit argmax bit-for-bit
    score = heads.score if heads.score is not None else jnp.int64(0)
    neg = jnp.int64(-(2**62))
    fit_ok = fits & heads.valid
    first_fit = jnp.argmax(jnp.where(fit_ok, score, neg), axis=1)
    pick = first_fit
    if heads.try_next is not None:
        # one bit per resource group whose flavor borrows; a lower
        # penalty wins before the score and the walk order
        penalty = jnp.bitwise_or.reduce(
            jnp.where(borrow_cells, heads.try_next, 0).astype(jnp.int8), axis=-1
        ).astype(jnp.int64)  # [W,K]
        pick = jnp.argmax(jnp.where(fit_ok, score - penalty, neg), axis=1)
    any_fit = jnp.any(fit_ok, axis=1)
    populated = heads.cq_row >= 0
    chosen = jnp.where(any_fit & populated, pick, -1).astype(jnp.int32)
    skipped = (chosen >= 0) & (pick != first_fit)

    pre_ok = pot_fits & heads.valid
    first_pre = jnp.argmax(jnp.where(pre_ok, score, neg), axis=1)
    any_pre = jnp.any(pre_ok, axis=1)
    preempt_k = jnp.where(
        any_pre & populated & (chosen < 0), first_pre, -1
    ).astype(jnp.int32)
    # Per-cell masks (for the drain's resource-group walks) live in the
    # standalone cell_masks() helper above — single source of truth.
    return chosen, borrows, preempt_k, skipped


def _avail_along_path(
    path: jnp.ndarray,  # int32[D+1]
    cells: jnp.ndarray,  # int32[C] (>=0-clamped upstream ok)
    usage: jnp.ndarray,  # int64[N,FR] current full usage tree
    subtree: jnp.ndarray,
    guaranteed: jnp.ndarray,
    borrowing_limit: jnp.ndarray,
    max_depth: int,
) -> jnp.ndarray:
    """available() at the path's leaf, computed root-down over the
    ancestor path only (resource_node.go:89-104). Returns int64[C]."""
    sub = _gather_cells(subtree, path, cells)  # [D+1, C]
    g = _gather_cells(guaranteed, path, cells)
    bl = _gather_cells(borrowing_limit, path, cells)
    u = _gather_cells(usage, path, cells)

    valid = path >= 0  # [D+1]
    root_pos = jnp.sum(valid.astype(jnp.int32)) - 1

    avail = jnp.zeros(cells.shape, dtype=jnp.int64)
    for d in range(max_depth, -1, -1):
        is_root = d == root_pos
        root_avail = sub[d] - u[d]
        stored = sub[d] - g[d]
        used = jnp.maximum(0, u[d] - g[d])
        with_max = stored - used + bl[d]
        clamped = jnp.where(bl[d] < NO_LIMIT, jnp.minimum(with_max, avail), avail)
        nonroot_avail = jnp.maximum(0, g[d] - u[d]) + clamped
        new_avail = jnp.where(is_root, root_avail, nonroot_avail)
        avail = jnp.where(valid[d], new_avail, avail)
    return avail


def _bubble_usage(
    path: jnp.ndarray,  # int32[D+1]
    cells: jnp.ndarray,  # int32[C]
    cell_valid: jnp.ndarray,  # bool[C]
    qty: jnp.ndarray,  # int64[C]
    usage: jnp.ndarray,  # int64[N,FR]
    guaranteed: jnp.ndarray,
    max_depth: int,
    apply: jnp.ndarray,  # bool scalar
) -> jnp.ndarray:
    """addUsage bubble-up (resource_node.go:123-144): add qty at the
    leaf, then add each node's over-guaranteed delta to its parent."""
    delta = jnp.where(cell_valid & apply, qty, 0)  # [C]
    ccells = jnp.maximum(cells, 0)
    for d in range(0, max_depth + 1):
        node = jnp.maximum(path[d], 0)
        node_valid = path[d] >= 0
        old = usage[node, ccells]  # [C]
        g = guaranteed[node, ccells]
        new = old + delta
        usage = usage.at[node, ccells].add(jnp.where(node_valid, delta, 0))
        # contribution delta to pass upward
        over_old = jnp.maximum(0, old - g)
        over_new = jnp.maximum(0, new - g)
        delta = jnp.where(node_valid, over_new - over_old, delta)
    return usage


def solve_cycle(
    tree: QuotaTree,
    local_usage: jnp.ndarray,
    heads: HeadsBatch,
    paths: jnp.ndarray,  # int32[N, D+1] from build_paths
) -> SolveResult:
    """One full admission cycle on device.

    Phase 1 picks flavors for all heads in parallel; phase 2 re-checks
    and admits in the reference's entry order — non-borrowing first,
    then priority desc, then queue timestamp (scheduler.go:575-599) —
    against incrementally-updated availability.
    """
    max_depth = tree.max_depth
    subtree, guaranteed = subtree_quota(tree)
    chosen, borrows_wk, preempt_k, skipped = phase1_classify(
        tree, subtree, guaranteed, local_usage, heads
    )

    w = heads.cq_row.shape[0]
    # effective candidate: the fit choice, else the preempt-mode
    # representative — preempt-mode heads participate in entry order so
    # their capacity reservation blocks later borrowers
    # (scheduler.go:228-242)
    eff_k = jnp.where(chosen >= 0, chosen, preempt_k)
    eff_safe = jnp.maximum(eff_k, 0)
    head_borrow = jnp.take_along_axis(borrows_wk, eff_safe[:, None], axis=1)[:, 0]
    head_borrow = head_borrow & (eff_k >= 0)

    # entry order: (borrowing asc, priority desc, timestamp asc); padded
    # or hopeless (NoFit-everywhere) heads sink to the end.
    nofit = eff_k < 0
    order = jnp.lexsort(
        (heads.timestamp, -heads.priority, head_borrow.astype(jnp.int64), nofit.astype(jnp.int64))
    )

    cells_eff = jnp.take_along_axis(
        heads.cells, eff_safe[:, None, None], axis=1
    )[:, 0]  # [W, C]
    qty_eff = jnp.take_along_axis(heads.qty, eff_safe[:, None, None], axis=1)[:, 0]

    # full usage tree as the scan carry (leaf + interior rows)
    usage0 = usage_tree(tree, guaranteed, local_usage)

    def step(usage, wi):
        cq = heads.cq_row[wi]
        cqs = jnp.maximum(cq, 0)
        path = paths[cqs]  # [D+1]
        cells = cells_eff[wi]
        qty = qty_eff[wi]
        ccells = jnp.maximum(cells, 0)
        cell_valid = (cells >= 0) & (qty > 0)

        avail = _avail_along_path(
            path, cells, usage, subtree, guaranteed, tree.borrowing_limit, max_depth
        )
        fits = jnp.all(jnp.where(cell_valid, avail >= qty, True))

        admit = (cq >= 0) & (chosen[wi] >= 0) & fits
        usage = _bubble_usage(
            path, cells, cell_valid, qty, usage, guaranteed, max_depth, admit
        )

        # blocked preempt-mode head: reserve capacity so later entries
        # can't take it (resourcesToReserve, scheduler.go:391-416)
        reserve = (
            (cq >= 0)
            & (chosen[wi] < 0)
            & (preempt_k[wi] >= 0)
            & heads.no_reclaim[wi]
        )
        nominal_c = tree.nominal[cqs, ccells]
        bl_c = tree.borrowing_limit[cqs, ccells]
        leaf_usage_c = usage[cqs, ccells]
        borrow_cap = jnp.where(
            bl_c < NO_LIMIT,
            jnp.minimum(qty, nominal_c + bl_c - leaf_usage_c),
            qty,
        )
        nominal_cap = jnp.maximum(0, jnp.minimum(qty, nominal_c - leaf_usage_c))
        reserve_qty = jnp.where(head_borrow[wi], borrow_cap, nominal_cap)
        usage = _bubble_usage(
            path, cells, cell_valid, reserve_qty, usage, guaranteed,
            max_depth, reserve,
        )
        return usage, (admit, reserve)

    usage_final, (admitted_in_order, reserved_in_order) = lax.scan(
        step, usage0, order
    )

    admitted = jnp.zeros(w, dtype=bool).at[order].set(admitted_in_order)
    reserved = jnp.zeros(w, dtype=bool).at[order].set(reserved_in_order)
    return SolveResult(
        chosen=chosen,
        admitted=admitted,
        borrows=head_borrow,
        reserved=reserved,
        usage=usage_final,
        order=order.astype(jnp.int32),
        skipped=skipped,
    )


solve_cycle_jit = jax.jit(solve_cycle, static_argnames=())


def segmented_rank(seg: jnp.ndarray, valid_sorted: jnp.ndarray) -> jnp.ndarray:
    """Per sorted slot: how many valid same-segment predecessors it has.

    Sort-plus-cumsum formulation — O(W log W) compute, O(W) memory —
    replacing the former W x W pairwise mask, which was quadratic and
    capped the usable head/queue count (~1k) well below the 10k+-CQ
    shapes the drain targets. A stable sort groups slots by segment
    while preserving slot order; within each run the exclusive cumsum of
    the valid flags minus the run-start offset is exactly the pairwise
    rank.
    """
    w = seg.shape[0]
    order2 = jnp.lexsort((jnp.arange(w), seg))  # group by segment, keep slot order
    valid2 = valid_sorted[order2].astype(jnp.int32)
    seg2 = seg[order2]
    excl = jnp.cumsum(valid2) - valid2  # exclusive prefix count of valid
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), seg2[1:] != seg2[:-1]]
    )
    # excl is nondecreasing, so a running max of run-start values always
    # holds the CURRENT run's start offset
    base = lax.cummax(jnp.where(first, excl, -1))
    rank2 = (excl - base).astype(jnp.int32)
    return jnp.zeros(w, dtype=jnp.int32).at[order2].set(rank2)


def solve_cycle_segmented(
    tree: QuotaTree,
    local_usage: jnp.ndarray,
    heads: HeadsBatch,
    paths: jnp.ndarray,  # int32[N, D+1]
    seg_id: jnp.ndarray,  # int32[W] compact root-cohort id per head (-1 pad)
    n_segments: int,  # static: number of distinct live root cohorts (bucketed)
    n_steps: int,  # static: >= max heads per root cohort (bucketed)
) -> SolveResult:
    """Segmented phase-2: independent root cohorts resolve in parallel.

    Heads of ClusterQueues under different cohort roots touch disjoint
    node rows (usage bubbles stay inside their tree), so the sequential
    admit-order scan only has to serialize WITHIN a root. Each scan step
    processes one head per live root — all roots advance together —
    cutting sequential depth from O(W) to O(max heads per root): the
    50-cohort north-star shape runs ~W/50 steps of 50-wide vector work
    instead of W scalar steps.

    ``seg_id`` is the host-compacted root id (build_roots + np.unique),
    so step width is the number of LIVE roots, not the node count.

    Semantics are identical to solve_cycle: within a root, heads process
    in the global entry order (scheduler.go:575-599); across roots the
    interleaving differs but no state is shared, so the admitted set,
    reservations and final usage tree match exactly (property-tested in
    tests/test_assign_kernel.py).
    """
    max_depth = tree.max_depth
    subtree, guaranteed = subtree_quota(tree)
    chosen, borrows_wk, preempt_k, skipped = phase1_classify(
        tree, subtree, guaranteed, local_usage, heads
    )

    w = heads.cq_row.shape[0]
    eff_k = jnp.where(chosen >= 0, chosen, preempt_k)
    eff_safe = jnp.maximum(eff_k, 0)
    head_borrow = jnp.take_along_axis(borrows_wk, eff_safe[:, None], axis=1)[:, 0]
    head_borrow = head_borrow & (eff_k >= 0)

    nofit = eff_k < 0
    order = jnp.lexsort(
        (heads.timestamp, -heads.priority, head_borrow.astype(jnp.int64), nofit.astype(jnp.int64))
    )

    cq = jnp.maximum(heads.cq_row, 0)  # [W]

    # per sorted slot: its segment and whether it does any work
    seg = jnp.maximum(seg_id, 0)[order]  # [W]
    valid_sorted = (heads.cq_row[order] >= 0) & (seg_id[order] >= 0) & (~nofit[order])
    # rank = number of valid same-segment predecessors in sorted order
    rank = segmented_rank(seg, valid_sorted)  # [W]

    # schedule matrix: mat[s, g] = head index processed at step s
    rank_scatter = jnp.where(valid_sorted, rank, n_steps)  # OOB rows drop
    mat = (
        jnp.full((n_steps, n_segments), -1, dtype=jnp.int32)
        .at[rank_scatter, seg]
        .set(order.astype(jnp.int32), mode="drop")
    )

    cells_eff = jnp.take_along_axis(
        heads.cells, eff_safe[:, None, None], axis=1
    )[:, 0]  # [W, C]
    qty_eff = jnp.take_along_axis(heads.qty, eff_safe[:, None, None], axis=1)[:, 0]

    usage0 = usage_tree(tree, guaranteed, local_usage)

    avail_v = jax.vmap(
        _avail_along_path, in_axes=(0, 0, None, None, None, None, None)
    )

    def step(usage, s):
        idx = mat[s]  # [G] head index or -1
        active = idx >= 0
        hidx = jnp.maximum(idx, 0)
        cqs = cq[hidx]  # [G]
        path = paths[cqs]  # [G, D+1]
        cells = cells_eff[hidx]  # [G, C]
        qty = qty_eff[hidx]
        ccells = jnp.maximum(cells, 0)
        cell_valid = (cells >= 0) & (qty > 0) & active[:, None]

        avail = avail_v(
            path, cells, usage, subtree, guaranteed, tree.borrowing_limit, max_depth
        )  # [G, C]
        fits = jnp.all(jnp.where(cell_valid, avail >= qty, True), axis=1)

        admit = active & (chosen[hidx] >= 0) & fits
        reserve = (
            active
            & (chosen[hidx] < 0)
            & (preempt_k[hidx] >= 0)
            & heads.no_reclaim[hidx]
        )
        nominal_c = tree.nominal[cqs[:, None], ccells]  # [G, C]
        bl_c = tree.borrowing_limit[cqs[:, None], ccells]
        leaf_usage_c = usage[cqs[:, None], ccells]
        borrow_cap = jnp.where(
            bl_c < NO_LIMIT,
            jnp.minimum(qty, nominal_c + bl_c - leaf_usage_c),
            qty,
        )
        nominal_cap = jnp.maximum(0, jnp.minimum(qty, nominal_c - leaf_usage_c))
        reserve_qty = jnp.where(head_borrow[hidx][:, None], borrow_cap, nominal_cap)

        delta = jnp.where(
            cell_valid & admit[:, None],
            qty,
            jnp.where(cell_valid & reserve[:, None], reserve_qty, 0),
        )  # [G, C]

        # vectorized addUsage bubble-up: slots touch disjoint trees, so
        # one scatter-add per level is conflict-free across slots
        for d in range(0, max_depth + 1):
            node = jnp.maximum(path[:, d], 0)  # [G]
            node_valid = (path[:, d] >= 0)[:, None]
            old = usage[node[:, None], ccells]  # [G, C]
            g = guaranteed[node[:, None], ccells]
            new = old + delta
            usage = usage.at[node[:, None], ccells].add(
                jnp.where(node_valid, delta, 0)
            )
            over_old = jnp.maximum(0, old - g)
            over_new = jnp.maximum(0, new - g)
            delta = jnp.where(node_valid, over_new - over_old, delta)
        return usage, (admit, reserve)

    usage_final, (admit_sn, reserve_sn) = lax.scan(
        step, usage0, jnp.arange(n_steps)
    )

    # scatter [S, G] step outcomes back onto heads
    flat_idx = mat.reshape(-1)
    safe_idx = jnp.where(flat_idx >= 0, flat_idx, w)  # OOB drops
    admitted = (
        jnp.zeros(w, dtype=bool).at[safe_idx].set(admit_sn.reshape(-1), mode="drop")
    )
    reserved = (
        jnp.zeros(w, dtype=bool).at[safe_idx].set(reserve_sn.reshape(-1), mode="drop")
    )
    return SolveResult(
        chosen=chosen,
        admitted=admitted,
        borrows=head_borrow,
        reserved=reserved,
        usage=usage_final,
        order=order.astype(jnp.int32),
        skipped=skipped,
    )


solve_cycle_segmented_jit = jax.jit(
    solve_cycle_segmented, static_argnames=("n_segments", "n_steps")
)


def _solve_cycle_segmented_packed(
    tree, local_usage, heads, paths, seg_id, n_segments: int, n_steps: int
):
    """solve_cycle_segmented with the per-head outputs stacked into ONE
    int64[6, W] tensor, so the host retrieves the whole cycle outcome in
    a single device->host fetch (each fetch pays a full round trip)."""
    r = solve_cycle_segmented(
        tree, local_usage, heads, paths, seg_id, n_segments, n_steps
    )
    packed = jnp.stack(
        [
            r.chosen.astype(jnp.int64),
            r.admitted.astype(jnp.int64),
            r.borrows.astype(jnp.int64),
            r.reserved.astype(jnp.int64),
            r.order.astype(jnp.int64),
            r.skipped.astype(jnp.int64),
        ]
    )
    return packed


solve_cycle_segmented_packed_jit = jax.jit(
    _solve_cycle_segmented_packed, static_argnames=("n_segments", "n_steps")
)
