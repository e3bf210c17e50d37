"""Host glue lowering a Snapshot + cycle heads into the batched solver.

This is the boundary between the object-model world (core/) and the
dense-tensor world (ops/assign_kernel.py). It mirrors the candidate
enumeration the host FlavorAssigner performs sequentially — flavor
eligibility (taints, node-selector labels), resume-from-cursor
(LastAssignment), default-fungibility ordering — but emits it as a
padded (W x K x C) tensor batch the TPU consumes in one dispatch.

A head of a ``whenCanBorrow: TryNextFlavor`` ClusterQueue carries one
bit per resource group on its candidate cells (``Lowered.try_next``):
the kernel then prefers, group by group, a flavor that fits without
borrowing over an earlier one that fits only by borrowing.

Heads the dense formulation cannot represent exactly fall back to the
host authority path and are reported in ``Lowered.fallback``:
  - multi-podset workloads (the reference assigns flavors per podset;
    aggregation would force one flavor for all podsets),
  - ``whenCanPreempt: Preempt`` (a walk may stop at a preempt-mode
    flavor before a later one that fits), and ``whenCanBorrow:
    TryNextFlavor`` under a scoring admission policy,
  - candidate fan-out beyond the static K.
This matches the design stance in SURVEY.md §7: the batched solver
resolves the Fit/NoFit majority; preemption-mode nomination stays host.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kueue_tpu.models import ResourceFlavor, Workload
from kueue_tpu.models.cluster_queue import ClusterQueue
from kueue_tpu.models.constants import FlavorFungibilityPolicy
from kueue_tpu.models.resource_flavor import flavor_eligible, group_label_keys
from kueue_tpu.core.snapshot import Snapshot
from kueue_tpu.core.workload_info import effective_podset_count, quota_per_pod
from kueue_tpu.resources import PODS, FlavorResource
from kueue_tpu.tracing.phases import Phase, phase
from kueue_tpu.utils.priority import priority_of

# the per-cycle solve's device round trip (tracing/phases.py)
_LAUNCH = Phase("cycle.launch", counter="launches")
_FETCH = Phase("cycle.fetch")


@dataclass
class Lowered:
    """Dense batch + bookkeeping to map results back to workloads."""

    cq_row: np.ndarray  # int32[W]
    cells: np.ndarray  # int32[W,K,C]
    qty: np.ndarray  # int64[W,K,C]
    valid: np.ndarray  # bool[W,K]
    priority: np.ndarray  # int64[W]
    timestamp: np.ndarray  # int64[W] (ns)
    no_reclaim: np.ndarray  # bool[W] — reserve capacity when blocked
    # int64[W,K] admission-policy candidate scores (kueue_tpu/policy:
    # annotate_lowered compiles them from workload labels); None = the
    # default first-fit policy (pack_heads ships zeros, the kernel's
    # score-argmax then IS the first-fit argmax)
    score: Optional[np.ndarray] = None
    # int8[W,K,C]: for a head of a whenCanBorrow TryNextFlavor queue,
    # each candidate cell's resource-group bit (1 << group); 0 for every
    # other head, whose first fitting candidate wins as before
    try_next: Optional[np.ndarray] = None

    # per head: candidate k -> flavor name chosen per resource group
    candidate_flavors: List[List[Dict[str, str]]] = field(default_factory=list)
    # per head: candidate k -> resource -> host-equivalent tried-flavor
    # cursor (LastAssignment idx; -1 when the chosen flavor is the last
    # of its resource group, matching _find_flavor_for_resource)
    candidate_tried: List[List[Dict[str, int]]] = field(default_factory=list)
    heads: List[Workload] = field(default_factory=list)
    cq_names: List[str] = field(default_factory=list)
    fallback: List[int] = field(default_factory=list)  # indices into input heads
    # per head: number of resource groups its request touches
    n_groups: List[int] = field(default_factory=list)
    # heads of a ClusterQueue with non-default fungibility offered to
    # the lowering, and those of them lowered onto the device
    fungible_heads: int = 0
    fungible_lowered: int = 0
    # heads lowered with a resume cursor (a LastAssignment that starts
    # some resource group's walk past its first flavor)
    resumed: int = 0



def _default_fungibility(cq: ClusterQueue) -> bool:
    ff = cq.flavor_fungibility
    return (
        ff.when_can_borrow == FlavorFungibilityPolicy.BORROW
        and ff.when_can_preempt == FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
    )


def _borrow_tries_next(cq: ClusterQueue) -> bool:
    """whenCanBorrow TryNextFlavor with whenCanPreempt TryNextFlavor:
    the one non-default fungibility the cycle kernel represents."""
    ff = cq.flavor_fungibility
    return (
        ff.when_can_borrow == FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
        and ff.when_can_preempt == FlavorFungibilityPolicy.TRY_NEXT_FLAVOR
    )


# a group bit must fit the int8 try_next cells
_MAX_TRY_NEXT_GROUPS = 7


class _Template:
    """Quantity-independent lowering of one (CQ, podset shape, cursor)
    combination — the candidate enumeration is identical for every
    workload sharing it, so bulk lowering (50k-pending drains) builds it
    once and only fills per-workload quantities."""

    __slots__ = (
        "fallback",
        "n_groups",
        "cq_row",
        "no_reclaim",
        "candidates",
        "any_valid",
        "cells_arr",
        "valid_row",
        "qty_sel",
        "cgrp_arr",
        "try_next_arr",
        "res_names",
        "flavor_list",
        "tried_list",
        "group_list",
        "group_sizes",
    )

    def __init__(self):
        self.fallback = False
        self.n_groups = 0
        self.cq_row = -1
        self.no_reclaim = False
        # per candidate slot: None (invalid) or
        # (cell_js, cell_resources, flavor_map, tried_map)
        self.candidates: List = []
        self.any_valid = False
        # dense per-template rows shared by every head using it:
        # cells_arr int32[K,C]; valid_row bool[K]; qty_sel int32[K,C]
        # indexes a per-head request vector laid out as res_names + [0]
        self.cells_arr = None
        self.valid_row = None
        self.qty_sel = None
        # int8[K,C]: resource-group index of each candidate cell (-1 pad)
        self.cgrp_arr = None
        # int8[K,C]: each cell's resource-group bit (0 pad), the
        # try_next rows of a whenCanBorrow TryNextFlavor head
        self.try_next_arr = None
        self.res_names: Tuple[str, ...] = ()
        self.flavor_list: List[Dict[str, str]] = []
        self.tried_list: List[Dict[str, int]] = []
        # per candidate: tuple per group of (flavor idx in rg.flavors,
        # is-last-flavor flag); empty tuple for invalid candidates
        self.group_list: List[tuple] = []
        # full walk length (len(rg.flavors)) per touched group — sizes
        # the drain's convergent-retry odometer bound
        self.group_sizes: Tuple[int, ...] = ()


def _podset_sig(ps, per_pod) -> tuple:
    sel = tuple(sorted(ps.node_selector.items())) if ps.node_selector else ()
    return (tuple(sorted(per_pod)), sel, tuple(ps.tolerations))


def _build_template(
    snapshot: Snapshot,
    cq,
    cq_name: str,
    ps,
    per_pod: Dict[str, int],  # quota-view requests (overhead+transform)
    starts: Tuple[int, ...],
    flavors: Dict[str, ResourceFlavor],
    k: int,
    c: int,
    allow_tas: bool = False,
) -> _Template:
    t = _Template()

    # resource groups touched by this podset, in CQ order (names only —
    # quantities are per-workload)
    touched: List[Tuple[object, List[str]]] = []
    for rg in cq.resource_groups:
        rg_res = [r for r in sorted(per_pod) if r in rg.covered_resources]
        if PODS in rg.covered_resources:
            rg_res.append(PODS)
        if rg_res:
            touched.append((rg, sorted(rg_res)))
    covered = {r for rg, _ in touched for r in rg.covered_resources}
    if any(r not in covered for r in per_pod):
        t.fallback = True  # resource not covered: host reports it
        return t
    t.n_groups = len(touched)
    t.group_sizes = tuple(len(rg.flavors) for rg, _ in touched)

    per_rg: List[List[Tuple[str, int]]] = []
    for gidx, (rg, rg_res) in enumerate(touched):
        label_keys = group_label_keys(rg.flavors, flavors)
        start = starts[gidx] if gidx < len(starts) else 0
        n_flavors = len(rg.flavors)
        options: List[Tuple[str, int]] = []
        for gi in range(start, n_flavors):
            fq = rg.flavors[gi]
            flavor = flavors.get(fq.name)
            if (
                not allow_tas
                and flavor is not None
                and flavor.topology_name is not None
            ):
                # TAS flavors (incl. implied TAS on TAS-only CQs)
                # need topology placement — host path only, unless the
                # caller is the TAS drain (run_drain_tas), which does
                # the placement in kernel
                t.fallback = True
                return t
            if flavor_eligible(flavor, ps, label_keys):
                # host cursor semantics: a FIT at the group's last
                # flavor stores -1 (restart from 0 next time)
                last = gi == n_flavors - 1
                tried = -1 if last else gi
                options.append((fq.name, tried, gi, last))
        if not options:
            t.fallback = True
            return t
        per_rg.append(options)

    n_cand = 1
    for options in per_rg:
        n_cand *= len(options)
    n_cells = sum(len(rg_res) for _, rg_res in touched)
    if n_cand > k or n_cells > c:
        t.fallback = True
        return t

    # cartesian product across RGs in reference order (first RG's
    # flavor walk is the outer loop — matches the sequential search
    # trying RG1 flavors fully per RG0 choice)
    combos: List[List[tuple]] = [[]]
    for gidx, options in enumerate(per_rg):
        combos = [
            prev + [(gidx, f, tr, gi, lastf)]
            for prev in combos
            for (f, tr, gi, lastf) in options
        ]

    from kueue_tpu.core.preemption import can_always_reclaim

    t.cq_row = snapshot.row(cq_name)
    t.no_reclaim = not can_always_reclaim(cq)
    for combo in combos:
        cell_js: List[int] = []
        cell_rs: List[str] = []
        cell_gs: List[int] = []
        flavor_map: Dict[str, str] = {}
        tried_map: Dict[str, int] = {}
        gvec: List[tuple] = []
        ok = True
        for gidx, fname, tried, gi, lastf in combo:
            gvec.append((gi, lastf))
            for r in touched[gidx][1]:
                j = snapshot.fr_index.get(FlavorResource(fname, r))
                if j is None:
                    ok = False
                    break
                cell_js.append(j)
                cell_rs.append(r)
                cell_gs.append(gidx)
                flavor_map[r] = fname
                tried_map[r] = tried
            if not ok:
                break
        if ok:
            t.candidates.append(
                (tuple(cell_js), tuple(cell_rs), flavor_map, tried_map, tuple(cell_gs))
            )
            t.group_list.append(tuple(gvec))
            t.any_valid = True
        else:
            t.candidates.append(None)
            t.group_list.append(())
    if not t.any_valid:
        t.fallback = True
        return t

    # dense rows for the vectorized per-head fill
    res_names = tuple(sorted({r for _, rg_res in touched for r in rg_res}))
    r_idx = {r: x for x, r in enumerate(res_names)}
    t.res_names = res_names
    t.cells_arr = np.full((k, c), -1, dtype=np.int32)
    t.valid_row = np.zeros(k, dtype=bool)
    # unused cell slots select the trailing 0 of the request vector
    t.qty_sel = np.full((k, c), len(res_names), dtype=np.int32)
    t.cgrp_arr = np.full((k, c), -1, dtype=np.int8)
    for ki, cand in enumerate(t.candidates):
        if cand is None:
            t.flavor_list.append({})
            t.tried_list.append({})
            continue
        cell_js, cell_rs, flavor_map, tried_map, cell_gs = cand
        for ci, (j, r, cg) in enumerate(zip(cell_js, cell_rs, cell_gs)):
            t.cells_arr[ki, ci] = j
            t.qty_sel[ki, ci] = r_idx[r]
            t.cgrp_arr[ki, ci] = cg
        t.valid_row[ki] = True
        t.flavor_list.append(flavor_map)
        t.tried_list.append(tried_map)
    if t.n_groups <= _MAX_TRY_NEXT_GROUPS:
        t.try_next_arr = np.where(
            t.cgrp_arr >= 0, np.left_shift(1, np.maximum(t.cgrp_arr, 0)), 0
        ).astype(np.int8)
    return t


def _resolve_starts(cq, per_pod, state, ps_idx: int) -> Tuple[int, ...]:
    """Per-resource-group cursor starts from a workload's
    LastAssignment (AssignmentState.next_flavor_to_try) — ONE
    definition shared by the cycle and drain lowerings."""
    if state is None:
        return ()
    starts_l = []
    for rg in cq.resource_groups:
        rg_res = [r for r in sorted(per_pod) if r in rg.covered_resources]
        if PODS in rg.covered_resources:
            rg_res.append(PODS)
        if rg_res:
            starts_l.append(state.next_flavor_to_try(ps_idx, sorted(rg_res)[0]))
    return tuple(starts_l)


def resumed_assignment(cq, wl, walks, generation: int):
    """The LastAssignment that makes ``_resolve_starts`` return
    ``walks[p]`` (int[P, G] next flavor per touched resource group) for
    every podset of ``wl`` — how a drain cut off mid-walk hands a head's
    flavor walk to the next round's lowering, as the host cycle loop
    keeps it across cycles."""
    from kueue_tpu.core.flavor_assigner import AssignmentState

    tried = []
    for p, ps in enumerate(wl.pod_sets):
        per_pod = quota_per_pod(ps)
        by_res: Dict[str, int] = {}
        g = 0
        for rg in cq.resource_groups:
            rg_res = [r for r in sorted(per_pod) if r in rg.covered_resources]
            if PODS in rg.covered_resources:
                rg_res.append(PODS)
            if rg_res:
                for r in rg_res:
                    by_res[r] = int(walks[p, g]) - 1
                g += 1
        tried.append(by_res)
    return AssignmentState(
        last_tried_flavor_idx=tried, cluster_queue_generation=generation
    )


def lower_heads(
    snapshot: Snapshot,
    heads: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 16,
    timestamp_fn=None,
    transform=None,  # ResourceTransformConfig for the quota view
    try_next: bool = True,  # lower whenCanBorrow TryNextFlavor heads
) -> Lowered:
    """Build the dense head batch for the INTERACTIVE cycle path; route
    inexpressible heads to ``fallback`` (handled by the host
    FlavorAssigner). The drain lowers via lower_heads_multi, which also
    carries multi-podset, fungibility-policy and cursor-vector inputs.
    With ``try_next`` False (a scoring admission policy ranks the
    flavors instead) whenCanBorrow TryNextFlavor heads fall back too.

    Candidate enumeration is memoized per (CQ, podset shape, cursor):
    a bulk backlog over 1k CQs lowers in O(templates + heads), not
    O(heads x flavors)."""
    w = len(heads)
    k, c = max_candidates, max_cells
    out = Lowered(
        cq_row=np.full(w, -1, dtype=np.int32),
        cells=np.full((w, k, c), -1, dtype=np.int32),
        qty=np.zeros((w, k, c), dtype=np.int64),
        valid=np.zeros((w, k), dtype=bool),
        priority=np.zeros(w, dtype=np.int64),
        timestamp=np.zeros(w, dtype=np.int64),
        no_reclaim=np.zeros(w, dtype=bool),
        try_next=np.zeros((w, k, c), dtype=np.int8),
    )
    templates: Dict[tuple, _Template] = {}
    # template key -> (template, head indexes, per-head (per_pod, count),
    # whenCanBorrow TryNextFlavor)
    groups: Dict[tuple, tuple] = {}

    for i, (wl, cq_name) in enumerate(heads):
        out.heads.append(wl)
        out.cq_names.append(cq_name)
        out.candidate_flavors.append([])
        out.candidate_tried.append([])
        out.n_groups.append(0)
        if cq_name not in snapshot.cq_models:
            out.fallback.append(i)
            continue
        cq = snapshot.cq_models[cq_name]
        tries_next = False
        if not _default_fungibility(cq):
            out.fungible_heads += 1
            tries_next = try_next and _borrow_tries_next(cq)
            if not tries_next:
                out.fallback.append(i)
                continue
        if len(wl.pod_sets) != 1:
            out.fallback.append(i)
            continue
        ps = wl.pod_sets[0]
        if ps.topology_request is not None:
            out.fallback.append(i)  # TAS placement stays on the host path
            continue
        per_pod = quota_per_pod(ps, transform)

        # per-RG cursor starts (LastAssignment resume)
        state = wl.last_assignment
        gen = snapshot.generations.get(cq_name, 0)
        if state is not None and gen > state.cluster_queue_generation:
            state = None
        starts = _resolve_starts(cq, per_pod, state, 0)

        key = (cq_name, _podset_sig(ps, per_pod), starts)
        t = templates.get(key)
        if t is None:
            t = _build_template(
                snapshot, cq, cq_name, ps, per_pod, starts, flavors, k, c
            )
            templates[key] = t
        out.n_groups[i] = t.n_groups
        if t.fallback or (tries_next and t.try_next_arr is None):
            out.fallback.append(i)
            continue
        out.fungible_lowered += tries_next
        out.resumed += any(starts)

        count = effective_podset_count(wl, ps)

        out.no_reclaim[i] = t.no_reclaim
        out.priority[i] = priority_of(wl, snapshot.priority_classes)
        ts = timestamp_fn(wl) if timestamp_fn else wl.creation_time
        out.timestamp[i] = int(ts * 1e9)
        # shared read-only maps (one list per template, not per head)
        out.candidate_flavors[i] = t.flavor_list
        out.candidate_tried[i] = t.tried_list
        # defer the array fills: heads sharing a template batch into ONE
        # numpy op per field instead of four small ops per head (the
        # per-head fills dominated bulk-drain lowering wall time)
        group = groups.get(key)
        if group is None:
            group = groups[key] = (t, [], [], tries_next)
        group[1].append(i)
        group[2].append((per_pod, count))

    for t, idxs, pcs, tries_next in groups.values():
        ii = np.asarray(idxs, dtype=np.intp)
        out.cq_row[ii] = t.cq_row
        out.cells[ii] = t.cells_arr
        out.valid[ii] = t.valid_row
        if tries_next:
            out.try_next[ii] = t.try_next_arr
        # request matrix: rows = heads in this group, cols = the
        # template's resource order (+1 zero column for padding cells)
        rmat = np.zeros((len(ii), len(t.res_names) + 1), dtype=np.int64)
        for x, r in enumerate(t.res_names):
            if r == PODS:
                rmat[:, x] = [count for (_, count) in pcs]
            else:
                rmat[:, x] = [pp.get(r, 0) * count for (pp, count) in pcs]
        out.qty[ii] = rmat[:, t.qty_sel]
    return out


def tree_arrays(snapshot: Snapshot):
    """(QuotaTree, paths, roots) device inputs from a Snapshot, via the
    shared snapshot->array codec (core/encode.py) — the ONE encoding
    the cycle dispatch, the drain and the planner all consume."""
    from kueue_tpu.core.encode import device_arrays, encode_snapshot

    return device_arrays(encode_snapshot(snapshot))


class ResidentCycleState:
    """Device-resident quota tensors for the interactive cycle path.

    The interactive scheduler's device dispatch used to re-ship the
    whole quota tree + usage matrix every cycle; each transfer adds to
    the interactive round trip and pushes the auto-gate's crossover to
    larger head counts. The tree changes rarely (quota/config edits) and
    usage changes touch a few ClusterQueue rows per cycle
    (admissions/evictions/finishes), so both stay RESIDENT on the
    device between cycles: per cycle the host compares the fresh
    snapshot against its copy of the device content and ships only the
    changed usage rows (scatter with a donated buffer — no device-side
    copy), re-uploading everything only when the structure fingerprint
    (row order, cell universe, quota values, cohort edges) changes.
    The heads batch still ships per cycle: it IS the cycle's input.
    """

    def __init__(self):
        self._names = None
        self._parent = None
        self._quota_key = None  # (nominal, lending, borrowing) copies
        self._tree = None
        self._paths = None
        self._roots = None
        self._usage = None  # device [N, FR]
        self._usage_host = None  # numpy mirror of the device content
        # telemetry (debugging)
        self.full_uploads = 0
        self.delta_cycles = 0
        self.delta_rows = 0

    def _structure_matches(self, snapshot: Snapshot) -> bool:
        import numpy as np

        if self._names != tuple(snapshot.flat.cq_names):
            return False
        if self._usage_host is None or (
            self._usage_host.shape != snapshot.local_usage.shape
        ):
            return False
        if not np.array_equal(self._parent, snapshot.flat.parent):
            return False
        nom, lend, bor = self._quota_key
        return (
            np.array_equal(nom, snapshot.nominal)
            and np.array_equal(lend, snapshot.lending_limit)
            and np.array_equal(bor, snapshot.borrowing_limit)
        )

    def refresh(self, snapshot: Snapshot):
        """(tree, paths, roots, usage_dev) with minimal transfer."""
        import numpy as np

        from kueue_tpu._jax import jnp

        if not self._structure_matches(snapshot):
            self._tree, self._paths, self._roots = tree_arrays(snapshot)
            # a copy: on the CPU backend jnp.asarray may alias the numpy
            # buffer, and the snapshot's usage is mutated by the admit
            # loop while this buffer stays resident
            self._usage = jnp.asarray(snapshot.local_usage.copy())
            self._usage_host = snapshot.local_usage.copy()
            self._names = tuple(snapshot.flat.cq_names)
            self._parent = np.array(snapshot.flat.parent, copy=True)
            self._quota_key = (
                snapshot.nominal.copy(),
                snapshot.lending_limit.copy(),
                snapshot.borrowing_limit.copy(),
            )
            self.full_uploads += 1
            return self._tree, self._paths, self._roots, self._usage

        new = snapshot.local_usage
        changed = (new != self._usage_host).any(axis=1)
        idx = np.flatnonzero(changed)
        if idx.size:
            if idx.size > max(16, new.shape[0] // 4):
                # bulk change: a fresh upload beats a huge scatter (a
                # copy, as above)
                self._usage = jnp.asarray(new.copy())
            else:
                # bucket the delta width (pad by repeating the first
                # changed row — idempotent under .set) so the scatter
                # jit compiles once per bucket, not once per distinct
                # changed-row count
                n = _bucket(int(idx.size), minimum=4)
                idx_p = np.concatenate(
                    [idx, np.full(n - idx.size, idx[0], dtype=idx.dtype)]
                ).astype(np.int32)
                rows_p = new[idx_p]
                self._usage = _scatter_rows_jit()(
                    self._usage, jnp.asarray(idx_p), jnp.asarray(rows_p)
                )
            self._usage_host = new.copy()
            self.delta_rows += int(idx.size)
        self.delta_cycles += 1
        return self._tree, self._paths, self._roots, self._usage


def _scatter_rows(usage, idx, rows):
    return usage.at[idx].set(rows)


_SCATTER_JIT = None


def _scatter_rows_jit():
    """Lazy jit (module stays importable without configuring JAX);
    donating the resident buffer updates it in place on device."""
    global _SCATTER_JIT
    if _SCATTER_JIT is None:
        from kueue_tpu._jax import jax

        _SCATTER_JIT = jax.jit(_scatter_rows, donate_argnums=(0,))
    return _SCATTER_JIT


def _bucket(w: int, minimum: int = 64) -> int:
    """Round the head count up to a power-of-two bucket so the jit
    solver compiles once per bucket, not once per distinct head count
    (workload arrival is continuous; XLA shapes are static)."""
    n = minimum
    while n < w:
        n *= 2
    return n


def pack_heads(lowered: Lowered, roots, w_pad: int):
    """Pad a lowered head batch to ``w_pad`` rows and derive the
    segmented phase-2 schedule inputs. Shared by the cycle dispatch and
    the planner's scenario batch so the two cannot disagree on padding
    or segment compaction. Returns numpy
    (HeadsBatch, seg_id, n_segments, n_steps)."""
    import numpy as np

    from kueue_tpu.ops.assign_kernel import HeadsBatch

    w = len(lowered.heads)
    cq_row, cells, qty = lowered.cq_row, lowered.cells, lowered.qty
    valid, priority = lowered.valid, lowered.priority
    timestamp, no_reclaim = lowered.timestamp, lowered.no_reclaim
    # policy score tensor: always shipped as a real array (zeros = the
    # default first-fit policy) so every consumer — mesh placement,
    # the planner's vmapped sweep, the host mirror — sees one pytree
    score = lowered.score
    if score is None:
        score = np.zeros(valid.shape, dtype=np.int64)
    try_next = lowered.try_next
    if try_next is None:
        try_next = np.zeros(cells.shape, dtype=np.int8)
    if w_pad > w:
        pad = w_pad - w
        cq_row = np.concatenate([cq_row, np.full(pad, -1, dtype=np.int32)])
        cells = np.concatenate(
            [cells, np.full((pad,) + cells.shape[1:], -1, dtype=np.int32)]
        )
        qty = np.concatenate([qty, np.zeros((pad,) + qty.shape[1:], dtype=np.int64)])
        valid = np.concatenate([valid, np.zeros((pad,) + valid.shape[1:], dtype=bool)])
        priority = np.concatenate([priority, np.zeros(pad, dtype=np.int64)])
        timestamp = np.concatenate([timestamp, np.zeros(pad, dtype=np.int64)])
        no_reclaim = np.concatenate([no_reclaim, np.zeros(pad, dtype=bool)])
        score = np.concatenate(
            [score, np.zeros((pad,) + score.shape[1:], dtype=np.int64)]
        )
        try_next = np.concatenate(
            [try_next, np.zeros((pad,) + try_next.shape[1:], dtype=np.int8)]
        )
    batch_np = HeadsBatch(
        cq_row=cq_row, cells=cells, qty=qty, valid=valid,
        priority=priority, timestamp=timestamp, no_reclaim=no_reclaim,
        score=score, try_next=try_next,
    )
    # compact segment ids: one per LIVE root cohort; the max head count
    # within one root bounds phase-2's sequential depth
    seg_id = np.full(w_pad, -1, dtype=np.int32)
    live_mask = cq_row >= 0
    if live_mask.any():
        uniq, inv = np.unique(roots[cq_row[live_mask]], return_inverse=True)
        seg_id[live_mask] = inv.astype(np.int32)
        n_segments = _bucket(len(uniq), minimum=8)
        n_steps = _bucket(int(np.bincount(inv).max()), minimum=8)
    else:
        n_segments = n_steps = 8
    return batch_np, seg_id, n_segments, n_steps


def dispatch_lowered(
    snapshot: Snapshot,
    lowered: Lowered,
    pad_heads: bool = True,
    mesh=None,  # jax.sharding.Mesh: shard heads along "wl"
    resident: Optional[ResidentCycleState] = None,
    phases=None,  # tracing.phase sink (the cycle's CycleTrace)
):
    """Ship an already-lowered batch to the segmented device solver.

    Padding rows (cq_row == -1) are inert in both solver phases, so the
    first ``len(lowered.heads)`` result entries map 1:1 onto the input
    heads. The phase-2 step bound is the max head count in any root
    cohort (independent roots resolve in parallel), bucketed so the jit
    caches per bucket.

    With ``resident`` (single-device interactive path) the quota tree,
    paths and usage matrix stay device-resident between cycles and the
    host ships only changed usage rows — the heads batch is the only
    per-cycle payload besides the deltas.

    Returns a HOST-side SolveResult (numpy arrays, usage omitted):
    all per-head outputs come back in one packed fetch, because every
    device->host retrieval pays a full round trip and the scheduler
    reads several fields per cycle.

    ``phases`` receives two regions: ``cycle.launch`` (the resident
    refresh, packing, transfers and the launch, up to the return of the
    jitted call) and ``cycle.fetch`` (the blocking fetch).
    """
    import numpy as np

    from kueue_tpu._jax import jnp
    from kueue_tpu.ops.assign_kernel import (
        HeadsBatch,
        SolveResult,
        solve_cycle_segmented_packed_jit,
    )

    with phase(_LAUNCH, phases) as launch:
        w = len(lowered.heads)
        w_pad = _bucket(w) if pad_heads else w
        if mesh is not None:
            # W must divide the mesh's wl axis (uneven device_put
            # shards are rejected); power-of-two buckets already do
            # for power-of-two meshes, this covers the rest
            from kueue_tpu.parallel.sharded_solver import pad_w_multiple

            w_pad = pad_w_multiple(w_pad, mesh.shape["wl"])
        usage_resident = None
        if resident is not None and mesh is None:
            tree, paths, roots, usage_resident = resident.refresh(snapshot)
        else:
            tree, paths, roots = tree_arrays(snapshot)
        batch_np, seg_id, n_segments, n_steps = pack_heads(
            lowered, roots, w_pad
        )
        if mesh is not None:
            # numpy -> device_put straight onto the shards (one
            # transfer, no staging of the full batch on a single device)
            from kueue_tpu.parallel.sharded_solver import place_cycle_inputs

            tree, usage_in, batch, paths, seg_in = place_cycle_inputs(
                mesh, tree, snapshot.local_usage, batch_np, paths, seg_id
            )
        else:
            batch = HeadsBatch(*(jnp.asarray(x) for x in batch_np))
            usage_in = (
                usage_resident
                if usage_resident is not None
                else jnp.asarray(snapshot.local_usage)
            )
            seg_in = jnp.asarray(seg_id)
        out = solve_cycle_segmented_packed_jit(
            tree,
            usage_in,
            batch,
            paths,
            seg_in,
            n_segments=n_segments,
            n_steps=n_steps,
        )
        launch.count = 1
    with phase(_FETCH, phases):
        # ONE device->host round trip for the whole cycle outcome
        packed = np.asarray(out)
    return SolveResult(
        chosen=packed[0].astype(np.int32),
        admitted=packed[1].astype(bool),
        borrows=packed[2].astype(bool),
        reserved=packed[3].astype(bool),
        usage=None,
        order=packed[4].astype(np.int32),
        skipped=packed[5].astype(bool),
    )


def solve_heads(
    snapshot: Snapshot,
    heads: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 16,
    timestamp_fn=None,
    pad_heads: bool = True,
):
    """One-call convenience: lower, dispatch, return (Lowered, SolveResult)."""
    lowered = lower_heads(
        snapshot, heads, flavors, max_candidates, max_cells, timestamp_fn
    )
    return lowered, dispatch_lowered(snapshot, lowered, pad_heads)


@dataclass
class MultiLowered:
    """Dense multi-podset head batch for the drain: the single-podset
    layout with an extra P axis (podsets padded to a common max). A
    workload's podsets nominate SEQUENTIALLY in the kernel — the
    host couples them only through assignment_usage at shared
    (flavor, resource) cells, so each podset keeps its own candidate
    template, cursor vector, and walk."""

    cq_row: np.ndarray  # int32[W]
    n_podsets: np.ndarray  # int32[W]
    cells: np.ndarray  # int32[W,P,K,C]
    qty: np.ndarray  # int64[W,P,K,C]
    valid: np.ndarray  # bool[W,P,K]
    cgrp: np.ndarray  # int8[W,P,K,C]
    priority: np.ndarray  # int64[W]
    timestamp: np.ndarray  # int64[W]
    no_reclaim: np.ndarray  # bool[W]
    ffb: np.ndarray  # bool[W]
    ffp: np.ndarray  # bool[W]
    # int64[W,P,K] admission-policy candidate scores (kueue_tpu/policy:
    # annotate_multi); None = default first-fit (plan_drain ships zeros)
    score: Optional[np.ndarray] = None
    # per head per podset: candidate k -> maps (template-shared lists)
    candidate_flavors: List[List[list]] = field(default_factory=list)
    candidate_groups: List[List[list]] = field(default_factory=list)
    heads: List[Workload] = field(default_factory=list)
    cq_names: List[str] = field(default_factory=list)
    fallback: List[int] = field(default_factory=list)
    n_groups: List[int] = field(default_factory=list)  # max over podsets
    # per head: number of distinct joint cursor states its podsets' walk
    # odometer can take — prod over podsets of prod over groups of
    # (walk length + 1); a CONVERGENT PendingFlavors retry sequence
    # cannot exceed it, so it is the sound stuck-detection budget
    walk_states: List[int] = field(default_factory=list)
    # heads lowered with a resume cursor in some podset's walk
    resumed: int = 0
    # head index -> per podset, the resume cursor of each touched
    # resource group, for each ClusterQueue's first head that carries
    # one (its template enumerates the whole walk)
    head_starts: Dict[int, List[Tuple[int, ...]]] = field(default_factory=dict)


def lower_heads_multi(
    snapshot: Snapshot,
    heads: Sequence[Tuple[Workload, str]],
    flavors: Dict[str, ResourceFlavor],
    max_candidates: int = 8,
    max_cells: int = 16,
    max_podsets: int = 4,
    timestamp_fn=None,
    transform=None,
    any_fungibility: bool = True,
    allow_tas: bool = False,
) -> MultiLowered:
    """lower_heads generalized over podsets (drain path).

    Cursor starts resolve per (podset index, resource) from the
    workload's LastAssignment, exactly like the host's
    AssignmentState.next_flavor_to_try. The drain kernels start each
    queue's walk at ``DrainQueues.g_start0``, so the first head of each
    ClusterQueue lowers its whole walk and reports its cursor in
    ``head_starts``: a walk that restarts at the first flavor in the
    drain still finds it. A later head that carries a cursor falls
    back."""
    w = len(heads)
    k, c = max_candidates, max_cells
    # size the podset axis to what the batch actually needs: the
    # common all-single-podset backlog must not pay 4x the memory and
    # memset of a padded axis
    pmax = max(
        [1]
        + [
            len(wl.pod_sets)
            for wl, cqn in heads
            if len(wl.pod_sets) <= max_podsets
            and cqn in snapshot.cq_models
        ]
    )
    out = MultiLowered(
        cq_row=np.full(w, -1, dtype=np.int32),
        n_podsets=np.zeros(w, dtype=np.int32),
        cells=np.full((w, pmax, k, c), -1, dtype=np.int32),
        qty=np.zeros((w, pmax, k, c), dtype=np.int64),
        valid=np.zeros((w, pmax, k), dtype=bool),
        cgrp=np.full((w, pmax, k, c), -1, dtype=np.int8),
        priority=np.zeros(w, dtype=np.int64),
        timestamp=np.zeros(w, dtype=np.int64),
        no_reclaim=np.zeros(w, dtype=bool),
        ffb=np.ones(w, dtype=bool),
        ffp=np.zeros(w, dtype=bool),
    )
    templates: Dict[tuple, _Template] = {}
    groups: Dict[tuple, tuple] = {}  # (key, p) -> (t, idxs, pcs)
    seen_cqs = set()

    for i, (wl, cq_name) in enumerate(heads):
        first = cq_name not in seen_cqs
        seen_cqs.add(cq_name)
        out.heads.append(wl)
        out.cq_names.append(cq_name)
        # per-podset maps appended as podsets lower (indexed p <
        # n_podsets only; fallback heads keep empty lists)
        flav_i: list = []
        grp_i: list = []
        out.candidate_flavors.append(flav_i)
        out.candidate_groups.append(grp_i)
        out.n_groups.append(0)
        out.walk_states.append(1)
        if cq_name not in snapshot.cq_models:
            out.fallback.append(i)
            continue
        cq = snapshot.cq_models[cq_name]
        if len(wl.pod_sets) > max_podsets or (
            not any_fungibility and not _default_fungibility(cq)
        ):
            out.fallback.append(i)
            continue
        ff = cq.flavor_fungibility
        out.ffb[i] = ff.when_can_borrow == FlavorFungibilityPolicy.BORROW
        out.ffp[i] = ff.when_can_preempt == FlavorFungibilityPolicy.PREEMPT

        state = wl.last_assignment
        gen = snapshot.generations.get(cq_name, 0)
        if state is not None and gen > state.cluster_queue_generation:
            state = None

        # fast path: the overwhelmingly common single-podset head skips
        # the per-podset list plumbing below (bulk-drain lowering cost)
        if len(wl.pod_sets) == 1:
            ps = wl.pod_sets[0]
            if ps.topology_request is not None and not allow_tas:
                out.fallback.append(i)
                continue
            per_pod = quota_per_pod(ps, transform)
            starts = _resolve_starts(cq, per_pod, state, 0)
            resumed = any(starts)
            if resumed:
                if not first:
                    out.fallback.append(i)
                    continue
                out.head_starts[i] = [starts]
                starts = ()
            key = (cq_name, _podset_sig(ps, per_pod), starts)
            t = templates.get(key)
            if t is None:
                t = _build_template(
                    snapshot, cq, cq_name, ps, per_pod, starts, flavors, k, c,
                    allow_tas=allow_tas,
                )
                templates[key] = t
            if t.fallback:
                out.head_starts.pop(i, None)
                out.fallback.append(i)
                continue
            out.resumed += resumed
            out.cq_row[i] = t.cq_row
            out.n_podsets[i] = 1
            out.no_reclaim[i] = t.no_reclaim
            out.priority[i] = priority_of(wl, snapshot.priority_classes)
            ts = timestamp_fn(wl) if timestamp_fn else wl.creation_time
            out.timestamp[i] = int(ts * 1e9)
            out.n_groups[i] = t.n_groups
            ws = 1
            for n_g in t.group_sizes:
                ws *= n_g + 1
            out.walk_states[i] = ws
            flav_i.append(t.flavor_list)
            grp_i.append(t.group_list)
            group = groups.get((key, 0))
            if group is None:
                group = groups[(key, 0)] = (t, [], [])
            group[1].append(i)
            group[2].append((per_pod, effective_podset_count(wl, ps)))
            continue

        bad = False
        head_templates = []
        head_starts = []
        for ps_idx, ps in enumerate(wl.pod_sets):
            if ps.topology_request is not None and not allow_tas:
                bad = True  # TAS placement stays on the host path
                break
            per_pod = quota_per_pod(ps, transform)
            starts = _resolve_starts(cq, per_pod, state, ps_idx)
            head_starts.append(starts)
            # the whole walk: the kernel starts it at the cursor
            key = (cq_name, _podset_sig(ps, per_pod), ())
            t = templates.get(key)
            if t is None:
                t = _build_template(
                    snapshot, cq, cq_name, ps, per_pod, (), flavors, k, c
                )
                templates[key] = t
            if t.fallback:
                bad = True
                break
            head_templates.append((key, t, ps, per_pod))
        resumed = any(any(st) for st in head_starts)
        if resumed and not first:
            bad = True
        if bad:
            out.fallback.append(i)
            continue
        out.resumed += resumed
        if resumed:
            out.head_starts[i] = head_starts

        out.cq_row[i] = head_templates[0][1].cq_row
        out.n_podsets[i] = len(wl.pod_sets)
        out.no_reclaim[i] = head_templates[0][1].no_reclaim
        out.priority[i] = priority_of(wl, snapshot.priority_classes)
        ts = timestamp_fn(wl) if timestamp_fn else wl.creation_time
        out.timestamp[i] = int(ts * 1e9)
        out.n_groups[i] = max(t.n_groups for _, t, _, _ in head_templates)
        ws = 1
        for _, t, _, _ in head_templates:
            for n_g in t.group_sizes:
                ws *= n_g + 1
        out.walk_states[i] = ws
        for p, (key, t, ps, per_pod) in enumerate(head_templates):
            flav_i.append(t.flavor_list)
            grp_i.append(t.group_list)
            count = effective_podset_count(wl, ps)
            group = groups.get((key, p))
            if group is None:
                group = groups[(key, p)] = (t, [], [])
            group[1].append(i)
            group[2].append((per_pod, count))

    for (key, p), (t, idxs, pcs) in groups.items():
        ii = np.asarray(idxs, dtype=np.intp)
        out.cells[ii, p] = t.cells_arr
        out.valid[ii, p] = t.valid_row
        out.cgrp[ii, p] = t.cgrp_arr
        rmat = np.zeros((len(ii), len(t.res_names) + 1), dtype=np.int64)
        for x, r in enumerate(t.res_names):
            if r == PODS:
                rmat[:, x] = [count for (_, count) in pcs]
            else:
                rmat[:, x] = [pp.get(r, 0) * count for (pp, count) in pcs]
        out.qty[ii, p] = rmat[:, t.qty_sel]
    return out
