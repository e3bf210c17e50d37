"""ShardedSolver — the admission cycle over a jax.sharding.Mesh.

The reference scales by running ONE scheduler goroutine per cluster
(pkg/scheduler/scheduler.go:143-154 — leader-elected, single-threaded).
The TPU-native scale axis is different: one cycle is a batched tensor
program, and the mesh shards it:

  - ``wl`` (data axis): heads are sharded — phase-1 flavor
    classification is embarrassingly parallel over heads, so each
    device classifies its shard against replicated quota tensors.
  - ``fr`` (tensor axis, 2-D meshes): the [N, FR] quota tensors are
    sharded over flavor-resource cells for very wide clusters (many
    flavors x resources); XLA inserts the gathers.

Phase-2 conflict resolution (the lax.scan over admission order) is
sequential by construction — it runs replicated on the gathered
phase-1 output, which costs one all-gather of O(W) small vectors and no
communication inside the scan.

Multi-host: build the mesh from ``jax.devices()`` after
``jax.distributed.initialize()`` — the same code shards over ICI within
a host/pod and DCN across hosts; no host-side changes needed
(collectives ride the mesh like any pjit program).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from kueue_tpu._jax import jax, jnp
from kueue_tpu.ops.assign_kernel import HeadsBatch, SolveResult, solve_cycle
from kueue_tpu.ops.quota import QuotaTree


def make_mesh(
    n_devices: Optional[int] = None, fr_parallel: bool = False
):
    """A 1-D ``(wl,)`` or 2-D ``(wl, fr)`` mesh over the first
    n_devices available devices."""
    from jax.sharding import Mesh

    devices = np.array(jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if fr_parallel and n >= 4 and n % 2 == 0:
        return Mesh(devices.reshape(n // 2, 2), ("wl", "fr"))
    return Mesh(devices.reshape(n), ("wl",))


class ShardedSolver:
    """Places solver inputs on the mesh and runs the jitted cycle.

    The jit is cached per (shapes, mesh); repeated cycles with the same
    padded shapes reuse the compiled executable — size buckets should be
    chosen by the caller (static shapes are an XLA requirement; see
    SURVEY.md §7 hard-parts (c)).
    """

    def __init__(self, mesh):
        self.mesh = mesh
        self._jit = jax.jit(solve_cycle)

    def place(self, tree: QuotaTree, local_usage, heads: HeadsBatch, paths):
        """device_put every input with its mesh sharding (shared layout
        builders — the same specs the production entries use)."""
        if heads.score is None:
            heads = heads._replace(
                score=jnp.zeros(heads.valid.shape, dtype=jnp.int64)
            )
        if heads.try_next is None:
            heads = heads._replace(
                try_next=jnp.zeros(heads.cells.shape, dtype=jnp.int8)
            )
        fr_size = tree.nominal.shape[1]
        return (
            jax.device_put(tree, build_tree_spec(self.mesh, fr_size)),
            jax.device_put(local_usage, _fr_spec(self.mesh, fr_size)),
            jax.device_put(heads, build_heads_spec(self.mesh)),
            jax.device_put(paths, _sh(self.mesh, None, None)),
        )

    @property
    def wl_axis_size(self) -> int:
        return self.mesh.shape["wl"]

    def pad_heads(self, heads: HeadsBatch) -> HeadsBatch:
        """Pad W up to a multiple of the wl axis (padding rows have
        cq_row == -1 and are never admitted)."""
        w = heads.cq_row.shape[0]
        step = self.wl_axis_size
        target = ((w + step - 1) // step) * step
        if target == w:
            return heads
        pad = target - w

        def pad0(x):
            widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, widths, constant_values=0)

        score = (
            heads.score
            if heads.score is not None
            else jnp.zeros(heads.valid.shape, dtype=jnp.int64)
        )
        try_next = (
            heads.try_next
            if heads.try_next is not None
            else jnp.zeros(heads.cells.shape, dtype=jnp.int8)
        )
        return HeadsBatch(
            cq_row=jnp.pad(heads.cq_row, (0, pad), constant_values=-1),
            cells=jnp.pad(
                heads.cells, [(0, pad), (0, 0), (0, 0)], constant_values=-1
            ),
            qty=pad0(heads.qty),
            valid=pad0(heads.valid),
            priority=pad0(heads.priority),
            timestamp=pad0(heads.timestamp),
            no_reclaim=pad0(heads.no_reclaim),
            score=pad0(score),
            try_next=pad0(try_next),
        )

    def __call__(
        self, tree: QuotaTree, local_usage, heads: HeadsBatch, paths
    ) -> SolveResult:
        heads = self.pad_heads(heads)
        tree_d, usage_d, heads_d, paths_d = self.place(
            tree, local_usage, heads, paths
        )
        with self.mesh:
            return self._jit(tree_d, usage_d, heads_d, paths_d)


# ---- production-entry placement (the segmented cycle + the drains) ----
def _sh(mesh, *spec):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(*spec))


def _fr_spec(mesh, fr_size: int):
    """[_, FR] sharding: fr-sharded only when the mesh has an fr axis
    AND the cell count divides it (device_put rejects uneven shards);
    replicated otherwise."""
    if "fr" in mesh.axis_names and fr_size % mesh.shape["fr"] == 0:
        return _sh(mesh, None, "fr")
    return _sh(mesh, None, None)


def build_tree_spec(mesh, fr_size: int) -> QuotaTree:
    fr = _fr_spec(mesh, fr_size)
    return QuotaTree(
        parent=_sh(mesh, None),
        level_mask=_sh(mesh, None, None),
        nominal=fr,
        lending_limit=fr,
        borrowing_limit=fr,
    )


def build_heads_spec(mesh) -> HeadsBatch:
    return HeadsBatch(
        cq_row=_sh(mesh, "wl"),
        cells=_sh(mesh, "wl", None, None),
        qty=_sh(mesh, "wl", None, None),
        valid=_sh(mesh, "wl", None),
        priority=_sh(mesh, "wl"),
        timestamp=_sh(mesh, "wl"),
        no_reclaim=_sh(mesh, "wl"),
        score=_sh(mesh, "wl", None),
        try_next=_sh(mesh, "wl", None, None),
    )


def place_cycle_inputs(mesh, tree: QuotaTree, local_usage, heads: HeadsBatch, paths, seg_id):
    """device_put the segmented-cycle inputs (core/solver.dispatch_lowered)
    with the production layout: heads + segment ids sharded along ``wl``,
    quota tensors replicated (fr-sharded on a 2-D mesh when FR divides
    the axis). Inputs may be numpy arrays — device_put transfers each
    host buffer straight to its shards (no staging on one device). The
    caller pads W to a multiple of the wl axis (pad_w_multiple)."""
    fr_size = tree.nominal.shape[1]
    return (
        jax.device_put(tree, build_tree_spec(mesh, fr_size)),
        jax.device_put(local_usage, _fr_spec(mesh, fr_size)),
        jax.device_put(heads, build_heads_spec(mesh)),
        jax.device_put(paths, _sh(mesh, None, None)),
        jax.device_put(seg_id, _sh(mesh, "wl")),
    )


def pad_w_multiple(w: int, multiple: int) -> int:
    """Head-count target divisible by the mesh's wl axis."""
    return ((w + multiple - 1) // multiple) * multiple


def pad_queue_arrays(queues_np: dict, multiple: int) -> dict:
    """Pad the drain's Q axis to a multiple of the mesh's wl axis with
    inert queues (qlen 0, cq_row/seg_id -1)."""
    import numpy as np

    q = queues_np["qlen"].shape[0]
    target = ((q + multiple - 1) // multiple) * multiple
    if target == q:
        return queues_np
    pad = target - q
    out = {}
    for name, arr in queues_np.items():
        pad_block = np.zeros((pad,) + arr.shape[1:], dtype=arr.dtype)
        if name in ("cq_rows", "seg_id"):
            pad_block -= 1
        if name in ("cells", "cgrp"):
            pad_block[:] = -1
        out[name] = np.concatenate([arr, pad_block])
    return out


def place_drain_inputs(mesh, tree: QuotaTree, local_usage, queues, paths, victims=None):
    """device_put drain inputs: per-queue tensors sharded along ``wl``
    (the Q axis — each device owns a slice of the ClusterQueues; the
    phase-2 segmented scan runs on the gathered per-cycle heads),
    quota tree + paths replicated."""
    rep2 = _sh(mesh, None, None)
    tree_d = jax.device_put(
        tree,
        QuotaTree(
            parent=_sh(mesh, None), level_mask=rep2, nominal=rep2,
            lending_limit=rep2, borrowing_limit=rep2,
        ),
    )
    q_specs = type(queues)(
        **{
            name: _sh(mesh, "wl", *([None] * (getattr(queues, name).ndim - 1)))
            for name in queues._fields
        }
    )
    out = (
        tree_d,
        jax.device_put(local_usage, rep2),
        jax.device_put(queues, q_specs),
        jax.device_put(paths, rep2),
    )
    if victims is None:
        return out
    v_specs = type(victims)(
        **{
            name: _sh(mesh, "wl", *([None] * (getattr(victims, name).ndim - 1)))
            for name in victims._fields
        }
    )
    return out + (jax.device_put(victims, v_specs),)


from kueue_tpu.ops.drain_kernel import (  # noqa: E402
    NO_BWC_THRESHOLD,
    SEG_VICTIM_Q_FIELDS as _VICTIM_Q_FIELDS,
)


def pad_victim_arrays(victims_np: dict, q_target: int) -> dict:
    """Pad SegVictims' per-queue arrays to the mesh-padded Q with inert
    queues (identity perm, no entries, all policies off)."""
    import numpy as np

    q = victims_np["hlocal"].shape[0]
    if q_target == q:
        return victims_np
    pad = q_target - q
    out = dict(victims_np)
    for name in _VICTIM_Q_FIELDS:
        arr = victims_np[name]
        if name == "perm":
            block = np.tile(
                np.arange(arr.shape[1], dtype=arr.dtype), (pad, 1)
            )
        elif name == "entry_slot":
            block = np.full((pad,) + arr.shape[1:], -1, dtype=arr.dtype)
        elif name == "bwc_thr1":
            block = np.full((pad,), NO_BWC_THRESHOLD, dtype=arr.dtype)
        else:
            block = np.zeros((pad,) + arr.shape[1:], dtype=arr.dtype)
        out[name] = np.concatenate([arr, block])
    return out


def place_preempt_drain_inputs(mesh, tree, local_usage, queues, victims, paths):
    """device_put for the preemption drain: per-queue tensors (queues +
    SegVictims' per-queue config) sharded along ``wl``; quota tree,
    paths and the per-segment candidate pools replicated (every shard's
    queues search the same pools; pool-state updates are resolved by
    GSPMD)."""
    tree_d, local_d, queues_d, paths_d = place_drain_inputs(
        mesh, tree, local_usage, queues, paths
    )
    v_specs = type(victims)(
        **{
            name: (
                _sh(mesh, "wl", *([None] * (getattr(victims, name).ndim - 1)))
                if name in _VICTIM_Q_FIELDS
                else _sh(mesh, *([None] * getattr(victims, name).ndim))
            )
            for name in victims._fields
        }
    )
    return tree_d, local_d, queues_d, jax.device_put(victims, v_specs), paths_d


def place_fair_preempt_drain_inputs(
    mesh, tree, local_usage, queues, victims, fairp, paths
):
    """device_put for the fair-preemption drain: the classic preempt
    placement (per-queue tensors + SegVictims' per-queue config sharded
    along ``wl``, candidate pools replicated) plus the FairSegPanels
    replicated — every panel tensor lives in SEGMENT space [S, ...],
    and the tournament reduces over whole root cohorts on every shard
    (separate roots are independent; GSPMD resolves the panel-state
    scatters exactly like the fair drain's node-space ones)."""
    tree_d, local_d, queues_d, victims_d, paths_d = (
        place_preempt_drain_inputs(mesh, tree, local_usage, queues,
                                   victims, paths)
    )
    f_specs = type(fairp)(
        **{
            name: _sh(mesh, *([None] * getattr(fairp, name).ndim))
            for name in fairp._fields
        }
    )
    return (
        tree_d, local_d, queues_d, victims_d,
        jax.device_put(fairp, f_specs), paths_d,
    )


# TASHeads fields indexed by queue (sharded along ``wl``); the merged
# domain forest (leaf_flavor / parent_map, and the topo_free /
# tas_usage0 / seg_ids companions) stays replicated — every shard's
# queues place into the same forest and GSPMD resolves the leaf-usage
# scatters of the sequential placement scan.
TAS_Q_FIELDS = (
    "t_is", "t_req", "t_count", "t_level", "t_mode", "t_top",
    "t_flavor", "t_bad",
)


def pad_tas_arrays(theads_np: dict, q_target: int) -> dict:
    """Pad TASHeads' per-queue arrays to the mesh-padded Q with inert
    rows (t_is False — the kernel never touches them; zero requests)."""
    import numpy as np

    q = theads_np["t_is"].shape[0]
    if q_target == q:
        return theads_np
    pad = q_target - q
    out = dict(theads_np)
    for name in TAS_Q_FIELDS:
        arr = theads_np[name]
        block = np.zeros((pad,) + arr.shape[1:], dtype=arr.dtype)
        out[name] = np.concatenate([arr, block])
    return out


def place_tas_drain_inputs(
    mesh, tree, local_usage, queues, paths,
    topo_free, tas_usage0, seg_ids, theads,
):
    """device_put for the TAS drain: heavy per-queue tensors (ndim >= 2
    — cells/qty/cursors, TASHeads' request matrices) sharded along
    ``wl``; the merged domain forest replicated (every shard's queues
    place into the same forest and GSPMD resolves the placement scan's
    leaf-usage scatters).

    GSPMD fence: the 1-D per-queue control vectors (cq_rows, qlen,
    retry_cap, the policy flags, t_is/t_top/t_flavor) stay REPLICATED
    here — sharding any of them trips a partitioner miscompile in this
    kernel's admission scan (a mixed s64/s32 index compare in the
    partitioned dynamic_update_slice; hlo-verifier rejection observed
    on the 8-device CPU mesh, same family as the narrow-panel
    compaction bug). They are O(Q) scalars, so replicating them costs
    nothing next to the [Q,L,P,K,C] candidate tensors that DO shard;
    decision parity is asserted in tests/test_mesh_drain.py."""
    rep2 = _sh(mesh, None, None)
    tree_d = jax.device_put(
        tree,
        QuotaTree(
            parent=_sh(mesh, None), level_mask=rep2, nominal=rep2,
            lending_limit=rep2, borrowing_limit=rep2,
        ),
    )
    q_specs = type(queues)(
        **{
            name: (
                _sh(mesh, "wl", *([None] * (getattr(queues, name).ndim - 1)))
                if getattr(queues, name).ndim >= 2
                else _sh(mesh, *([None] * getattr(queues, name).ndim))
            )
            for name in queues._fields
        }
    )
    rep = lambda a: jax.device_put(  # noqa: E731
        a, _sh(mesh, *([None] * a.ndim))
    )
    t_specs = type(theads)(
        **{
            name: (
                _sh(mesh, "wl", *([None] * (getattr(theads, name).ndim - 1)))
                if name in TAS_Q_FIELDS and getattr(theads, name).ndim >= 2
                else _sh(mesh, *([None] * getattr(theads, name).ndim))
            )
            for name in theads._fields
        }
    )
    return (
        tree_d,
        jax.device_put(local_usage, rep2),
        jax.device_put(queues, q_specs),
        jax.device_put(paths, rep2),
        rep(topo_free), rep(tas_usage0), rep(seg_ids),
        jax.device_put(theads, t_specs),
    )


def place_fair_drain_extras(mesh, depth_of, weight, lendable, res_of_fr):
    """device_put the fair drain's node-space extras replicated (the
    tournament reduces over the whole cohort forest on every shard;
    separate root cohorts are independent, so the Q-sharded chain work
    parallelizes and GSPMD resolves the node-space scatters)."""
    return (
        jax.device_put(depth_of, _sh(mesh, None)),
        jax.device_put(weight, _sh(mesh, None)),
        jax.device_put(lendable, _sh(mesh, None, None)),
        jax.device_put(res_of_fr, _sh(mesh, None)),
    )


def place_fair_problem(mesh, problem):
    """device_put a FairProblem with every head row sharded along
    ``wl`` — the fair tournament search is embarrassingly parallel over
    heads (one local-subtree simulation each)."""
    specs = type(problem)(
        **{
            name: _sh(
                mesh, "wl", *([None] * (getattr(problem, name).ndim - 1))
            )
            for name in problem._fields
        }
    )
    return jax.device_put(problem, specs)
