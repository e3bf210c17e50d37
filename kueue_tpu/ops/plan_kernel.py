"""Vmapped multi-scenario admission solve — the planner's device path.

One extra ``jax.vmap`` axis over the existing segmented cycle solver
(ops/assign_kernel.solve_cycle_segmented): S scenario variants of the
quota tensors (nominal / lending / borrowing limits, leaf usage, head
priorities) solve against ONE shared heads batch in a single launch.
Structure — parent links, level masks, ancestor paths, candidate cells,
the segment schedule — is scenario-invariant (capacity planning changes
quantities, never the forest shape), so it stays unbatched and the XLA
program is the cycle solver's body under vmap, not S copies of it.
Subtree quotas and the usage tree are recomputed per scenario inside
the vmapped body, so a nominal-quota delta flows through guaranteed /
available exactly as it would on a reconfigured live cluster.

Per scenario the launch returns, packed for minimal host fetches:
  per_head int64[S, 6, W]  — chosen candidate, admitted flag, borrows,
                             reserved (blocked preempt-mode capacity
                             hold), phase-2 entry order, and the
                             preempt-mode representative candidate
                             (>=0 means preemption could admit it);
  usage    int64[S, N, FR] — the post-admission usage tree, from which
                             the host derives per-CQ utilization.
"""

from __future__ import annotations

from kueue_tpu._jax import jax, jnp
from kueue_tpu.ops.assign_kernel import (
    HeadsBatch,
    phase1_classify,
    solve_cycle_segmented,
)
from kueue_tpu.ops.quota import QuotaTree, subtree_quota


def _solve_scenarios(
    parent,  # int32[N]
    level_mask,  # bool[D+1, N]
    nominal_s,  # int64[S, N, FR]
    lending_s,  # int64[S, N, FR]
    borrowing_s,  # int64[S, N, FR]
    usage_s,  # int64[S, N, FR]
    priority_s,  # int64[S, W]
    score_s,  # int64[S, W, K] — per-scenario policy scores (the
    #            ``policy`` scenario kind; all-zero rows = first-fit)
    heads: HeadsBatch,  # shared across scenarios (priority/score overridden)
    paths,  # int32[N, D+1]
    seg_id,  # int32[W]
    n_segments: int,
    n_steps: int,
):
    def one(nominal, lending, borrowing, usage, priority, score):
        tree = QuotaTree(
            parent=parent,
            level_mask=level_mask,
            nominal=nominal,
            lending_limit=lending,
            borrowing_limit=borrowing,
        )
        h = heads._replace(priority=priority, score=score)
        subtree, guaranteed = subtree_quota(tree)
        # preempt-mode representative per head (phase 1 inside the
        # segmented solve doesn't surface it); XLA CSEs the shared work
        _, _, preempt_k, _ = phase1_classify(tree, subtree, guaranteed, usage, h)
        r = solve_cycle_segmented(
            tree, usage, h, paths, seg_id, n_segments, n_steps
        )
        per_head = jnp.stack(
            [
                r.chosen.astype(jnp.int64),
                r.admitted.astype(jnp.int64),
                r.borrows.astype(jnp.int64),
                r.reserved.astype(jnp.int64),
                r.order.astype(jnp.int64),
                preempt_k.astype(jnp.int64),
            ]
        )
        return per_head, r.usage

    return jax.vmap(one)(
        nominal_s, lending_s, borrowing_s, usage_s, priority_s, score_s
    )


solve_scenarios_jit = jax.jit(
    _solve_scenarios, static_argnames=("n_segments", "n_steps")
)
