"""Device-resident admission megaloop — K drain rounds in ONE launch.

The pipelined drain loop (core/pipeline.py) still pays one host↔device
round trip per drain round (a fixed dispatch floor per launch), and
even double-buffering overlaps the host apply with only ONE in-flight
launch. This kernel fuses K
consecutive rounds into a single dispatch: an outer ``lax.while_loop``
(bounded at ``max_rounds`` or quiescence) over the SAME per-cycle body
``solve_drain`` runs (ops/drain_kernel._plain_cycle — one definition,
shared by construction), with explicit round boundaries every
``chunk_cycles`` cycles that reproduce EXACTLY what a serial host
re-plan over the undecided suffix would produce:

- per-round walk state resets: ``g_start`` (per-group flavor cursors),
  ``retries`` and the global stagnation counter all zero at a boundary
  — a fresh ``plan_drain`` over the remaining entries starts them at
  zero too;
- stuck queues retire at the boundary (``alive`` mask): a serial
  round's stuck-frozen entries are reported as fallback and the host
  loop does not re-feed them to the NEXT round's launch, so the fused
  continuation must stop nominating them (their within-round frozen
  re-nominations still shape decisions exactly like the host's spin);
- per-round retry budgets re-derive from the remaining suffix:
  ``cap_suffix[q, p] = min(4096, max(walk_states[p:]) + 1)`` is the
  retry_cap a fresh re-plan over positions >= p would compute, gathered
  at each boundary at the round's starting cursor.

The result is a round-stamped decision log: which round admitted each
entry (``admitted_round``), the in-round cycle stamp a per-round serial
launch would have recorded (``admitted_cycle``), and per-round
cursor / stuck / leaf-usage / cycle-count snapshots from which the host
(core/drain.MegaloopLaunch.fetch) reconstructs one DrainOutcome per
round. The host journals/applies/audits the log ROUND BY ROUND, trailing
the device, validating each round's implied inputs with the same
conflict-check contract the PR-7 speculative commit uses
(drain_inputs_match + pending_matches); any mismatch truncates the
batch at that round — so correctness never rests on the fused
continuation, exactly as it never rested on the pipeline's speculation.

Decision parity with per-round serial launches is asserted against the
numpy mirror ops/megaloop_np.solve_megaloop_np (which IS the serial
loop over suffix-trimmed queue tensors) in tests/test_megaloop.py, and
registered in ops/__init__.KERNEL_MIRRORS.
"""

from __future__ import annotations

from typing import NamedTuple

from kueue_tpu._jax import jax, jnp, lax
from kueue_tpu.ops.drain_kernel import DrainQueues, _plain_cycle, first_walks
from kueue_tpu.ops.quota import QuotaTree, subtree_quota


class MegaloopResult(NamedTuple):
    """The round-stamped decision log of one fused launch.

    admitted_k:     int32[Q,L,P] chosen candidate per entry (-1 never);
    admitted_cycle: int32[Q,L]   IN-ROUND cycle of the admission;
    admitted_round: int32[Q,L]   round index of the admission (-1);
    round_cursor:   int32[R,Q]   cursor at each round's end;
    round_stuck:    bool[R,Q]    stuck-or-retired at each round's end;
    round_cycles:   int32[R]     cycles executed within each round;
    round_usage:    int64[R,N,FR] leaf usage at each round's end — the
                    speculative post-apply snapshot of the NEXT round
                    (the host's conflict check compares the real
                    post-apply state against it);
    round_gstart:   int32[R,Q,P,G] each head's flavor walk at each
                    round's end, carried into the next round as a
                    re-plan would resume it from the head's
                    LastAssignment;
    rounds:         int32 scalar — rounds actually executed;
    cycles:         int32 scalar — total kernel cycles."""

    admitted_k: jnp.ndarray
    admitted_cycle: jnp.ndarray
    admitted_round: jnp.ndarray
    round_cursor: jnp.ndarray
    round_stuck: jnp.ndarray
    round_cycles: jnp.ndarray
    round_usage: jnp.ndarray
    round_gstart: jnp.ndarray
    rounds: jnp.ndarray
    cycles: jnp.ndarray


def solve_drain_megaloop(
    tree: QuotaTree,
    local_usage: jnp.ndarray,  # int64[N, FR] starting leaf usage
    queues: DrainQueues,
    paths: jnp.ndarray,  # int32[N, D+1]
    cap_suffix: jnp.ndarray,  # int32[Q, L] suffix retry budgets
    n_segments: int,
    n_steps: int,
    chunk_cycles: int,
    max_rounds: int,
) -> MegaloopResult:
    subtree, guaranteed = subtree_quota(tree)
    from kueue_tpu.ops.assign_kernel import potential_available_all

    potential = potential_available_all(tree, subtree, guaranteed)

    q, l, pmax, k, c = queues.cells.shape
    g = queues.gidx.shape[-1]
    n, fr = local_usage.shape
    q_idx = jnp.arange(q)

    def cap_of(cursor, alive):
        # the retry_cap vector a fresh re-plan over the remaining
        # entries would ship: suffix budget at the round's starting
        # cursor for queues still in the plan, 0 (inert) for retired /
        # drained queues — so the stagnation guard's max ranges over
        # exactly the queues a serial round would contain
        rem = (cursor < queues.qlen) & alive
        cap = cap_suffix[q_idx, jnp.minimum(cursor, l - 1)]
        return jnp.where(rem, cap, 0).astype(jnp.int32)

    def body(state):
        (local, cursor, g_start, retries, stuck, no_prog, adm_k,
         adm_cycle, adm_round, alive, cap_eff, round_idx, round_cycle,
         r_cursor, r_stuck, r_cycles, r_usage, r_gstart, cycle) = state

        # one plain drain cycle, bit-for-bit solve_drain's, with the
        # per-round dynamic retry budget and the retired-queue mask
        inner = (local, cursor, g_start, retries, stuck, no_prog,
                 adm_k, adm_cycle, round_cycle)
        (local, cursor, g_start, retries, stuck, no_prog, adm_k,
         adm_cycle, round_cycle) = _plain_cycle(
            tree, subtree, guaranteed, potential,
            queues._replace(retry_cap=cap_eff), paths,
            n_segments, n_steps, inner, alive=alive,
        )
        # round stamp: an entry whose admission just landed carries the
        # current round (adm_cycle got its in-round stamp in the cycle)
        adm_round = jnp.where(
            (adm_k[:, :, 0] >= 0) & (adm_round < 0), round_idx, adm_round
        )
        cycle = cycle + 1

        # ---- round boundary: chunk exhausted or round quiesced ----
        rem = (cursor < queues.qlen) & alive
        quiesced = ~jnp.any(rem & ~stuck)
        boundary = quiesced | (round_cycle >= chunk_cycles)
        ri = jnp.minimum(round_idx, max_rounds - 1)
        r_cursor = r_cursor.at[ri].set(
            jnp.where(boundary, cursor, r_cursor[ri])
        )
        r_stuck = r_stuck.at[ri].set(
            jnp.where(boundary, stuck | ~alive, r_stuck[ri])
        )
        r_cycles = r_cycles.at[ri].set(
            jnp.where(boundary, round_cycle, r_cycles[ri])
        )
        r_usage = r_usage.at[ri].set(
            jnp.where(boundary, local, r_usage[ri])
        )
        r_gstart = r_gstart.at[ri].set(
            jnp.where(boundary, g_start, r_gstart[ri])
        )
        # a queue stuck at the boundary retires: the serial loop
        # reports its unprocessed entries as fallback and never feeds
        # them to the next round's launch
        alive = jnp.where(boundary, alive & ~stuck, alive)
        # fresh-plan state for the next round; the heads' flavor walks
        # carry over, as the re-plan resumes them from LastAssignment
        retries = jnp.where(boundary, 0, retries)
        no_prog = jnp.where(boundary, 0, no_prog)
        stuck = jnp.where(boundary, jnp.zeros_like(stuck), stuck)
        cap_eff = jnp.where(boundary, cap_of(cursor, alive), cap_eff)
        round_idx = round_idx + boundary.astype(jnp.int32)
        round_cycle = jnp.where(boundary, 0, round_cycle)

        return (local, cursor, g_start, retries, stuck, no_prog, adm_k,
                adm_cycle, adm_round, alive, cap_eff, round_idx,
                round_cycle, r_cursor, r_stuck, r_cycles, r_usage, r_gstart,
                cycle)

    def cond(state):
        (_, cursor, _, _, stuck, _, _, _, _, alive, _, round_idx, _,
         _, _, _, _, _, _) = state
        more = jnp.any((cursor < queues.qlen) & ~stuck & alive)
        return more & (round_idx < max_rounds)

    alive0 = jnp.ones(q, dtype=bool)
    init = (
        local_usage,
        jnp.zeros(q, dtype=jnp.int32),
        first_walks(queues),
        jnp.zeros(q, dtype=jnp.int32),
        jnp.zeros(q, dtype=bool),
        jnp.int32(0),
        jnp.full((q, l, pmax), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        jnp.full((q, l), -1, dtype=jnp.int32),
        alive0,
        cap_of(jnp.zeros(q, dtype=jnp.int32), alive0),
        jnp.int32(0),
        jnp.int32(0),
        jnp.zeros((max_rounds, q), dtype=jnp.int32),
        jnp.zeros((max_rounds, q), dtype=bool),
        jnp.zeros(max_rounds, dtype=jnp.int32),
        jnp.zeros((max_rounds, n, fr), dtype=jnp.int64),
        jnp.zeros((max_rounds, q, pmax, g), dtype=jnp.int32),
        jnp.int32(0),
    )
    (local_f, cursor_f, _, _, _, _, adm_k, adm_cycle, adm_round, _, _,
     rounds_f, _, r_cursor, r_stuck, r_cycles, r_usage, r_gstart,
     cycles_f) = lax.while_loop(cond, body, init)
    return MegaloopResult(
        admitted_k=adm_k,
        admitted_cycle=adm_cycle,
        admitted_round=adm_round,
        round_cursor=r_cursor,
        round_stuck=r_stuck,
        round_cycles=r_cycles,
        round_usage=r_usage,
        round_gstart=r_gstart,
        rounds=rounds_f,
        cycles=cycles_f,
    )


def _solve_drain_megaloop_packed(
    tree, local_usage, queues, paths, cap_suffix,
    n_segments: int, n_steps: int, chunk_cycles: int, max_rounds: int,
):
    """solve_drain_megaloop with the whole round-stamped log packed for
    ONE fetch — K rounds of decisions retrieved together (the entire
    point of the fusion): an int32 vector of the decisions and counts,
    and the int64 per-round usage as its own output (an int64
    concatenation gathered across a mesh does not lower on the TPU)."""
    r = solve_drain_megaloop(
        tree, local_usage, queues, paths, cap_suffix,
        n_segments, n_steps, chunk_cycles, max_rounds,
    )
    log = jnp.concatenate(
        [
            r.admitted_k.reshape(-1).astype(jnp.int32),
            r.admitted_cycle.reshape(-1).astype(jnp.int32),
            r.admitted_round.reshape(-1).astype(jnp.int32),
            r.round_cursor.reshape(-1).astype(jnp.int32),
            r.round_stuck.reshape(-1).astype(jnp.int32),
            r.round_cycles.reshape(-1).astype(jnp.int32),
            r.round_gstart.reshape(-1),
            r.rounds[None].astype(jnp.int32),
            r.cycles[None].astype(jnp.int32),
        ]
    )
    return log, r.round_usage


solve_drain_megaloop_packed_jit = jax.jit(
    _solve_drain_megaloop_packed,
    static_argnames=("n_segments", "n_steps", "chunk_cycles", "max_rounds"),
)
