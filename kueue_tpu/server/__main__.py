"""python -m kueue_tpu.server — standalone control-plane server.

The cmd/kueue/main.go analog for the service surface: loads optional
state (--state, the CLI's JSON wire format), binds the HTTP server
(object API + visibility + metrics + jax-assign + dashboard), and
serves until interrupted.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def fenced_checkpoint(srv, state_path: str) -> bool:
    """Atomically checkpoint srv.runtime to ``state_path``; returns
    False without writing when this replica no longer holds the lease.

    Atomic (unique tmp + os.replace, tmp unlinked on failure): a SIGKILL
    mid-write must not destroy the only durable copy, and a concurrent
    periodic + shutdown checkpoint must not race on a shared tmp path.
    Fenced: with an elector, the file write runs inside the lease's
    critical section only while the on-disk record still names us WITH
    THE SAME fencing token the snapshot was serialized under — a deposed
    leader resuming from a stall cannot clobber the new leader's newer
    checkpoint, even if it re-acquired the lease in the meantime (its
    token changed, so the pre-deposition snapshot is refused).
    Serialization happens OUTSIDE the flock (under the server lock
    alone): holding the shared-volume lock for a multi-second
    50k-workload dump would stall every replica's election tick.
    A process-local sequence (srv._ckpt_seq/_ckpt_written) additionally
    orders concurrent checkpoints in the SAME process, so a stalled
    periodic dump can never replace a newer shutdown dump."""
    from kueue_tpu import serialization as ser
    from kueue_tpu.utils.lease import atomic_write_text

    with srv.lock:
        state = ser.runtime_to_state(srv.runtime)
        snap_token = srv.elector.lease.token if srv.elector else None
        # stamp the serialization-time token into the checkpoint: the
        # recovery replay refuses journal records with OLDER tokens (a
        # deposed leader's stray appends)
        state["persistence"]["token"] = snap_token
        # the journal prefix this checkpoint covers — safe to compact
        # once the checkpoint is durably on disk
        snap_journal_seq = state["persistence"]["journalSeq"]
        journal = getattr(srv.runtime, "journal", None)
        text = json.dumps(state, indent=1)
        srv._ckpt_seq += 1
        seq = srv._ckpt_seq

    def _write_if_newest() -> bool:
        if seq <= srv._ckpt_written:
            return False  # a newer snapshot already landed
        if journal is not None:
            # records up to snap_journal_seq must be durable BEFORE the
            # checkpoint that compacts them away claims to cover them
            try:
                journal.sync()
            except OSError:
                pass  # degraded journal: the checkpoint still lands
        atomic_write_text(
            state_path, text, ".state-", fault_point="checkpoint.mid_write"
        )
        srv._ckpt_written = seq
        if journal is not None:
            # the checkpoint IS the compaction point
            journal.compact(snap_journal_seq)
        return True

    if srv.elector is None:
        with srv._ckpt_write_lock:
            return _write_if_newest()
    lease = srv.elector.lease
    with lease._locked():
        if not lease.is_held() or lease.token != snap_token:
            # deposed since the snapshot was taken (even if we lead
            # again under a new token): the snapshot is stale
            return False
        with srv._ckpt_write_lock:
            return _write_if_newest()


def fenced_delta_checkpoint(srv) -> bool:
    """``fenced_checkpoint`` for the delta-chain shape (--state-dir):
    same two-phase choreography — serialize under the server lock
    (``DeltaCheckpointer.prepare``: journal mark + O(changed) delta or
    periodic full anchor), then the durable write + compaction + chain
    GC (``commit``) inside the lease's critical section only while the
    on-disk record still names us with the snapshot's token. The
    process-local ``_ckpt_seq``/``_ckpt_written`` ordering holds too: a
    stalled periodic prepare can never commit over a newer shutdown
    one (its marks were never cleared, so nothing is lost by the
    abandon)."""
    ckpt = getattr(srv.runtime, "checkpointer", None)
    if ckpt is None:
        return False
    with srv.lock:
        snap_token = srv.elector.lease.token if srv.elector else None
        prep = ckpt.prepare(srv.runtime, token=snap_token)
        srv._ckpt_seq += 1
        seq = srv._ckpt_seq

    def _write_if_newest() -> bool:
        if seq <= srv._ckpt_written:
            ckpt.abandon(prep)
            return False  # a newer snapshot already landed
        ok = ckpt.commit(prep)
        if ok:
            srv._ckpt_written = seq
        return ok

    if srv.elector is None:
        with srv._ckpt_write_lock:
            return _write_if_newest()
    lease = srv.elector.lease
    with lease._locked():
        if not lease.is_held() or lease.token != snap_token:
            # deposed since the snapshot was taken: the snapshot is
            # stale (and its dirty marks survive for the next leader
            # tenure's checkpoint)
            ckpt.abandon(prep)
            return False
        with srv._ckpt_write_lock:
            return _write_if_newest()


def promote_reload(srv, state_path: str, build_runtime,
                   run_reconcile: bool = True,
                   require_standby: bool = False,
                   journal_path: str = "",
                   journal_opts: dict = None) -> bool:
    """On lease takeover, REBUILD srv.runtime from the old leader's
    latest checkpoint — not an upsert into the standby's stale store,
    which would resurrect objects the old leader deleted. Data loss is
    bounded by the checkpoint period — or, with ``journal_path``, by
    the journal fsync window: promotion then runs full recovery
    (checkpoint + replay of newer records, stale fencing tokens
    refused, invariants checked) and attaches the journal to the new
    runtime. Returns True when a checkpoint was loaded (or, with a
    journal, when anything was recovered).

    Also used for the standby read-refresh with ``run_reconcile=False``
    + ``require_standby=True``: a standby mirrors the leader's
    checkpoint verbatim and must NOT run scheduling cycles of its own
    — nor open the journal for append (that would truncate/extend a
    file the leader is writing); standby refreshes stay
    checkpoint-only. And if this replica was promoted while the (slow)
    mirror rebuild was in flight, the swap is abandoned — installing a
    never-reconciled pre-promotion mirror over the new leader's live
    runtime would discard writes accepted since promotion."""
    from kueue_tpu import serialization as ser

    journal = None
    if journal_path and not require_standby:
        from kueue_tpu.storage import recover

        fresh = build_runtime()
        res = recover(state_path, journal_path, runtime=fresh, strict=True,
                      **(journal_opts or {}))
        journal = res.journal
        loaded = res.checkpoint_loaded or res.replayed > 0
        if not loaded:
            journal.close()
            return False
    else:
        from kueue_tpu.storage import load_state_any

        if not (state_path and os.path.exists(state_path)):
            return False
        # load_state_any reads both checkpoint shapes: a full-dump FILE
        # or a delta-chain DIRECTORY (--state-dir)
        data = load_state_any(state_path)
        if data is None:
            return False
        fresh = build_runtime()
        ser.runtime_from_state(data, runtime=fresh)
    with srv.lock:
        if require_standby and srv.elector is not None and srv.elector.is_leader:
            return False
        if journal is not None:
            journal.token_provider = (
                (lambda: srv.elector.lease.token) if srv.elector else None
            )
            fresh.attach_journal(journal)
            old_journal = getattr(srv.runtime, "journal", None)
            if old_journal is not None and old_journal is not journal:
                old_journal.close()
        srv.runtime = fresh
        if run_reconcile:
            fresh.run_until_idle()
    return True


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="kueue_tpu.server")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8082)
    parser.add_argument(
        "--config",
        help="manager configuration file (kueue_tpu.config schema, "
        "the --config of cmd/kueue/main.go)",
    )
    parser.add_argument(
        "--state",
        help="JSON state file (CLI wire format): loaded at startup if "
        "present, written back on shutdown — the durable checkpoint "
        "active-passive recovery restarts from",
    )
    parser.add_argument(
        "--journal",
        help="directory for the write-ahead admission journal: every "
        "state mutation is appended as a CRC-framed record; startup "
        "(and promotion) recovers from the newest checkpoint plus "
        "replay of newer records, bounding crash data loss to the "
        "fsync window instead of the checkpoint period",
    )
    parser.add_argument(
        "--journal-fsync", choices=["always", "interval", "never"],
        default="interval",
        help="journal durability policy: always = fsync every append "
        "(power-loss-safe, slow), interval = fsync at most every "
        "--journal-fsync-interval seconds (the default), never = "
        "leave it to the OS",
    )
    parser.add_argument(
        "--journal-fsync-interval", type=float, default=0.05,
        help="seconds between journal fsyncs under --journal-fsync "
        "interval (the bounded power-loss window)",
    )
    parser.add_argument(
        "--journal-segment-bytes", type=int, default=8 * 1024 * 1024,
        help="rotate journal segments at this size; checkpoints delete "
        "fully-covered segments (compaction)",
    )
    parser.add_argument(
        "--state-dir",
        help="directory for DELTA checkpoints (requires --journal, "
        "replaces --state): periodic checkpoints record only objects "
        "changed since the previous one, chained back to a full "
        "anchor every --checkpoint-anchor-every checkpoints — "
        "compaction cost is O(changed) instead of O(live workloads). "
        "Recovery loads anchor + delta chain + journal suffix; "
        "`kueuectl state verify` walks the chain — see deploy/README "
        "'Sustained operation'",
    )
    parser.add_argument(
        "--checkpoint-anchor-every", type=int, default=16,
        help="write a full anchor checkpoint after this many deltas "
        "(bounds chain length and recovery walk; --state-dir only)",
    )
    parser.add_argument(
        "--checkpoint-retain", type=int, default=1,
        help="checkpoint chains (anchor + its deltas) to keep on disk; "
        "older chains are garbage-collected after each successful "
        "checkpoint (--state-dir only)",
    )
    parser.add_argument(
        "--no-solver", action="store_true",
        help="disable the batched TPU nomination path",
    )
    parser.add_argument(
        "--solver-path", choices=["auto", "host", "device"], default="auto",
        help="solver guard mode (core/guard.py): auto = device with "
        "circuit-breaker failover to the numpy host mirror (the "
        "default), host = force the host mirror (degraded-solver "
        "runbook escape hatch), device = never fail over (debugging; "
        "device faults propagate)",
    )
    parser.add_argument(
        "--policy", default="first-fit",
        help="admission policy (kueue_tpu/policy closed registry): "
        "first-fit = the score-free default (bit-for-bit the "
        "pre-policy decisions), gavel = heterogeneity-aware flavor "
        "scoring from kueue.tpu/throughput-<flavor> labels, prema = "
        "predictive victim ordering from kueue.tpu/remaining-seconds, "
        "deadline = SLO-boosted nomination from kueue.tpu/deadline, "
        "gavel-deadline = both. What-if a switch first: kueuectl plan "
        "with a {\"kind\": \"policy\"} scenario delta",
    )
    parser.add_argument(
        "--pipeline", choices=["on", "serial", "off"], default="on",
        help="double-buffered bulk-drain loop (core/pipeline.py): on = "
        "chunked drain rounds with the next round's encode+solve "
        "prefetched on a speculative snapshot while the host applies "
        "the current one (the default; overlap observable via "
        "kueue_pipeline_* metrics and /debug/cycles spans), serial = "
        "the same chunked rounds without prefetch (A/B baseline), "
        "off = the pre-pipeline single-dispatch drain",
    )
    parser.add_argument(
        "--pipeline-chunk-cycles", type=int, default=16,
        help="kernel cycles per pipelined drain round: smaller chunks "
        "overlap sooner but pay more dispatch round trips",
    )
    parser.add_argument(
        "--megaloop", default="off", metavar="on|off|K",
        help="device-resident admission megaloop "
        "(ops/megaloop_kernel): fuse up to K drain rounds of "
        "--pipeline-chunk-cycles kernel cycles each into ONE device "
        "dispatch — the host journals/applies the batched "
        "round-stamped decision log trailing the device, each round "
        "validated by the pipeline's conflict-check contract (any "
        "mismatch truncates the batch and re-solves from the real "
        "state). on = K tuned online per backlog mix, an integer "
        "pins K, off = per-round launches (the default). Composes "
        "with --pipeline (on also prefetches the next fused launch) "
        "and --mesh; observable via kueue_megaloop_* metrics — see "
        "deploy/README 'Megaloop'",
    )
    parser.add_argument(
        "--mesh", default="off", metavar="auto|N|off",
        help="multi-chip admission (kueue_tpu/parallel): shard every "
        "drain-family device launch over a (wl[, fr]) device mesh — "
        "auto = all local devices, N = the first N, off = "
        "single-device (the default). Composes with --pipeline "
        "(prefetched launches ride the same sharded path) and with "
        "the solver guard (host mirrors are mesh-agnostic). auto on "
        "a one-device host stays single-device; an N the host cannot "
        "supply refuses to start. Multi-host meshes need jax.distributed.initialize() before "
        "startup (deploy/README 'Multi-chip admission')",
    )
    parser.add_argument(
        "--panel-widths", default=None, metavar="W1,W2",
        help="fixed victim-search panel-width schedule for the "
        "contended drain (e.g. '16,64': narrow cost-ordered panel "
        "first, escalate to the exact wide panel only on an "
        "inconclusive truncated search). Default: the online "
        "per-workload-mix PanelTuner picks the narrow width",
    )
    parser.add_argument(
        "--no-auto-reconcile", action="store_true",
        help="only reconcile on POST /reconcile",
    )
    parser.add_argument(
        "--gateway", choices=["on", "off"], default="off",
        help="write-path gateway (kueue_tpu/gateway): coalesce "
        "concurrent workload POSTs (and batch sections) into one "
        "serving-lock critical section, one group-committed journal "
        "sync and one admission pass per flush window, with "
        "per-tenant token-bucket load-shedding (429 + Retry-After). "
        "The serving-at-scale ingest path — see deploy/README "
        "'Serving at scale'",
    )
    parser.add_argument(
        "--gateway-flush-interval", type=float, default=0.005,
        help="seconds one gateway flush window coalesces arrivals for "
        "(smaller = lower added latency, less batching)",
    )
    parser.add_argument(
        "--gateway-max-batch", type=int, default=256,
        help="most requests one gateway flush applies in one critical "
        "section",
    )
    parser.add_argument(
        "--gateway-queue-depth", type=int, default=4096,
        help="bounded coalescing-queue capacity; arrivals beyond it "
        "are shed with 429",
    )
    parser.add_argument(
        "--gateway-tenant-rate", type=float, default=0.0,
        help="per-tenant (LocalQueue/namespace) sustained write budget "
        "in requests/s; 0 disables the rate limiter (queue-capacity "
        "shedding still applies)",
    )
    parser.add_argument(
        "--gateway-tenant-burst", type=float, default=0.0,
        help="per-tenant token-bucket burst (default: 2x the rate)",
    )
    parser.add_argument(
        "--slo-target-p95", type=float, default=0.0,
        help="default queue-to-admission p95 target in seconds for "
        "every ClusterQueue (kueue_slo_* family; 0 disables SLO "
        "tracking unless --slo-target sets per-CQ targets)",
    )
    parser.add_argument(
        "--slo-target", action="append", default=None, metavar="CQ=SECONDS",
        help="per-ClusterQueue queue-to-admission p95 target "
        "(repeatable; overrides --slo-target-p95 for that CQ)",
    )
    parser.add_argument(
        "--slo-objective", type=float, default=0.95,
        help="fraction of admissions that must land within the target "
        "(the error budget is 1 - objective)",
    )
    parser.add_argument(
        "--slo-burn-window", type=float, default=300.0,
        help="sliding window (seconds) the error-budget burn rate is "
        "computed over",
    )
    parser.add_argument(
        "--slo-burn-threshold", type=float, default=2.0,
        help="burn rate above which the budget is burning too fast; "
        "sustained for --slo-sustain seconds flips /healthz to "
        "'degraded' and kueue_slo_degraded to 1",
    )
    parser.add_argument(
        "--slo-sustain", type=float, default=60.0,
        help="seconds the burn threshold must be continuously exceeded "
        "before the SLO reports degraded",
    )
    parser.add_argument(
        "--replica-of", metavar="URL",
        help="run as a journal-tailing READ REPLICA of the leader at "
        "URL (a kueue_tpu.server started with --journal): the leader's "
        "replication feed is polled and replayed into a live read-only "
        "runtime serving watch/SSE, visibility, explain and "
        "best-effort-stale plan; mutating requests 307-redirect to the "
        "leader. Staleness is reported on /healthz and "
        "kueue_replica_{applied_seq,lag_seconds}",
    )
    parser.add_argument(
        "--replica-poll-interval", type=float, default=0.5,
        help="seconds between replication-feed polls in --replica-of "
        "mode (the staleness floor)",
    )
    parser.add_argument(
        "--replica-id",
        help="this replica's identity in the leader's roster "
        "(default: hostname-pid)",
    )
    parser.add_argument(
        "--replica-token", default=None,
        help="bearer token presented to a --replica-of leader started "
        "with --auth-token (default: --auth-token, so one shared "
        "token secures both directions)",
    )
    parser.add_argument(
        "--federation-worker", action="append", default=None,
        metavar="NAME=URL",
        help="run this control plane as a MultiKueue federation manager "
        "dispatching to the named worker control plane (repeatable; "
        "URL is another kueue_tpu.server). Pending workloads mirror to "
        "the planner-ranked workers, admit wherever quota clears "
        "first, and losers are retracted through the journaled "
        "at-least-once retraction protocol",
    )
    parser.add_argument(
        "--federation-worker-token", default=None,
        help="bearer token presented to --federation-worker servers "
        "started with --auth-token",
    )
    parser.add_argument(
        "--federation-lost-timeout", type=float, default=900.0,
        help="seconds a partitioned worker may hold a workload's "
        "reservation before the dispatcher deposes it (fence bump + "
        "re-dispatch; the multiKueue.workerLostTimeout analog)",
    )
    parser.add_argument(
        "--federation-fanout", type=int, default=None,
        help="mirror each workload to at most this many best-ranked "
        "workers (default: all configured workers)",
    )
    parser.add_argument(
        "--global-scheduler", choices=["on", "off"], default="off",
        help="run the federation-wide global scheduler on this manager "
        "(requires --federation-worker): aggregate every worker's "
        "pending positions / fair-share standings / capacities by "
        "tailing the replica feed each worker already serves, rescore "
        "all (pending workload x cluster) pairs in one batched kernel "
        "pass every --global-rescore-interval, and retract-and-"
        "redispatch placements another cluster beats by more than "
        "--global-hysteresis (journaled, fenced — exactly-one "
        "admission preserved). Served at GET /global/standings and "
        "`kueuectl pending-workloads --global`",
    )
    parser.add_argument(
        "--global-hysteresis", type=float, default=60.0,
        help="seconds of forecast gain another cluster must offer "
        "before a placement is rebalanced (churn guard)",
    )
    parser.add_argument(
        "--global-rescore-interval", type=float, default=30.0,
        help="seconds between global rescore passes",
    )
    parser.add_argument(
        "--elastic", choices=["on", "off"], default="off",
        help="run the elastic capacity plane: pending "
        "ProvisioningRequests are ranked by scoring each candidate "
        "flavor scale-up through one batched planner launch, the "
        "winner is submitted to --capacity-provider, and on "
        "Provisioned a journaled elastic_grant raises the flavor's "
        "nominal quota (BookingExpired/CapacityRevoked shrink it back "
        "via elastic_revoke). Served at GET /capacity and `kueuectl "
        "capacity`",
    )
    parser.add_argument(
        "--capacity-provider", choices=["simulated"], default="simulated",
        help="capacity provider backing --elastic (simulated: clock-"
        "driven in-process autoscaler with --elastic-provision-delay)",
    )
    parser.add_argument(
        "--elastic-provision-delay", type=float, default=5.0,
        help="seconds the simulated capacity provider takes to "
        "provision an accepted request",
    )
    parser.add_argument(
        "--elastic-capacity-limit", action="append", default=None,
        metavar="FLAVOR:RESOURCE=AMOUNT",
        help="cap the simulated provider's total grantable capacity "
        "for a (flavor, resource) pair (repeatable; default: "
        "unlimited) — requests past the cap fail and walk the "
        "b*2^(n-1) retry ladder",
    )
    parser.add_argument(
        "--leader-elect-lease",
        help="path to a shared lease file (on the state volume): "
        "enables leader election — the holder accepts writes and "
        "schedules, standbys serve reads and take over on lapse "
        "(the LeaderElection analog of cmd/kueue/main.go)",
    )
    parser.add_argument(
        "--leader-elect-identity",
        help="this replica's identity in the lease "
        "(default: hostname-pid)",
    )
    parser.add_argument(
        "--leader-elect-lease-duration", type=float, default=15.0,
        help="seconds a lapsed lease stays unclaimable before takeover",
    )
    parser.add_argument(
        "--state-checkpoint-period", type=float, default=30.0,
        help="seconds between periodic --state checkpoints while "
        "leading (bounds data loss on SIGKILL; 0 disables)",
    )
    parser.add_argument(
        "--trace-capacity", type=int, default=4096,
        help="traces kept in the in-memory distributed-tracing store "
        "(LRU-bounded; served at /debug/traces, exported by `kueuectl "
        "trace`, replicated to read replicas on the journal feed). "
        "0 disables tracing entirely",
    )
    parser.add_argument(
        "--auth-token",
        default=os.environ.get("KUEUE_AUTH_TOKEN") or None,
        help="bearer token gating mutating routes, metrics, state and "
        "debug (the secured-endpoint analog of cmd/kueue/main.go "
        "authn/z; default: $KUEUE_AUTH_TOKEN, unset = open)",
    )
    parser.add_argument(
        "--tls-cert-dir",
        help="serve TLS with self-managed certs in this directory "
        "(ca.crt/tls.crt/tls.key generated and rotated before expiry "
        "— the internalCertManagement analog of pkg/util/cert; "
        "clients verify against ca.crt)",
    )
    parser.add_argument(
        "--tls-cert",
        help="serve TLS with a provided certificate (PEM path; pair "
        "with --tls-key — the provided-certificates mode of "
        "cmd/kueue/main.go:161-168)",
    )
    parser.add_argument("--tls-key", help="private key for --tls-cert")
    parser.add_argument(
        "--tls-dns-name", action="append", default=None,
        help="SAN for self-managed certs (repeatable; default: "
        "--host + localhost + 127.0.0.1)",
    )
    args = parser.parse_args(argv)
    if args.state_dir and not args.journal:
        parser.error("--state-dir requires --journal (deltas chain over "
                     "the journal's sequence numbers)")
    if args.state_dir and args.state:
        parser.error("--state-dir and --state are mutually exclusive")
    if bool(args.tls_cert) != bool(args.tls_key):
        parser.error("--tls-cert and --tls-key must be given together")
    if args.tls_cert_dir and args.tls_cert:
        parser.error(
            "--tls-cert-dir (self-managed) and --tls-cert (provided) "
            "are mutually exclusive"
        )
    if args.replica_of:
        # a replica never writes: it neither journals (single-writer
        # log), contends for the lease, dispatches federation work,
        # nor batches writes (it 307s them to the leader)
        for flag, val in (
            ("--journal", args.journal),
            ("--state", args.state),
            ("--state-dir", args.state_dir),
            ("--leader-elect-lease", args.leader_elect_lease),
            ("--federation-worker", args.federation_worker),
            ("--gateway", args.gateway if args.gateway == "on" else None),
            ("--elastic", args.elastic if args.elastic == "on" else None),
        ):
            if val:
                parser.error(f"--replica-of is incompatible with {flag}")
    slo_targets = {}
    for spec in args.slo_target or []:
        cq, sep, seconds = spec.partition("=")
        if not sep or not cq:
            parser.error(f"--slo-target must be CQ=SECONDS, got {spec!r}")
        try:
            slo_targets[cq] = float(seconds)
        except ValueError:
            parser.error(f"--slo-target must be CQ=SECONDS, got {spec!r}")

    from kueue_tpu import serialization as ser
    from kueue_tpu.server import KueueServer

    use_solver = False if args.no_solver else None

    if args.panel_widths:
        from kueue_tpu.core import drain as _drain_mod

        _drain_mod.set_default_panel_widths(
            tuple(int(w) for w in args.panel_widths.split(","))
        )

    mesh = None
    if args.mesh and args.mesh != "off":
        from kueue_tpu.parallel import mesh_shape_str, resolve_mesh

        try:
            mesh = resolve_mesh(args.mesh)
        except ValueError as e:
            parser.error(f"--mesh {args.mesh}: {e}")
        if mesh is None:
            print(
                f"--mesh {args.mesh}: one device; running single-device",
                flush=True,
            )
        else:
            print(
                f"multi-chip admission: mesh {mesh_shape_str(mesh)} over "
                f"{mesh.size} devices",
                flush=True,
            )

    def build_runtime():
        """Construct a runtime exactly the way startup does — also used
        to REBUILD on promotion, so a promoted standby starts from the
        checkpoint alone instead of merging it into a stale store."""
        from kueue_tpu.tas import TASCache

        if args.config:
            import yaml

            from kueue_tpu.config import load_config, runtime_from_config

            with open(args.config) as f:
                cfg = load_config(yaml.safe_load(f))
            rt = runtime_from_config(cfg, tas_cache=TASCache())
            if use_solver is not None:
                rt.scheduler.use_solver = use_solver
            if args.solver_path != "auto":
                rt.guard.config.mode = args.solver_path
            rt.drain_pipeline = args.pipeline
            rt.pipeline_chunk_cycles = max(1, args.pipeline_chunk_cycles)
            rt.set_megaloop(args.megaloop)
            rt.set_mesh(mesh)
            if args.policy != "first-fit":
                rt.set_policy(args.policy, journal=False)
            _apply_trace_capacity(rt)
            _apply_slo(rt)
            return rt
        from kueue_tpu.controllers import ClusterRuntime

        rt = ClusterRuntime(
            use_solver=use_solver, tas_cache=TASCache(),
            solver_path=args.solver_path,
            drain_pipeline=args.pipeline,
            pipeline_chunk_cycles=args.pipeline_chunk_cycles,
            drain_megaloop=args.megaloop,
            mesh=mesh,
            policy=args.policy,
        )
        _apply_trace_capacity(rt)
        _apply_slo(rt)
        return rt

    def _apply_trace_capacity(rt):
        if args.trace_capacity <= 0:
            rt.tracer.enabled = False
        else:
            rt.tracer.max_traces = args.trace_capacity

    def _apply_slo(rt):
        slo = getattr(rt, "slo", None)
        if slo is None:
            return
        slo.configure(
            default_target_s=args.slo_target_p95,
            targets=slo_targets,
            objective=args.slo_objective,
            burn_window_s=args.slo_burn_window,
            burn_threshold=args.slo_burn_threshold,
            sustain_s=args.slo_sustain,
        )

    journal_opts = {
        "fsync_policy": args.journal_fsync,
        "fsync_interval_s": args.journal_fsync_interval,
        "segment_max_bytes": args.journal_segment_bytes,
    }
    # the durable-state anchor this process recovers from and
    # checkpoints to: a delta-chain directory or the classic full dump
    state_ref = args.state_dir or args.state
    runtime = build_runtime()
    journal = None
    checkpointer = None
    if args.journal:
        from kueue_tpu.storage import recover

        # crash recovery: checkpoint + replay of newer journal records
        # (torn tail truncated, stale fencing tokens refused), then the
        # invariant check — a violating state must not serve
        res = recover(
            state_ref, args.journal, runtime=runtime, strict=True,
            **journal_opts,
        )
        journal = res.journal
        print(f"journal recovery: {res.summary()}", flush=True)
        if args.state_dir:
            from kueue_tpu.storage import DeltaCheckpointer

            checkpointer = DeltaCheckpointer(
                args.state_dir,
                anchor_every=args.checkpoint_anchor_every,
                retain_chains=args.checkpoint_retain,
            ).open()
    elif args.state and os.path.exists(args.state):
        with open(args.state) as f:
            ser.runtime_from_state(json.load(f), runtime=runtime)
    srv = None  # assigned below; the callbacks close over it
    # last_token: the fencing token of our last tenure. A promotion
    # only reloads the checkpoint when the token moved — i.e. another
    # holder (or an unknown intermediary) intervened. Re-acquiring our
    # own still-valid lease after a transient renewal failure keeps the
    # token, so a lease flap must NOT roll the runtime back to a
    # checkpoint that predates writes we acknowledged. "boot" marks the
    # initial synchronous tick in srv.start(): we just loaded the same
    # checkpoint in main(), so reloading it again is pure waste.
    ha = {"last_token": None, "boot": True}

    def checkpoint() -> bool:
        if args.state_dir:
            return fenced_delta_checkpoint(srv)
        if not args.state:
            return True
        return fenced_checkpoint(srv, args.state)

    def on_promoted() -> None:
        tok = elector.lease.token
        first = ha["boot"]  # cleared in main() right after srv.start()
        resumed = ha["last_token"] is not None and ha["last_token"] == tok
        if first or resumed:
            ha["last_token"] = tok
            return
        # Record the token only AFTER a successful reload: if the
        # reload raises (transient volume error), tick() leaves us
        # non-leader and the NEXT promotion attempt must not classify
        # itself as a resume and skip the reload — that would lead with
        # the stale pre-takeover runtime.
        reloaded = (state_ref or args.journal) and promote_reload(
            srv, state_ref, build_runtime,
            journal_path=args.journal or "", journal_opts=journal_opts,
        )
        if reloaded and checkpointer is not None:
            # the fresh runtime journals into a fresh tracker; the
            # chain head on disk is still ours to extend
            srv.runtime.checkpointer = checkpointer
        ha["last_token"] = tok
        if reloaded:
            print(
                "promoted to leader; rebuilt state from checkpoint"
                + (" + journal replay" if args.journal else ""),
                flush=True,
            )

    elector = None
    if args.leader_elect_lease:
        import socket

        from kueue_tpu.utils.lease import FileLease, LeaderElector

        identity = (
            args.leader_elect_identity
            or f"{socket.gethostname()}-{os.getpid()}"
        )
        elector = LeaderElector(
            FileLease(
                args.leader_elect_lease,
                identity,
                duration=args.leader_elect_lease_duration,
            ),
            on_started_leading=on_promoted,
        )
    if journal is not None:
        # attach AFTER recovery (replay must not re-journal) and after
        # the elector exists, so records carry the live fencing token
        journal.token_provider = (
            (lambda: elector.lease.token) if elector is not None else None
        )
        runtime.attach_journal(journal)
        if checkpointer is not None:
            runtime.checkpointer = checkpointer
            print(
                "delta checkpoints: chain dir "
                f"{args.state_dir} (anchor every "
                f"{args.checkpoint_anchor_every} deltas, retaining "
                f"{args.checkpoint_retain} chain(s))",
                flush=True,
            )
    if args.elastic == "on":
        # elastic capacity plane: built AFTER journal attach/recovery
        # so grants journal durably and the plane adopts any
        # elastic_grant records replay already applied (it must never
        # re-ask the provider for capacity it provably holds)
        from kueue_tpu.elastic import SimulatedProvider, attach_elastic_plane

        limits = {}
        for spec in args.elastic_capacity_limit or []:
            fr, sep, amount = spec.partition("=")
            flavor, fsep, resource = fr.partition(":")
            if not sep or not fsep or not flavor or not resource:
                parser.error(
                    "--elastic-capacity-limit must be "
                    f"FLAVOR:RESOURCE=AMOUNT, got {spec!r}"
                )
            try:
                limits.setdefault(flavor, {})[resource] = int(amount)
            except ValueError:
                parser.error(
                    "--elastic-capacity-limit must be "
                    f"FLAVOR:RESOURCE=AMOUNT, got {spec!r}"
                )
        provider = SimulatedProvider(
            clock=runtime.clock,
            provision_delay_s=args.elastic_provision_delay,
            capacity_limits=limits or None,
        )
        attach_elastic_plane(runtime, provider=provider)
        print(
            "elastic capacity plane: provider "
            f"{args.capacity_provider} (delay "
            f"{args.elastic_provision_delay:g}s"
            + (f", limits {sorted(limits)}" if limits else "")
            + ")",
            flush=True,
        )
    if args.federation_worker:
        # federation manager mode: dispatch to remote worker control
        # planes over HTTP. Built AFTER journal attach so dispatch /
        # winner / retraction records are journaled, and the dispatcher
        # adopts any federation_* records recovery replayed.
        from kueue_tpu.admissionchecks.multikueue import MultiKueueCluster
        from kueue_tpu.admissionchecks.multikueue_transport import (
            HTTPTransport,
        )
        from kueue_tpu.federation import FederationDispatcher

        workers = {}
        worker_urls = {}
        for spec in args.federation_worker:
            name, sep, url = spec.partition("=")
            if not sep or not name or not url:
                parser.error(
                    f"--federation-worker must be NAME=URL, got {spec!r}"
                )
            worker_urls[name] = url
            workers[name] = MultiKueueCluster(
                name=name,
                transport=HTTPTransport(
                    url, token=args.federation_worker_token
                ),
            )
        dispatcher = FederationDispatcher(
            runtime,
            clusters=workers,
            worker_lost_timeout=args.federation_lost_timeout,
            fanout=args.federation_fanout,
        )
        print(
            f"federation manager: dispatching to {sorted(workers)}",
            flush=True,
        )
        if args.global_scheduler == "on":
            from kueue_tpu.federation import GlobalScheduler

            gs = GlobalScheduler(
                dispatcher,
                hysteresis_s=args.global_hysteresis,
                rescore_interval_s=args.global_rescore_interval,
            )
            # read each worker through the replica feed it already
            # serves (PR-9): one JournalTailer per wire-only worker
            # keeps a live read-only twin the forecasts run against
            for name, url in worker_urls.items():
                gs.attach_feed_reader(
                    name, url, token=args.federation_worker_token
                )
            print(
                "global scheduler: rescoring every "
                f"{args.global_rescore_interval:.0f}s, hysteresis "
                f"{args.global_hysteresis:.0f}s",
                flush=True,
            )
    elif args.global_scheduler == "on":
        parser.error("--global-scheduler requires --federation-worker")
    replica = None
    if args.replica_of:
        import socket

        from kueue_tpu.replica import ReadReplica

        replica = ReadReplica(
            args.replica_of,
            token=args.replica_token or args.auth_token,
            replica_id=(
                args.replica_id or f"{socket.gethostname()}-{os.getpid()}"
            ),
            build_runtime=build_runtime,
            poll_interval_s=args.replica_poll_interval,
        )
    tls = None
    if args.tls_cert_dir:
        from kueue_tpu.utils.cert import CertRotator

        sans = args.tls_dns_name or ["localhost", "127.0.0.1", args.host]
        # dedupe, keep order (the host may already be a default SAN)
        tls = CertRotator(args.tls_cert_dir, dns_names=list(dict.fromkeys(sans)))
    elif args.tls_cert:
        tls = (args.tls_cert, args.tls_key)
    gateway = None
    if args.gateway == "on":
        from kueue_tpu.gateway import TenantLimiter, WriteGateway

        limiter = None
        if args.gateway_tenant_rate > 0:
            limiter = TenantLimiter(
                args.gateway_tenant_rate,
                burst=args.gateway_tenant_burst or None,
            )
        gateway = WriteGateway(
            flush_interval_s=args.gateway_flush_interval,
            max_batch=args.gateway_max_batch,
            max_queue=args.gateway_queue_depth,
            limiter=limiter,
        )
        print(
            "gateway: coalescing writes "
            f"(flush window {args.gateway_flush_interval * 1e3:.1f} ms, "
            f"queue {args.gateway_queue_depth}, tenant rate "
            f"{args.gateway_tenant_rate or 'unlimited'}/s)",
            flush=True,
        )
    srv = KueueServer(
        runtime=runtime,
        host=args.host,
        port=args.port,
        auto_reconcile=not args.no_auto_reconcile,
        elector=elector,
        auth_token=args.auth_token,
        tls=tls,
        replica=replica,
        gateway=gateway,
    )
    port = srv.start()
    if replica is not None:
        # anchor on the leader's checkpoint (best-effort — an
        # unreachable leader leaves an empty replica retrying) and
        # start the tail loop
        replica.start()
    ha["boot"] = False  # any later promotion is a real takeover
    role = ""
    if elector is not None:
        role = " as leader" if elector.is_leader else " as standby"
    elif replica is not None:
        role = f" as read replica of {args.replica_of}"
    scheme = "https" if tls is not None else "http"
    print(
        f"kueue-tpu server listening on {scheme}://{args.host}:{port}{role}",
        flush=True,
    )
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    if hasattr(signal, "SIGUSR2"):
        # the pkg/debugger analog: kill -USR2 <pid> dumps queues,
        # cache, recent cycles, persistence/solver/replication posture
        # to stderr. Reads srv.runtime at SIGNAL time, so it follows
        # promotion/replica runtime swaps.
        from kueue_tpu import debugger

        signal.signal(
            signal.SIGUSR2,
            lambda *_: sys.stderr.write(debugger.dump(srv.runtime) + "\n"),
        )

    if args.elastic == "on" or args.federation_worker:
        # the elastic capacity loop is TIME-driven (provider delays,
        # retry backoffs) and drain-ahead scale-down re-dispatches
        # deposed placements from federation.step(): both only make
        # progress inside run_until_idle, which otherwise fires only on
        # API traffic — an in-flight grant or a drained placement could
        # sit forever on an idle server without this ticker
        tick = 1.0
        if args.elastic == "on":
            tick = max(0.2, min(2.0, args.elastic_provision_delay / 2))

        def _reconcile_loop():
            while not stop.wait(tick):
                try:
                    if elector is not None and not elector.is_leader:
                        continue
                    with srv.lock:
                        srv.runtime.run_until_idle()
                except Exception as e:  # noqa: BLE001 — a provider or
                    # worker hiccup must not kill the capacity loop for
                    # the rest of the process lifetime
                    print(f"background reconcile failed: {e!r}", flush=True)

        threading.Thread(target=_reconcile_loop, daemon=True).start()

    ckpt_thread = None
    if state_ref and args.state_checkpoint_period > 0:
        # Periodic leader checkpoints bound the data lost to a SIGKILL
        # (and are what a promoted standby reloads). Standbys never
        # checkpoint — on a shared state volume that would clobber the
        # leader's durable copy with a stale one — but they DO reload
        # each new checkpoint so their read endpoints (visibility,
        # metrics, dashboard, GETs) track the leader instead of serving
        # boot-time state forever.
        # start from the checkpoint main() already loaded: the first
        # standby iteration must not rebuild identical state
        # (a chain DIRECTORY's mtime moves when a checkpoint file lands
        # or is GC'd, so the standby refresh check works for both)
        reloaded_mtime = [
            os.path.getmtime(state_ref) if os.path.exists(state_ref) else 0.0
        ]

        def _ckpt_loop():
            while not stop.wait(args.state_checkpoint_period):
                try:
                    if elector is None or elector.is_leader:
                        checkpoint()
                    elif os.path.exists(state_ref):
                        mtime = os.path.getmtime(state_ref)
                        if mtime > reloaded_mtime[0]:
                            promote_reload(srv, state_ref, build_runtime,
                                           run_reconcile=False,
                                           require_standby=True)
                            reloaded_mtime[0] = mtime
                except Exception as e:  # noqa: BLE001 — any failure
                    # (volume error, serialization bug) must not
                    # silently kill periodic durability for the
                    # rest of the process lifetime
                    print(f"checkpoint failed: {e!r}", flush=True)

        ckpt_thread = threading.Thread(target=_ckpt_loop, daemon=True)
        ckpt_thread.start()

    stop.wait()
    was_leader = elector is None or elector.is_leader
    # write-safe shutdown: requests drain, THEN the final checkpoint
    # lands, THEN the lease is released — a standby promoted by the
    # release reloads a checkpoint that includes every accepted write
    final = {"saved": False}

    def _final_checkpoint() -> None:
        final["saved"] = checkpoint()

    if replica is not None:
        replica.stop()
    srv.stop(before_release=_final_checkpoint if was_leader else None)
    if ckpt_thread is not None:
        ckpt_thread.join(timeout=5)
    live_journal = getattr(srv.runtime, "journal", None)
    if live_journal is not None:
        live_journal.close()  # final fsync of any unsynced tail
    if state_ref and was_leader:
        if final["saved"]:
            print(f"state saved to {state_ref}", flush=True)
        else:
            # the fence refused the write: the lease lapsed during
            # drain and another replica owns the state file now
            print(
                f"final checkpoint SKIPPED (lease no longer held); "
                f"latest state lives with the current leader",
                flush=True,
            )
    return 0


if __name__ == "__main__":
    from kueue_tpu._jax import use_compile_cache

    use_compile_cache()
    sys.exit(main())
