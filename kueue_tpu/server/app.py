"""HTTP/JSON server: object API, visibility, metrics, jax-assign.

Routes (all JSON unless noted):

  GET  /healthz | /readyz                      liveness/readiness probes
                                               (cmd/kueue/main.go:181-189)
  GET  /metrics                                Prometheus text exposition
                                               (cmd/kueue/main.go:154-179)
  GET  /apis/visibility/v1beta1/clusterqueues/{cq}/pendingworkloads
  GET  /apis/visibility/v1beta1/namespaces/{ns}/localqueues/{lq}/pendingworkloads
                                               (pkg/visibility/server.go:62-118,
                                               api/v1beta1/pending_workloads_cq.go:37-46;
                                               items carry the latest structured
                                               inadmissibleReason from the audit trail)
  GET  /debug/workloads/{ns}/{name}/decisions  per-workload decision audit
                                               trail (core/audit.py) — the
                                               `kueuectl explain` payload
  POST /debug/plan                             what-if capacity planner
                                               (kueue_tpu/planner): scenario
                                               deltas (or an auto-generated
                                               sweep for a target) solved in
                                               one vmapped device launch —
                                               strictly read-only, leader
                                               only (forecasts the LEADER's
                                               next decisions; standby state
                                               may lag)
  GET  /apis/kueue/v1beta1/{section}           list objects w/ status
  POST /apis/kueue/v1beta1/{section}           upsert one object (webhook
                                               defaulting+validation applied)
  DELETE /apis/kueue/v1beta1/workloads/{ns}/{name}
  DELETE /apis/kueue/v1beta1/{clusterqueues|resourceflavors|nodes}/{name}
  POST /apis/kueue/v1beta1/workloads/{ns}/{name}/admissionchecks
                                               flip a check state — the
                                               phase-2 plugin boundary
                                               (admissioncheck_types.go:23-45)
  GET  /apis/kueue/v1beta1/journal?sinceSeq=N  replication feed (leader):
                                               journal records past N bundled
                                               with event-recorder and audit
                                               deltas — the read-replica tail
                                               (storage/tailer.py); registers
                                               the polling replica in the
                                               roster
  GET  /apis/kueue/v1beta1/replicas            replica roster (leader) or
                                               this replica's own status
  GET  /apis/kueue/v1beta1/events              recorded events (+resourceVersion)
  GET  /apis/kueue/v1beta1/{section}?watch=1&resourceVersion=N
                                               long-poll: blocks until events
                                               newer than N land (410 when N
                                               fell out of the ring — relist)
  GET  /events/stream                          Server-Sent-Events live tail
                                               (id: = resourceVersion, resumes
                                               via Last-Event-ID)
  POST /reconcile                              run_until_idle; returns cycles
  GET  /state                                  full state dump (checkpoint)
  POST /apis/solver/v1beta1/assign             stateless jax-assign: body is
                                               a serialized snapshot, reply
                                               is per-workload decisions
  GET  /                                       dashboard (kueueviz analog)
  GET  /api/dashboard                          dashboard JSON feed

The server owns one ClusterRuntime guarded by an RLock; handlers are
thin translations between the wire format (serialization.py) and
runtime calls. ThreadingHTTPServer gives per-request threads the way
the reference's apiservers do per-connection goroutines.
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from kueue_tpu import serialization as ser
from kueue_tpu import visibility
from kueue_tpu.models.constants import (
    AdmissionCheckStateType,
    WorkloadConditionType,
)
from kueue_tpu.tracing.phases import Phase, phase

# the batch apply's phases (tracing/phases.py), summed on the runtime
_INGEST_DECODE = Phase("ingest.decode")
_INGEST_APPLY = Phase("ingest.apply", counter="ingest.objects")


class _ServingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a serving-tier accept backlog. The
    stdlib default listen(5) RSTs concurrent connections the moment
    more than a handful of writers arrive between accept() calls —
    at gateway-scale ingest (dozens of concurrent POSTs) that
    surfaces as ConnectionResetError on the client."""

    request_queue_size = 256


class ApiError(Exception):
    def __init__(self, status: int, message: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.status = status
        self.message = message
        # 429 load-shedding: surfaced as the Retry-After header so
        # clients (KueueClient honors it with capped jittered backoff)
        # pace themselves instead of hammering a saturated gateway
        self.retry_after_s = retry_after_s


class _Section:
    """One object kind's wiring: wire<->model codecs, the runtime add
    method, and its store (one accessor serves both the point lookup
    and listing, so the mappings cannot diverge)."""

    def __init__(self, from_dict, to_dict, add_name, store_map, namespaced=False):
        self.from_dict = from_dict
        self.to_dict = to_dict
        self.add_name = add_name
        self.store_map = store_map  # runtime -> {key: model}
        self.namespaced = namespaced

    def lookup(self, rt, namespace: str, name: str):
        key = f"{namespace}/{name}" if self.namespaced else name
        return self.store_map(rt).get(key)


_SECTIONS: Dict[str, _Section] = {
    "resourceflavors": _Section(
        ser.flavor_from_dict, ser.flavor_to_dict, "add_flavor",
        lambda rt: rt.cache.flavors,
    ),
    "clusterqueues": _Section(
        ser.cq_from_dict,
        lambda m: ser.cq_to_dict(m.model if hasattr(m, "model") else m),
        "add_cluster_queue",
        lambda rt: rt.cache.cluster_queues,
    ),
    "localqueues": _Section(
        ser.lq_from_dict, ser.lq_to_dict, "add_local_queue",
        lambda rt: rt.cache.local_queues, namespaced=True,
    ),
    "workloads": _Section(
        ser.workload_from_dict, ser.workload_to_dict, "add_workload",
        lambda rt: rt.workloads, namespaced=True,
    ),
    "cohorts": _Section(
        ser.cohort_from_dict, ser.cohort_to_dict, "add_cohort",
        lambda rt: rt.cache.cohorts,
    ),
    "admissionchecks": _Section(
        ser.check_from_dict, ser.check_to_dict, "add_admission_check",
        lambda rt: rt.cache.admission_checks,
    ),
    "topologies": _Section(
        ser.topology_from_dict, ser.topology_to_dict, "add_topology",
        lambda rt: rt.cache.topologies,
    ),
    # TAS node inventory (the corev1.Node watch analog: a standalone
    # control plane ingests its topology capacity through its own API)
    "nodes": _Section(
        ser.node_from_dict, ser.node_to_dict, "add_node",
        lambda rt: (
            rt.cache.tas_cache.node_inventory
            if rt.cache.tas_cache is not None
            else {}
        ),
    ),
    "workloadpriorityclasses": _Section(
        ser.priority_class_from_dict, ser.priority_class_to_dict,
        "add_priority_class",
        lambda rt: rt.cache.priority_classes,
    ),
    # "events" is NOT here: it is read-only and served straight from
    # the runtime's EventRecorder (list + watch below), never upserted

    "limitranges": _Section(
        ser.limit_range_from_dict, ser.limit_range_to_dict, "add_limit_range",
        lambda rt: rt.limit_ranges, namespaced=True,
    ),
    "runtimeclasses": _Section(
        ser.runtime_class_from_dict, ser.runtime_class_to_dict,
        "add_runtime_class",
        lambda rt: rt.runtime_classes,
    ),
}


def solve_assign(request: dict) -> dict:
    """The ``jax-assign`` service: one nomination pass (or a full drain
    to idle) over a serialized snapshot, on the batched TPU solver.

    Stateless by design — the AdmissionCheck contract
    (admissioncheck_types.go:23-45) is that the controller observes a
    workload + cluster state and reports a verdict; feeding it explicit
    snapshots keeps the service free of watch machinery and lets one
    server serve many control planes.
    """
    state = request.get("state")
    if not isinstance(state, dict):
        raise ApiError(400, "body must carry a 'state' object")
    opts = request.get("options", {})
    use_solver = bool(opts.get("useSolver", True))
    until_idle = bool(opts.get("untilIdle", False))
    rt = ser.runtime_from_state(
        state,
        use_solver=use_solver,
        use_preempt_solver=use_solver,
    )
    cycles = 0
    decisions: List[dict] = []
    preemptions: List[dict] = []

    def observe(result) -> None:
        # per-cycle preemption targets via the scheduler's first-class
        # cycle-result hook; the bulk drain path reports through the
        # same surface (ClusterRuntime.bulk_drain -> notify_cycle)
        for entry in result.preempting:
            for tgt in entry.preemption_targets:
                preemptions.append(
                    {
                        "victim": tgt.workload.workload.key,
                        "by": entry.workload.key,
                        "reason": tgt.reason,
                    }
                )

    rt.scheduler.cycle_observers.append(observe)
    try:
        if until_idle:
            cycles = rt.run_until_idle()
        else:
            rt.schedule_once()
            cycles = 1
    finally:
        rt.scheduler.cycle_observers.remove(observe)
    for key in sorted(rt.workloads):
        wl = rt.workloads[key]
        item = {
            "workload": key,
            "outcome": (
                "Admitted"
                if wl.is_admitted
                else "QuotaReserved"
                if wl.has_quota_reservation
                else "Pending"
            ),
        }
        if wl.admission is not None:
            item["admission"] = ser.workload_to_dict(wl)["admission"]
        else:
            latest = rt.audit.latest(key)
            if latest is not None:
                item["inadmissibleReason"] = latest.reason.value
                item["message"] = latest.message
        decisions.append(item)
    return {
        "cycles": cycles,
        "decisions": decisions,
        "preemptions": preemptions,
        "resolution": "device" if use_solver else "host",
    }


class KueueServer:
    """Owns the runtime + HTTP server. start()/stop() for embedding in
    tests; ``python -m kueue_tpu.server`` for standalone use."""

    def __init__(
        self,
        runtime=None,
        host: str = "127.0.0.1",
        port: int = 0,
        auto_reconcile: bool = True,
        validators: Optional[list] = None,
        elector=None,  # utils.lease.LeaderElector: HA replica mode
        auth_token: Optional[str] = None,
        tls=None,  # utils.cert.CertRotator, or (cert_path, key_path)
        replica=None,  # replica.ReadReplica: journal-tailing follower
        gateway=None,  # gateway.WriteGateway: coalescing write path
    ):
        if runtime is None:
            from kueue_tpu.controllers import ClusterRuntime
            from kueue_tpu.tas import TASCache

            # TAS-capable by default: a standalone control plane must
            # be able to ingest node inventory through its own API
            runtime = ClusterRuntime(tas_cache=TASCache())
        self.runtime = runtime
        self.lock = threading.RLock()
        # serving-surface clock: the runtime's injected clock when it
        # has one (FakeClock tests drive roster staleness and feed
        # leaderTime through it), a fresh Clock otherwise
        clock = getattr(runtime, "clock", None)
        if clock is None:
            from kueue_tpu.utils.clock import Clock

            clock = Clock()
        self.clock = clock
        self.auto_reconcile = auto_reconcile
        if validators is None:
            from kueue_tpu.webhooks import default_admission_chain

            validators = default_admission_chain()
        # admission chain: callables (section, obj_dict, old_obj|None,
        # runtime) -> possibly-mutated obj_dict, raising ApiError on
        # rejection (the webhook layer; pkg/webhooks/webhooks.go:25)
        self.validators = list(validators)
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._host = host
        self._port = port
        # Leader election (leader_aware_reconciler.go analog): with an
        # elector configured, only the leader accepts mutating calls;
        # standbys keep serving reads (visibility, metrics, dashboard,
        # stateless solves) and take over when the lease lapses.
        self.elector = elector
        # Bearer-token authentication for the secured surface: mutating
        # routes, metrics, state and debug (the reference serves metrics
        # behind authn/z and its write paths through the authenticated
        # apiserver — cmd/kueue/main.go:154-179). None = open (dev mode,
        # in-cluster behind a NetworkPolicy). Probes, visibility and the
        # dashboard stay open either way.
        self.auth_token = auth_token
        # TLS serving (cmd/kueue/main.go:154-179: secure serving with a
        # cert watcher over rotated files). A CertRotator gives
        # self-managed certs with pre-expiry rotation hot-reloaded into
        # the live SSLContext; a (cert, key) path pair is the
        # provided-certificates mode.
        self.tls = tls
        self._ssl_context = None
        self._tls_rotation_stop = threading.Event()
        self._tls_rotation_thread: Optional[threading.Thread] = None
        self._election_stop = threading.Event()
        self._election_thread: Optional[threading.Thread] = None
        # checkpoint ordering (used by __main__.fenced_checkpoint): a
        # snapshot serialized earlier must never replace one serialized
        # later, even if its disk write happens last
        self._ckpt_seq = 0
        self._ckpt_written = 0
        self._ckpt_write_lock = threading.Lock()
        # flipped by stop(): parked watch long-polls and SSE tails
        # check it so shutdown never waits out a full poll window
        self._stopping = threading.Event()
        # Read-replica mode (kueue_tpu/replica): a journal-tailing
        # follower serving watch/SSE, visibility, explain and
        # best-effort-stale plan from replayed leader state; every
        # mutating route 307-redirects to the leader. The replica
        # installs its runtime (and every resync rebuild) through
        # self.lock, replacing whatever runtime= was passed.
        self.replica = replica
        # leader-side follower roster, fed by the replication feed's
        # ?replica=...&appliedSeq=... poll params (kueuectl replicas)
        self.replica_roster: Dict[str, dict] = {}
        if replica is not None:
            replica.attach(self)
        # Write-path gateway (kueue_tpu/gateway): when attached, every
        # workload POST / batch section drains through the bounded
        # coalescing queue — one serving-lock critical section, one
        # group-committed journal sync and one admission pass per flush
        # window — with per-tenant token-bucket shedding (429 +
        # Retry-After). Leader-side only (replicas redirect writes).
        self.gateway = gateway
        if gateway is not None:
            gateway.attach(self)

    def require_leader(self) -> None:
        if self.elector is not None and not self.elector.is_leader:
            raise ApiError(
                503,
                "not leader; writes are served by "
                f"{self.elector.lease.holder() or 'no current holder'}",
            )

    # ---- object API ----
    def _find_existing(self, section: str, obj: dict):
        """Wire dict of the stored object with the same identity, via a
        direct store lookup (no full-state serialization on the ingest
        path)."""
        sec = _SECTIONS.get(section)
        if sec is None:
            return None
        model = sec.lookup(
            self.runtime, obj.get("namespace", ""), obj.get("name", "")
        )
        return sec.to_dict(model) if model is not None else None

    def apply(self, section: str, obj: dict, reconcile: bool = True) -> dict:
        """Upsert one object through the webhook admission chain."""
        sec = _SECTIONS.get(section)
        if sec is None:
            raise ApiError(404, f"unknown section {section!r}")
        from kueue_tpu.webhooks import ValidationError

        self.require_leader()
        with self.lock:
            old = self._find_existing(section, obj)
            try:
                for admit in self.validators:
                    obj = admit(section, obj, old, self.runtime)
            except ValidationError as e:
                raise ApiError(422, str(e))
            if section == "nodes" and self.runtime.cache.tas_cache is None:
                # add_node would silently no-op: acknowledging a write
                # we discarded is worse than refusing it
                raise ApiError(
                    409, "runtime has no TAS cache; node inventory disabled"
                )
            try:
                model = sec.from_dict(obj)
            except (KeyError, TypeError, ValueError) as e:
                # a codec miss is the CALLER's malformed body, not a
                # server fault — 400, never a 500 stack trace
                raise ApiError(400, f"malformed {section} object: {e!r}")
            getattr(self.runtime, sec.add_name)(model)
            if reconcile and self.auto_reconcile:
                self.runtime.run_until_idle()
        return obj

    def delete(self, section: str, namespace: str, name: str) -> None:
        self.require_leader()
        with self.lock:
            if section == "workloads":
                wl = self.runtime.workloads.get(f"{namespace}/{name}")
                if wl is None:
                    raise ApiError(404, f"workload {namespace}/{name} not found")
                self.runtime.delete_workload(wl)
            elif section == "clusterqueues":
                if name not in self.runtime.cache.cluster_queues:
                    raise ApiError(404, f"clusterqueue {name} not found")
                self.runtime.delete_cluster_queue(name)
            elif section == "resourceflavors":
                if name not in self.runtime.cache.flavors:
                    raise ApiError(404, f"resourceflavor {name} not found")
                try:
                    self.runtime.delete_flavor(name)
                except ValueError as e:
                    # the ResourceFlavor finalizer's user-visible effect
                    raise ApiError(409, str(e))
            elif section == "nodes":
                tc = self.runtime.cache.tas_cache
                if tc is None or name not in tc.node_inventory:
                    raise ApiError(404, f"node {name} not found")
                self.runtime.delete_node(name)
            else:
                raise ApiError(405, f"delete not supported for {section}")
            if self.auto_reconcile:
                self.runtime.run_until_idle()

    def set_admission_check_state(
        self, namespace: str, name: str, check: str, state: str, message: str = ""
    ) -> None:
        """External controller flips a check — phase 2 of two-phase
        admission (workload_controller.go:251-275 syncs the Admitted
        condition on the next reconcile)."""
        self.require_leader()
        with self.lock:
            wl = self.runtime.workloads.get(f"{namespace}/{name}")
            if wl is None:
                raise ApiError(404, f"workload {namespace}/{name} not found")
            try:
                state_t = AdmissionCheckStateType(state)
            except ValueError:
                raise ApiError(400, f"invalid check state {state!r}")
            from kueue_tpu.models.admission_check import AdmissionCheckState

            wl.admission_check_states[check] = AdmissionCheckState(
                name=check, state=state_t, message=message
            )
            if self.auto_reconcile:
                self.runtime.run_until_idle()

    def get_object(self, section: str, namespace: str, name: str) -> dict:
        sec = _SECTIONS.get(section)
        if sec is None:
            raise ApiError(404, f"unknown section {section!r}")
        with self.lock:
            model = sec.lookup(self.runtime, namespace, name)
            if model is None:
                raise ApiError(404, f"{section[:-1]} {namespace}/{name} not found")
            obj = sec.to_dict(model)
            if section == "clusterqueues":
                # QueueVisibility (gated): the reference publishes the
                # interval snapshots into CQ .status.pendingWorkloadsStatus
                # (clusterqueue_controller.go snapshot worker)
                snap = self.runtime.cq_pending_snapshots.get(name)
                if snap is not None:
                    obj.setdefault("status", {})["pendingWorkloadsStatus"] = {
                        "clusterQueuePendingWorkload": snap,
                    }
            return obj

    @staticmethod
    def validate_batch_body(body: dict) -> None:
        """Shape check shared by the serial and gateway batch paths:
        unknown sections and non-list values are the CALLER's malformed
        request — refused whole, before anything applies."""
        unknown = [s for s in body if s not in _SECTIONS]
        if unknown:
            raise ApiError(404, f"unknown sections {unknown}")
        for section, objs in body.items():
            if not isinstance(objs, list):
                raise ApiError(400, f"section {section!r} must be a list")

    def apply_batch(self, body: dict) -> dict:
        """Bulk upsert: {section: [objects]} in one request (the
        MultiKueue batched-dispatch wire). Each object still passes the
        webhook admission chain; reconcile runs once at the end.

        Partial-failure semantics: one bad object rejects THAT object,
        not the whole batch — the response carries per-section
        applied/rejected counts plus the first error, so a mixed batch
        lands its good workloads while the caller learns exactly what
        bounced (HTTPTransport.create_workloads turns a non-empty
        rejected map back into RemoteRejected for federation)."""
        self.require_leader()
        self.validate_batch_body(body)
        applied: Dict[str, int] = {}
        rejected: Dict[str, int] = {}
        first_error: Optional[str] = None
        any_applied = False
        with phase(
            _INGEST_APPLY, getattr(self.runtime, "phases", None)
        ) as ingest:
            for section, objs in body.items():
                for i, obj in enumerate(objs):
                    try:
                        self.apply(section, obj, reconcile=False)
                        applied[section] = applied.get(section, 0) + 1
                        any_applied = True
                    except ApiError as e:
                        rejected[section] = rejected.get(section, 0) + 1
                        if first_error is None:
                            first_error = f"{section}[{i}]: {e.message}"
            ingest.count = sum(applied.values())
        if self.auto_reconcile and any_applied:
            with self.lock:
                self.runtime.run_until_idle()
        return {
            "applied": applied,
            "rejected": rejected,
            "firstError": first_error,
        }

    def list_section(self, section: str) -> dict:
        sec = _SECTIONS.get(section)
        if sec is None:
            raise ApiError(404, f"unknown section {section!r}")
        with self.lock:
            items = [
                sec.to_dict(m)
                for _, m in sorted(sec.store_map(self.runtime).items())
            ]
        return {"items": items}

    # ---- http plumbing ----
    def _load_certs(self) -> None:
        """(Re)load the serving cert into the live SSLContext — new
        handshakes pick it up immediately (the certwatcher analog)."""
        if hasattr(self.tls, "cert_path"):
            cert_path, key_path = self.tls.cert_path, self.tls.key_path
        else:
            cert_path, key_path = self.tls
        self._ssl_context.load_cert_chain(cert_path, key_path)

    def _tls_rotation_loop(self, period: float) -> None:
        import sys
        import traceback

        while not self._tls_rotation_stop.wait(period):
            try:
                self.tls.maybe_rotate()
            except Exception:  # noqa: BLE001 — a transient IO error on
                # the cert volume must not kill the rotation loop (the
                # cert would then silently expire in place) — but it
                # must be VISIBLE: a persistently failing rotation ends
                # in an expired cert ~a refresh window later, and the
                # operator needs the trail
                print("tls cert rotation failed:", file=sys.stderr)
                traceback.print_exc()

    def start(self, tls_rotation_period_s: float = 3600.0) -> int:
        self._stopping.clear()
        handler = _make_handler(self)
        self._httpd = _ServingHTTPServer((self._host, self._port), handler)
        if self.tls is not None:
            import ssl

            if hasattr(self.tls, "ensure"):
                self.tls.ensure()
            self._ssl_context = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            self._load_certs()
            if hasattr(self.tls, "reload_hooks"):
                self.tls.reload_hooks.append(self._load_certs)
            # handshake lazily in the per-request worker thread, NOT in
            # the accept loop: with the default do_handshake_on_connect
            # a single client that connects and sends nothing would
            # block accept() — and with it every other connection,
            # including the HTTPS probes — indefinitely. The handler
            # timeout below bounds a stalled handshake to its own
            # worker thread.
            self._httpd.socket = self._ssl_context.wrap_socket(
                self._httpd.socket,
                server_side=True,
                do_handshake_on_connect=False,
            )
            if handler.timeout is None:
                handler.timeout = 60.0
            if hasattr(self.tls, "maybe_rotate"):
                self._tls_rotation_stop.clear()
                self._tls_rotation_thread = threading.Thread(
                    target=self._tls_rotation_loop,
                    args=(tls_rotation_period_s,),
                    daemon=True,
                )
                self._tls_rotation_thread.start()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        if self.gateway is not None:
            self.gateway.start()
        if self.elector is not None:
            self.elector.tick()  # contend immediately, then renew async
            self._election_stop.clear()
            self._election_thread = threading.Thread(
                target=self._election_loop, daemon=True
            )
            self._election_thread.start()
        return self._httpd.server_address[1]

    def _election_loop(self) -> None:
        # renew at a third of the lease duration, the same ratio as
        # client-go's RenewDeadline/LeaseDuration defaults
        period = max(self.elector.lease.duration / 3.0, 0.05)
        while not self._election_stop.wait(period):
            try:
                self.elector.tick()
            except Exception:  # noqa: BLE001 — a transient IO error on
                # the lease volume must not kill the election loop (the
                # lease would then silently lapse / never be contended)
                pass

    def stop(self, before_release=None) -> None:
        """Shut down in write-safe order: stop accepting requests
        FIRST, then run ``before_release`` (the final state checkpoint),
        then release the lease — so a standby can only take over after
        the checkpoint it will reload from is fully on disk."""
        self._stopping.set()  # unpark watch long-polls / SSE tails
        if self._tls_rotation_thread is not None:
            self._tls_rotation_stop.set()
            self._tls_rotation_thread.join(timeout=5)
            self._tls_rotation_thread = None
        if self.tls is not None and hasattr(self.tls, "reload_hooks"):
            try:
                self.tls.reload_hooks.remove(self._load_certs)
            except ValueError:
                pass
        if self._election_thread is not None:
            self._election_stop.set()
            self._election_thread.join(timeout=5)
            self._election_thread = None
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self.gateway is not None:
            # after the HTTP drain: whatever the gateway still queues
            # belongs to already-answered or dropped connections; the
            # stop() flush applies it before the final checkpoint
            self.gateway.stop()
        if before_release is not None:
            before_release()
        if self.elector is not None:
            self.elector.step_down()

    @property
    def port(self) -> int:
        return self._httpd.server_address[1] if self._httpd else self._port


# route names gated by KueueServer.auth_token (when configured)
_SECURED_ROUTES = frozenset(
    {
        "apply", "apply_batch", "delete", "delete_ns", "check_state",
        "reconcile", "solve", "metrics", "state", "debug_cycles",
        "workload_decisions", "plan", "quarantine_list", "quarantine_clear",
        # the replication feed serializes every state mutation — gate
        # it exactly like /state
        "journal_tail",
        # trace stores expose workload identities + timing: same gate
        # as the decision audit surface
        "debug_traces", "debug_trace_get", "workload_trace",
        # dynamic membership mutates the dispatch roster (drain moves
        # real placements) — gate like every other write
        "federation_add_worker", "federation_remove_worker",
        "federation_membership",
    }
)

# mutating routes a read replica refuses: 307 to the leader, method and
# body preserved (kueuectl and KueueClient follow it transparently).
# NOT here: "solve" (stateless compute over a posted snapshot) and
# "plan" (read-only what-if over the replayed state — best-effort-stale
# by design, documented in deploy/README).
_REPLICA_REDIRECTED = frozenset(
    {
        "apply", "apply_batch", "delete", "delete_ns", "check_state",
        "reconcile", "quarantine_clear",
    }
)

_ROUTES: List[Tuple[str, re.Pattern, str]] = [
    ("GET", re.compile(r"^/healthz$"), "healthz"),
    ("GET", re.compile(r"^/readyz$"), "readyz"),
    ("GET", re.compile(r"^/metrics$"), "metrics"),
    (
        "GET",
        re.compile(
            r"^/apis/visibility/v1beta1/clusterqueues/([^/]+)/pendingworkloads$"
        ),
        "vis_cq",
    ),
    (
        "GET",
        re.compile(
            r"^/apis/visibility/v1beta1/namespaces/([^/]+)/localqueues/([^/]+)/pendingworkloads$"
        ),
        "vis_lq",
    ),
    (
        "POST",
        re.compile(r"^/apis/kueue/v1beta1/workloads/([^/]+)/([^/]+)/admissionchecks$"),
        "check_state",
    ),
    # literal routes FIRST: the generic section pattern below would
    # swallow "journal"/"replicas"/"slo" as object listings
    ("GET", re.compile(r"^/apis/kueue/v1beta1/journal$"), "journal_tail"),
    ("GET", re.compile(r"^/apis/kueue/v1beta1/replicas$"), "replicas"),
    ("GET", re.compile(r"^/apis/kueue/v1beta1/slo$"), "slo"),
    ("GET", re.compile(r"^/apis/kueue/v1beta1/([a-z]+)$"), "list"),
    (
        "GET",
        re.compile(r"^/apis/kueue/v1beta1/([a-z]+)/([^/]+)/([^/]+)$"),
        "get_ns",
    ),
    ("GET", re.compile(r"^/apis/kueue/v1beta1/([a-z]+)/([^/]+)$"), "get"),
    ("POST", re.compile(r"^/apis/kueue/v1beta1/batch$"), "apply_batch"),
    ("POST", re.compile(r"^/apis/kueue/v1beta1/([a-z]+)$"), "apply"),
    (
        "DELETE",
        re.compile(r"^/apis/kueue/v1beta1/(workloads)/([^/]+)/([^/]+)$"),
        "delete_ns",
    ),
    (
        "DELETE",
        re.compile(r"^/apis/kueue/v1beta1/(clusterqueues|resourceflavors|nodes)/([^/]+)$"),
        "delete",
    ),
    (
        "GET",
        re.compile(
            r"^/apis/kueue/v1beta1/localqueues/([^/]+)/([^/]+)/status$"
        ),
        "lq_status",
    ),
    (
        "GET",
        re.compile(r"^/apis/federation/v1beta1/clusters$"),
        "federation_clusters",
    ),
    (
        "GET",
        re.compile(r"^/apis/federation/v1beta1/status$"),
        "federation_status",
    ),
    (
        "POST",
        re.compile(r"^/apis/federation/v1beta1/clusters$"),
        "federation_add_worker",
    ),
    (
        "POST",
        re.compile(
            r"^/apis/federation/v1beta1/clusters/([^/]+)/(cordon|uncordon|drain)$"
        ),
        "federation_membership",
    ),
    (
        "DELETE",
        re.compile(r"^/apis/federation/v1beta1/clusters/([^/]+)$"),
        "federation_remove_worker",
    ),
    ("GET", re.compile(r"^/apis/elastic/v1beta1/capacity$"), "capacity"),
    ("GET", re.compile(r"^/global/standings$"), "global_standings"),
    ("POST", re.compile(r"^/reconcile$"), "reconcile"),
    ("GET", re.compile(r"^/events/stream$"), "events_stream"),
    ("GET", re.compile(r"^/debug/cycles$"), "debug_cycles"),
    ("GET", re.compile(r"^/debug/traces$"), "debug_traces"),
    ("GET", re.compile(r"^/debug/traces/([^/]+)$"), "debug_trace_get"),
    (
        "GET",
        re.compile(r"^/debug/workloads/([^/]+)/([^/]+)/trace$"),
        "workload_trace",
    ),
    ("GET", re.compile(r"^/debug/quarantine$"), "quarantine_list"),
    ("POST", re.compile(r"^/debug/quarantine/clear$"), "quarantine_clear"),
    ("POST", re.compile(r"^/debug/plan$"), "plan"),
    (
        "GET",
        re.compile(r"^/debug/workloads/([^/]+)/([^/]+)/decisions$"),
        "workload_decisions",
    ),
    ("GET", re.compile(r"^/state$"), "state"),
    ("POST", re.compile(r"^/apis/solver/v1beta1/assign$"), "solve"),
    ("GET", re.compile(r"^/api/dashboard$"), "dashboard_json"),
    ("GET", re.compile(r"^/$"), "dashboard_html"),
]


def _make_handler(srv: KueueServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet by default
            pass

        # ---- dispatch ----
        def _dispatch(self, method: str):
            parsed = urlparse(self.path)
            query = {k: v[-1] for k, v in parse_qs(parsed.query).items()}
            for m, pat, name in _ROUTES:
                if m != method:
                    continue
                match = pat.match(parsed.path)
                if match:
                    try:
                        if (
                            srv.replica is not None
                            and name in _REPLICA_REDIRECTED
                        ):
                            # writes belong to the leader: 307 keeps
                            # method + body intact across the redirect
                            self._send_redirect(
                                srv.replica.leader_url + self.path
                            )
                            return
                        self._check_auth(name)
                        getattr(self, f"_h_{name}")(*match.groups(), **{"query": query})
                    except ApiError as e:
                        headers = None
                        if e.retry_after_s is not None:
                            # shed writes tell the client when to come
                            # back; KueueClient backs off on it
                            headers = {
                                "Retry-After": f"{e.retry_after_s:.3f}"
                            }
                        self._send_json(
                            {"error": e.message}, status=e.status,
                            headers=headers,
                        )
                    except Exception as e:  # noqa: BLE001 — surface as 500
                        self._send_json({"error": repr(e)}, status=500)
                    return
            self._send_json({"error": f"no route for {method} {parsed.path}"}, 404)

        def do_GET(self):
            self._dispatch("GET")

        def do_POST(self):
            self._dispatch("POST")

        def do_DELETE(self):
            self._dispatch("DELETE")

        # ---- helpers ----
        def _check_auth(self, route_name: str) -> None:
            if srv.auth_token is None or route_name not in _SECURED_ROUTES:
                return
            import hmac

            header = self.headers.get("Authorization", "")
            expect = f"Bearer {srv.auth_token}"
            if not hmac.compare_digest(header.encode(), expect.encode()):
                # the rejected request's body was never read: drain it
                # (and drop the connection) so a keep-alive client's
                # next request is not parsed out of the stale bytes
                length = int(self.headers.get("Content-Length", 0))
                if length:
                    self.rfile.read(length)
                self.close_connection = True
                raise ApiError(401, "missing or invalid bearer token")

        def _body(self) -> dict:
            length = int(self.headers.get("Content-Length", 0))
            if length == 0:
                return {}
            raw = self.rfile.read(length)
            try:
                return json.loads(raw)
            except json.JSONDecodeError as e:
                raise ApiError(400, f"invalid JSON body: {e}")

        def _send_json(self, obj, status: int = 200, headers=None) -> None:
            payload = json.dumps(obj).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            for name, value in (headers or {}).items():
                self.send_header(name, value)
            if srv.replica is not None:
                # every replica-served read is labeled with its role +
                # staleness so clients (kueuectl) can tell the user the
                # answer may trail the leader
                self.send_header("X-Kueue-Role", "replica")
                self.send_header(
                    "X-Kueue-Replica-Lag",
                    f"{srv.replica.tailer.lag_s:.3f}",
                )
            if self.close_connection:
                # tell keep-alive clients not to reuse the connection
                # (set by the auth rejection path)
                self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(payload)

        def _send_redirect(self, location: str) -> None:
            """307: same method + body at the leader. The unread
            request body is drained (and the connection dropped) so a
            keep-alive client's next request does not parse out of the
            stale bytes."""
            length = int(self.headers.get("Content-Length", 0))
            if length:
                self.rfile.read(length)
            self.close_connection = True
            payload = json.dumps(
                {"error": "read replica: writes are served by the leader",
                 "leader": location}
            ).encode()
            self.send_response(307)
            self.send_header("Location", location)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(payload)

        def _send_text(self, text: str, content_type: str, status: int = 200) -> None:
            payload = text.encode()
            self.send_response(status)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        # ---- handlers ----
        def _h_healthz(self, query):
            body = {"status": "ok"}
            journal = getattr(srv.runtime, "journal", None)
            if journal is not None:
                st = journal.stats()
                # degraded persistence is a health DETAIL, not a
                # liveness failure: restarting the pod cannot fix a
                # full volume, so the probe stays 200 and the operator
                # pages on status/kueue_journal_degraded instead
                if st.degraded:
                    body["status"] = "degraded"
                body["persistence"] = {
                    "mode": "degraded" if st.degraded else "journaling",
                    "journalSegments": st.segments,
                    "journalBytes": st.bytes,
                    "journalReclaimedBytes": st.reclaimed_bytes,
                    "lastSeq": st.last_seq,
                    "droppedAppends": st.dropped_appends,
                    "lastError": st.last_error,
                    "lastFsyncAgeS": st.last_fsync_age_s,
                }
            # delta-checkpoint chain posture (storage/checkpoint.py):
            # same convention — a failing chain write (ENOSPC on the
            # state volume) flips "degraded" while the probe stays 200
            # (the previous chain is still valid; the operator pages
            # on kueue_checkpoint_degraded / this detail), and the
            # next successful checkpoint self-heals it
            ckpt = getattr(srv.runtime, "checkpointer", None)
            if ckpt is not None:
                detail = ckpt.status()
                body.setdefault("persistence", {})["checkpoint"] = detail
                if detail["degraded"]:
                    body["status"] = "degraded"
                    body["persistence"]["mode"] = "degraded"
            # solver-path detail (core/guard.py): same journal-degraded
            # convention — an open/quarantined device circuit or any
            # quarantined workload flips "degraded" while the probe
            # stays 200 (admission still runs, on the host mirror)
            guard = getattr(
                getattr(srv.runtime, "scheduler", None), "guard", None
            )
            if guard is not None:
                detail = guard.health()
                quarantine = getattr(srv.runtime, "quarantine", None)
                detail["quarantinedWorkloads"] = (
                    len(quarantine) if quarantine is not None else 0
                )
                body["solver"] = detail
                if guard.degraded or detail["quarantinedWorkloads"]:
                    body["status"] = "degraded"
            # active admission policy (kueue_tpu/policy): informational
            # — the dashboard badge and runbooks read it here
            policy = getattr(srv.runtime, "policy", None)
            if policy is not None:
                body["policy"] = policy.name
            # gateway serving tier (kueue_tpu/gateway): ingest posture
            # — queue depth, coalescing stats, per-reason shed counts
            if srv.gateway is not None:
                body["gateway"] = srv.gateway.status()
            # admission SLOs: attainment + burn per targeted CQ; a
            # SUSTAINED error-budget burn flips "degraded" while the
            # probe stays 200 (admission still runs — the operator
            # pages on kueue_slo_degraded / this detail)
            slo = getattr(srv.runtime, "slo", None)
            if slo is not None and slo.enabled:
                slo.maybe_refresh()
                detail = slo.report()
                body["slo"] = detail
                if detail["degraded"]:
                    body["status"] = "degraded"
            # federation detail (kueue_tpu/federation): same convention
            # — a lost, quarantined or gray (probation) worker cluster
            # flips "degraded" while the probe stays 200 (the
            # dispatcher keeps routing around it; the operator pages on
            # the detail / kueue_multikueue_clusters_active /
            # kueue_worker_health instead)
            fed = getattr(srv.runtime, "federation", None)
            if fed is not None:
                detail = fed.health_report()
                body["federation"] = detail
                if detail["degraded"]:
                    body["status"] = "degraded"
            # replication detail (kueue_tpu/replica): a replica reports
            # its staleness (appliedSeq, lagSeconds) here; a failing
            # tail flips "degraded" — the replica still serves its last
            # consistent state, so the probe stays 200 and the operator
            # pages on kueue_replica_lag_seconds / this detail
            if srv.replica is not None:
                detail = srv.replica.status()
                body["replication"] = detail
                if detail.get("lastError"):
                    body["status"] = "degraded"
            elif srv.replica_roster:
                from kueue_tpu.replica import replication_section

                detail = replication_section(srv.runtime)
                detail["replicas"] = len(srv.replica_roster)
                body["replication"] = detail
            self._send_json(body)

        def _h_readyz(self, query):
            # standby replicas are Ready (they serve reads) but report
            # their role so probes/operators can tell them apart
            body = {"status": "ok"}
            if srv.elector is not None:
                body["leader"] = srv.elector.is_leader
                body["holder"] = srv.elector.lease.holder()
                body["identity"] = srv.elector.identity
            self._send_json(body)

        def _h_metrics(self, query):
            slo = getattr(srv.runtime, "slo", None)
            if slo is not None:
                # scrape-time refresh so kueue_slo_* gauges are current
                slo.maybe_refresh()
            with srv.lock:
                text = srv.runtime.metrics.registry.expose()
            self._send_text(text, "text/plain; version=0.0.4")

        def _h_slo(self, query):
            """Admission-SLO standings (the `kueuectl slo` payload):
            per-ClusterQueue target, attainment ratio and error-budget
            burn rate over the configured window."""
            slo = getattr(srv.runtime, "slo", None)
            if slo is None:
                raise ApiError(404, "slo tracking is not available")
            slo.maybe_refresh()
            self._send_json(slo.report())

        def _int_param(self, query, key, default):
            try:
                return int(query.get(key, default))
            except ValueError:
                raise ApiError(400, f"query parameter {key!r} must be an integer")

        def _h_vis_cq(self, cq, query):
            offset = self._int_param(query, "offset", 0)
            limit = self._int_param(query, "limit", 1000)
            with srv.lock:
                summary = visibility.pending_workloads_in_cq(
                    srv.runtime.queues, cq, offset=offset, limit=limit,
                    audit=getattr(srv.runtime, "audit", None),
                )
            self._send_json(_summary_to_dict(summary))

        def _h_vis_lq(self, ns, lq, query):
            offset = self._int_param(query, "offset", 0)
            limit = self._int_param(query, "limit", 1000)
            with srv.lock:
                summary = visibility.pending_workloads_in_lq(
                    srv.runtime.queues, ns, lq, offset=offset, limit=limit,
                    audit=getattr(srv.runtime, "audit", None),
                )
            self._send_json(_summary_to_dict(summary))

        # section -> the event ``regarding.kind`` a watch on it filters
        # to ("events" itself is unfiltered). Today every emission site
        # regards a Workload; the map keeps the route shape K8s-true so
        # future kinds slot in without a new URL scheme.
        _REGARDING = {
            "events": None,
            "workloads": "Workload",
            "clusterqueues": "ClusterQueue",
            "localqueues": "LocalQueue",
        }

        def _h_list(self, section, query):
            if query.get("watch") in ("1", "true"):
                return self._watch(section, query)
            if section == "events":
                rec = srv.runtime.events
                items, _ = rec.since(
                    self._int_param(query, "resourceVersion", 0)
                )
                return self._send_json(
                    {"items": items, "resourceVersion": rec.resource_version}
                )
            self._send_json(srv.list_section(section))

        def _watch(self, section, query):
            """resourceVersion long-poll (the apiserver watch analog):
            blocks OUTSIDE srv.lock until the recorder stamps something
            newer than the client's resourceVersion, then returns the
            delta. 410 when the version fell out of the bounded ring —
            the client must relist and re-watch from the fresh head."""
            if section != "events" and section not in _SECTIONS:
                raise ApiError(404, f"unknown section {section!r}")
            regarding = self._REGARDING.get(
                section, section[:-1].capitalize()
            )
            rv = self._int_param(query, "resourceVersion", 0)
            try:
                timeout = min(float(query.get("timeoutSeconds", 30)), 300.0)
            except ValueError:
                raise ApiError(400, "timeoutSeconds must be a number")
            rec = srv.runtime.events
            items, latest, too_old = rec.wait(
                rv, timeout, regarding_kind=regarding,
                should_stop=srv._stopping.is_set,
            )
            if too_old:
                raise ApiError(
                    410,
                    f"resourceVersion {rv} is too old; relist and "
                    f"re-watch from {latest}",
                )
            self._send_json({"items": items, "resourceVersion": latest})

        def _h_events_stream(self, query):
            """Server-Sent-Events live tail of the event pipeline. Each
            frame's ``id:`` is the event's resourceVersion, so EventSource
            reconnects resume exactly where they dropped (Last-Event-ID);
            an ``event: reset`` frame tells the client its resume point
            fell out of the ring (the 410 analog mid-stream). Heartbeat
            comments every poll window keep proxies from reaping the
            connection and surface dead clients to the server."""
            rv = self._int_param(query, "resourceVersion", 0)
            last_id = self.headers.get("Last-Event-ID")
            if last_id:
                try:
                    rv = int(last_id)
                except ValueError:
                    pass
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header("Connection", "close")
            self.close_connection = True
            self.end_headers()
            rec = srv.runtime.events
            try:
                while not srv._stopping.is_set():
                    if srv.runtime.events is not rec:
                        # HA promotion swapped the runtime (and with it
                        # the recorder): restart from its head
                        rec = srv.runtime.events
                        rv = 0
                    items, latest, too_old = rec.wait(
                        rv, 15.0, should_stop=srv._stopping.is_set
                    )
                    if too_old:
                        self.wfile.write(b"event: reset\ndata: {}\n\n")
                    for item in items:
                        frame = (
                            f"id: {item['resourceVersion']}\n"
                            f"data: {json.dumps(item)}\n\n"
                        )
                        self.wfile.write(frame.encode())
                    if not items:
                        self.wfile.write(b": keep-alive\n\n")
                    self.wfile.flush()
                    rv = max(rv, latest)
            except (BrokenPipeError, ConnectionResetError, OSError):
                pass  # client went away — the stream's normal ending

        def _h_get_ns(self, section, ns, name, query):
            self._send_json(srv.get_object(section, ns, name))

        def _h_lq_status(self, ns, name, query):
            with srv.lock:
                status = srv.runtime.local_queue_status(ns, name)
            if status is None:
                raise ApiError(404, f"localqueue {ns}/{name} not found")
            self._send_json(status)

        def _h_get(self, section, name, query):
            self._send_json(srv.get_object(section, "", name))

        def _propagate_traceparent(self, section, obj) -> None:
            """W3C trace-context over the HTTP plane: a ``traceparent``
            request header on a workload upsert lands as the
            traceparent label, so the receiving runtime JOINS the
            caller's trace instead of minting a fresh id (the
            kubeconfig-free analog of header propagation — labels
            survive serialization, journaling and replication)."""
            if section != "workloads" or not isinstance(obj, dict):
                return
            from kueue_tpu.tracing import TRACEPARENT_LABEL, parse_traceparent

            header = self.headers.get("traceparent")
            if parse_traceparent(header) is None:
                return
            labels = obj.setdefault("labels", {})
            labels.setdefault(TRACEPARENT_LABEL, header)

        def _throttled(self, e) -> ApiError:
            return ApiError(
                429, f"write shed ({e.reason}): {e}",
                retry_after_s=e.retry_after_s,
            )

        def _h_apply(self, section, query):
            body = self._body()
            self._propagate_traceparent(section, body)
            if srv.gateway is not None:
                # coalescing write path: enqueue (shed with 429 +
                # Retry-After when over budget) and wait for the flush
                # window that applies it
                from kueue_tpu.gateway import GatewayThrottled

                srv.require_leader()
                try:
                    obj = srv.gateway.submit(section, body)
                except GatewayThrottled as e:
                    raise self._throttled(e)
                except TimeoutError as e:
                    raise ApiError(503, str(e))
            else:
                obj = srv.apply(section, body)
            self._send_json({"applied": obj})

        def _h_apply_batch(self, query):
            with phase(
                _INGEST_DECODE, getattr(srv.runtime, "phases", None)
            ):
                body = self._body()
            for obj in body.get("workloads", []) or []:
                self._propagate_traceparent("workloads", obj)
            if srv.gateway is not None:
                from kueue_tpu.gateway import GatewayThrottled

                srv.require_leader()
                srv.validate_batch_body(body)
                try:
                    out = srv.gateway.submit_batch(body)
                except GatewayThrottled as e:
                    raise self._throttled(e)
                except TimeoutError as e:
                    raise ApiError(503, str(e))
            else:
                out = srv.apply_batch(body)
            self._send_json(out)

        def _h_delete_ns(self, section, ns, name, query):
            srv.delete(section, ns, name)
            self._send_json({"deleted": f"{ns}/{name}"})

        def _h_delete(self, section, name, query):
            srv.delete(section, "", name)
            self._send_json({"deleted": name})

        def _h_check_state(self, ns, name, query):
            body = self._body()
            srv.set_admission_check_state(
                ns,
                name,
                check=body.get("name", ""),
                state=body.get("state", ""),
                message=body.get("message", ""),
            )
            self._send_json({"updated": f"{ns}/{name}"})

        def _h_federation_clusters(self, query):
            """Worker-cluster roster + connectivity/guard state — the
            `kueuectl clusters list` payload. 404 when this control
            plane is not running a federation dispatcher."""
            fed = getattr(srv.runtime, "federation", None)
            if fed is None:
                raise ApiError(404, "federation is not enabled")
            with srv.lock:
                items = fed.cluster_report()
            self._send_json({"items": items})

        def _h_federation_status(self, query):
            """Full federation status: cluster roster, per-workload
            dispatch state (winner, fence), pending retractions."""
            fed = getattr(srv.runtime, "federation", None)
            if fed is None:
                raise ApiError(404, "federation is not enabled")
            with srv.lock:
                status = fed.status()
            self._send_json(status)

        def _h_federation_add_worker(self, query):
            """Runtime scale-up join: add a worker cluster to the
            dispatch roster without a restart. Body: {"name", "url",
            "token"?}. The worker is dispatchable on the next pass."""
            fed = getattr(srv.runtime, "federation", None)
            if fed is None:
                raise ApiError(404, "federation is not enabled")
            srv.require_leader()
            body = self._body()
            name = body.get("name") or ""
            url = body.get("url") or ""
            if not name or not url:
                raise ApiError(400, "body must carry name and url")
            from kueue_tpu.admissionchecks.multikueue import (
                MultiKueueCluster,
            )
            from kueue_tpu.admissionchecks.multikueue_transport import (
                HTTPTransport,
            )

            with srv.lock:
                fed.add_worker(
                    MultiKueueCluster(
                        name=name,
                        transport=HTTPTransport(
                            url, token=body.get("token") or None
                        ),
                    )
                )
            self._send_json({"joined": name})

        def _h_federation_membership(self, name, action, query):
            """cordon: stop new dispatches; uncordon: readmit; drain:
            cordon + move every placement off the worker under the
            fencing protocol (deposed winners re-dispatch elsewhere)."""
            fed = getattr(srv.runtime, "federation", None)
            if fed is None:
                raise ApiError(404, "federation is not enabled")
            srv.require_leader()
            with srv.lock:
                if action == "drain":
                    if name not in fed.clusters:
                        raise ApiError(404, f"unknown worker cluster {name!r}")
                    deposed = fed.drain_worker(name)
                    out = {"drained": name, "deposed": deposed}
                else:
                    ok = (
                        fed.cordon(name)
                        if action == "cordon"
                        else fed.uncordon(name)
                    )
                    if not ok:
                        raise ApiError(404, f"unknown worker cluster {name!r}")
                    out = {action + "ed": name}
            self._send_json(out)

        def _h_federation_remove_worker(self, name, query):
            """Scale-down leave: drain, flush retractions, drop the
            worker from the roster."""
            fed = getattr(srv.runtime, "federation", None)
            if fed is None:
                raise ApiError(404, "federation is not enabled")
            srv.require_leader()
            with srv.lock:
                if not fed.remove_worker(name):
                    raise ApiError(404, f"unknown worker cluster {name!r}")
            self._send_json({"removed": name})

        def _h_capacity(self, query):
            """Elastic capacity plane status: provider grants, applied
            (journaled) requests, in-flight asks, last chooser verdict.
            404 when --elastic is off."""
            plane = getattr(srv.runtime, "elastic", None)
            if plane is None:
                raise ApiError(404, "elastic capacity plane is not enabled")
            with srv.lock:
                body = plane.status()
            self._send_json(body)

        def _h_global_standings(self, query):
            """Federation-wide visibility: the global scheduler's
            read-only rescore — per-worker standings + every pending
            workload's per-cluster forecast and best placement. 404
            when this plane runs no global scheduler."""
            fed = getattr(srv.runtime, "federation", None)
            gs = (
                getattr(fed, "global_scheduler", None)
                if fed is not None
                else None
            )
            if gs is None:
                raise ApiError(404, "global scheduler is not enabled")
            with srv.lock:
                body = gs.standings()
            self._send_json(body)

        def _h_reconcile(self, query):
            srv.require_leader()
            with srv.lock:
                cycles = srv.runtime.run_until_idle()
            self._send_json({"cycles": cycles})

        def _h_debug_cycles(self, query):
            # per-cycle phase attribution (the pprof-ish surface), plus
            # the runtime's cumulative loop and batch-apply phases
            with srv.lock:
                traces = [
                    t.to_dict() for t in srv.runtime.scheduler.last_traces
                ]
                totals = srv.runtime.phase_totals()
            self._send_json({"cycles": traces, "runtimePhases": totals})

        def _h_debug_traces(self, query):
            """Bounded in-memory trace store: newest traces first
            (id, root span, span count, duration)."""
            tracer = getattr(srv.runtime, "tracer", None)
            limit = self._int_param(query, "limit", 64)
            with srv.lock:
                items = (
                    tracer.traces_summary(limit) if tracer is not None else []
                )
            self._send_json({"items": items})

        def _h_debug_trace_get(self, trace_id, query):
            """One full span tree."""
            tracer = getattr(srv.runtime, "tracer", None)
            with srv.lock:
                spans = (
                    [s.to_dict() for s in tracer.trace(trace_id)]
                    if tracer is not None
                    else []
                )
            if not spans:
                raise ApiError(404, f"trace {trace_id} not found")
            self._send_json({"traceId": trace_id, "spans": spans})

        def _h_workload_trace(self, ns, name, query):
            """The workload's lifecycle trace plus every cycle trace
            its decisions reference — the `kueuectl trace` payload
            (Chrome-trace exportable)."""
            from kueue_tpu.tracing import workload_trace_payload

            key = f"{ns}/{name}"
            with srv.lock:
                payload = workload_trace_payload(srv.runtime, key)
                known = key in srv.runtime.workloads
            if not payload["spans"] and not known:
                raise ApiError(404, f"workload {key} not found")
            self._send_json(payload)

        def _h_quarantine_list(self, query):
            """Poison-workload quarantine triage (kueuectl quarantine
            list): sidelined workloads + the solver guard's state."""
            with srv.lock:
                items = srv.runtime.quarantine_report()
                guard = getattr(srv.runtime.scheduler, "guard", None)
                solver = guard.health() if guard is not None else {}
            self._send_json({"items": items, "solver": solver})

        def _h_quarantine_clear(self, query):
            """Release one (body: {"workload": "ns/name"}) or every
            (empty body) quarantined workload back to nomination —
            ``kueuectl quarantine clear`` / the manual requeue."""
            srv.require_leader()
            body = self._body()
            with srv.lock:
                cleared = srv.runtime.clear_quarantine(
                    body.get("workload") or None
                )
                if srv.auto_reconcile and cleared:
                    srv.runtime.run_until_idle()
            self._send_json({"cleared": cleared})

        def _h_plan(self, query):
            """What-if capacity planner. Leader-only in elector HA (a
            checkpoint-refresh standby's state can lag by the whole
            checkpoint period, so plans there would be confidently
            wrong) — but a journal-tailing READ REPLICA serves it:
            its state trails by one poll interval, the response carries
            the X-Kueue-Replica-Lag header, and the semantics are
            documented best-effort-stale (deploy/README "Read
            replicas"). Strictly read-only over the runtime
            (guardrail-tested: state dump and event resourceVersion are
            byte-identical across a plan call)."""
            if srv.replica is None:
                srv.require_leader()
            from kueue_tpu.planner import plan_request
            from kueue_tpu.planner.scenarios import ScenarioApplyError

            body = self._body()
            with srv.lock:
                try:
                    report = plan_request(srv.runtime, body)
                except (ScenarioApplyError, KeyError, ValueError) as e:
                    raise ApiError(400, f"invalid plan request: {e}")
            self._send_json(report)

        def _h_workload_decisions(self, ns, name, query):
            """Per-workload decision audit trail (oldest first). 404
            only when the workload is unknown AND left no trail — a
            just-deleted workload's history stays readable until the
            audit ring forgets it."""
            key = f"{ns}/{name}"
            with srv.lock:
                audit = getattr(srv.runtime, "audit", None)
                items = visibility.workload_decisions(audit, key)
                known = key in srv.runtime.workloads
            if not items and not known:
                raise ApiError(404, f"workload {key} not found")
            self._send_json({"workload": key, "items": items})

        def _h_state(self, query):
            with srv.lock:  # snapshot under lock; write to client outside
                state = ser.runtime_to_state(srv.runtime)
                if srv.replica is not None:
                    # the replica has no journal attached, so stamp its
                    # APPLIED position instead of journalSeq=0 — at
                    # quiescence this makes the replica's dump
                    # byte-identical to the leader's (the convergence
                    # acceptance check). The fence rides along so a
                    # downstream tailer anchoring on THIS node's state
                    # (fan-out trees) adopts the leader's token.
                    state["persistence"]["journalSeq"] = (
                        srv.replica.tailer.applied_seq
                    )
                    state["persistence"]["token"] = (
                        srv.replica.tailer.max_token
                    )
            self._send_json(state)

        def _h_journal_tail(self, query):
            """The replication feed read replicas poll: journal records
            past ``sinceSeq``, bundled with the event-recorder and
            audit-log deltas so one round trip per poll interval keeps
            every replica read surface current. Registers the polling
            replica in the roster. On the LEADER the segment scan runs
            OUTSIDE srv.lock — segments are append-only, the CRC
            framing makes a concurrently half-written tail frame
            invisible, and holding the serving lock for an O(delta)
            file scan would put reads back on the admission hot path.
            On a REPLICA the same contract is served from the tailer's
            bounded in-memory feed log — replicas tail replicas
            (``--replica-of`` pointed at another replica), so watch/SSE
            load fans out in a tree instead of all replicas hammering
            the leader; the response's ``hop``/``pathLag`` fields let
            downstream nodes report their distance and per-hop
            staleness."""
            since = self._int_param(query, "sinceSeq", 0)
            limit = max(1, min(self._int_param(query, "limit", 2048), 65536))
            if srv.replica is not None:
                tailer = srv.replica.tailer
                with srv.lock:
                    applied = tailer.applied_seq
                    feed = [
                        rec for rec in tailer.feed_log if rec.seq > since
                    ]
                    first_available = (
                        tailer.feed_log[0].seq
                        if tailer.feed_log
                        else applied + 1
                    )
                    token = tailer.max_token
                body = {
                    "lastSeq": applied,
                    "firstAvailableSeq": first_available,
                    "token": token,
                    "leaderTime": srv.clock.now(),
                    "hop": tailer.hop,
                    "pathLag": tailer.path_lag(),
                }
                if since + 1 < first_available and applied > since:
                    # trimmed feed log or post-resync anchor: the
                    # downstream must re-anchor on OUR checkpoint
                    # (GET /state stamps appliedSeq + fence) — the
                    # leader-compaction contract, one hop down
                    body["compacted"] = True
                    body["records"] = []
                else:
                    body["compacted"] = False
                    body["records"] = [r.to_dict() for r in feed[:limit]]
            else:
                journal = getattr(srv.runtime, "journal", None)
                if journal is None:
                    raise ApiError(
                        404,
                        "no journal attached; replicas tail a leader "
                        "started with --journal (or another replica)",
                    )
                first_available = journal.first_available_seq()
                body = {
                    "lastSeq": journal.last_seq,
                    "firstAvailableSeq": first_available,
                    "token": (
                        journal.token_provider()
                        if journal.token_provider is not None
                        else None
                    ),
                    "leaderTime": srv.clock.now(),
                    "hop": 0,
                    "pathLag": [],
                }
                if since + 1 < first_available and journal.last_seq > since:
                    # the requested prefix was compacted away: the
                    # replica must re-anchor on a checkpoint (GET
                    # /state) — sending records with a hole would
                    # corrupt its replay
                    body["compacted"] = True
                    body["records"] = []
                else:
                    body["compacted"] = False
                    # offset-cursor tail: a caught-up replica's repeat
                    # poll reads O(delta) bytes, not the whole segment
                    body["records"] = [
                        rec.to_dict()
                        for rec in journal.tail_records(since, limit=limit)
                    ]
            # event + audit deltas (rv/seq-addressed, recorder-locked)
            ev_rv = self._int_param(query, "sinceEventRv", 0)
            rec_events = srv.runtime.events
            items, too_old = rec_events.since(ev_rv)
            body["events"] = items
            body["eventsRv"] = rec_events.resource_version
            body["eventsTooOld"] = too_old
            audit = getattr(srv.runtime, "audit", None)
            audit_seq = self._int_param(query, "sinceAuditSeq", 0)
            body["audit"] = audit.since(audit_seq) if audit is not None else []
            body["auditSeq"] = audit.seq if audit is not None else 0
            # span delta (kueue_tpu/tracing): replicas render the
            # LEADER's waterfalls, so the feed ships every span stamped
            # since the replica's cursor alongside events/audit
            tracer = getattr(srv.runtime, "tracer", None)
            span_seq = self._int_param(query, "sinceSpanSeq", 0)
            body["spans"] = (
                tracer.since(span_seq) if tracer is not None else []
            )
            body["spansSeq"] = tracer.seq if tracer is not None else 0
            replica_id = query.get("replica")
            if replica_id:
                try:
                    applied = int(query.get("appliedSeq", since))
                    lag = float(query.get("lagSeconds", 0.0))
                    hop = int(query.get("hop", body["hop"] + 1))
                except ValueError:
                    applied, lag = since, 0.0
                    hop = body["hop"] + 1
                srv.replica_roster[replica_id] = {
                    "id": replica_id,
                    "appliedSeq": applied,
                    "lagSeconds": lag,
                    "hop": hop,
                    "lastSeen": body["leaderTime"],
                }
            self._send_json(body)

        def _roster_items(self, head_seq: int) -> list:
            now = srv.clock.now()
            items = []
            for entry in sorted(
                srv.replica_roster.values(), key=lambda e: e["id"]
            ):
                item = dict(entry)
                item["lastSeenAgoS"] = round(now - entry["lastSeen"], 3)
                item["behind"] = max(0, head_seq - entry["appliedSeq"])
                items.append(item)
            return items

        def _h_replicas(self, query):
            """Follower roster (leader) / own status + downstream
            children (replica) — the ``kueuectl replicas`` payload.
            In a fan-out tree every node serves this: the leader lists
            its hop-1 followers, each mid-tier replica lists its own
            tail status plus the hop-(n+1) nodes tailing IT."""
            if srv.replica is not None:
                out = {
                    "role": "replica",
                    "items": [srv.replica.status()],
                }
                if srv.replica_roster:
                    out["children"] = self._roster_items(
                        srv.replica.tailer.applied_seq
                    )
                self._send_json(out)
                return
            journal = getattr(srv.runtime, "journal", None)
            head = journal.last_seq if journal is not None else 0
            self._send_json(
                {
                    "role": "leader",
                    "lastSeq": head,
                    "items": self._roster_items(head),
                }
            )

        def _h_solve(self, query):
            # stateless: deliberately NOT under srv.lock — solving a
            # posted snapshot doesn't touch the live runtime
            self._send_json(solve_assign(self._body()))

        def _h_dashboard_json(self, query):
            from kueue_tpu.server.dashboard import dashboard_payload

            with srv.lock:
                payload = dashboard_payload(srv.runtime)
            self._send_json(payload)

        def _h_dashboard_html(self, query):
            from kueue_tpu.server.dashboard import DASHBOARD_HTML

            self._send_text(DASHBOARD_HTML, "text/html")

    return Handler


def _summary_to_dict(summary: visibility.PendingWorkloadsSummary) -> dict:
    return {
        "items": [
            {
                "name": pw.name,
                "namespace": pw.namespace,
                "localQueueName": pw.local_queue_name,
                "priority": pw.priority,
                "positionInClusterQueue": pw.position_in_cluster_queue,
                "positionInLocalQueue": pw.position_in_local_queue,
                "inadmissibleReason": pw.inadmissible_reason,
                "message": pw.message,
                "lastCycle": pw.last_cycle,
            }
            for pw in summary.items
        ]
    }
