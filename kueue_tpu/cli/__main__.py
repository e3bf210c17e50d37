"""python -m kueue_tpu.cli — the kueuectl equivalent.

Reference: cmd/kueuectl/app (create {cq,lq,rf}, list {cq,lq,workload,
rf}, stop/resume {workload,cq,lq}) plus cmd/importer (bulk pod import).
State lives in a JSON file (--state, default ./kueue-state.json) — the
CLI's durable store standing in for the API server; ``schedule`` loads
the state, runs admission cycles, and writes decisions back.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

from kueue_tpu import serialization as ser
from kueue_tpu.models.constants import StopPolicy, WorkloadConditionType
from kueue_tpu.models.workload import PodSet
from kueue_tpu.models import Workload
from kueue_tpu.resources import requests_from_spec


class State:
    def __init__(self, path: str):
        self.path = path
        self.is_chain_dir = os.path.isdir(path)
        if self.is_chain_dir:
            # a delta-checkpoint chain directory (--state-dir leaders):
            # readable as the merged anchor+deltas state
            from kueue_tpu.storage.checkpoint import load_state_any

            self.data = load_state_any(path) or ser.state_to_dict(
                [], [], [], []
            )
        elif os.path.exists(path):
            with open(path) as f:
                self.data = json.load(f)
        else:
            self.data = ser.state_to_dict([], [], [], [])

    def save(self) -> None:
        if self.is_chain_dir:
            # offline edits behind a delta chain would be silently
            # overwritten by the next checkpoint — refuse
            raise SystemExit(
                "error: state path is a delta-checkpoint chain "
                "directory (read-only from the CLI); apply changes "
                "through the running server"
            )
        with open(self.path, "w") as f:
            json.dump(self.data, f, indent=1, sort_keys=True)

    def find(self, section: str, name: str, namespace: str = "") -> dict:
        for obj in self.data.get(section, []):
            if obj["name"] == name and obj.get("namespace", "") == (namespace or obj.get("namespace", "")):
                return obj
        raise SystemExit(f"error: {section[:-1]} {name!r} not found")

    def upsert(self, section: str, obj: dict) -> None:
        items = self.data.setdefault(section, [])
        for i, existing in enumerate(items):
            if existing["name"] == obj["name"] and existing.get("namespace") == obj.get("namespace"):
                items[i] = obj
                return
        items.append(obj)

    def build_runtime(self):
        return ser.runtime_from_state(self.data)


def _parse_quotas(spec: str) -> Dict[str, str]:
    """cpu=10,memory=5Gi -> {"cpu": "10", "memory": "5Gi"}"""
    out: Dict[str, str] = {}
    for part in spec.split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        if not v:
            raise SystemExit(f"error: invalid quota {part!r} (want resource=quantity)")
        out[k] = v
    return out


def _parse_labels(spec: str) -> Dict[str, str]:
    return _parse_quotas(spec)


# ---- create ----
def _server_client(args):
    """KueueClient from the shared --server connection flags."""
    from kueue_tpu.server import KueueClient

    return KueueClient(
        args.server,
        token=args.token,
        ca_cert=getattr(args, "ca_cert", None),
        insecure=getattr(args, "insecure", False),
    )


def _replica_note(client) -> None:
    """After a read against --server: tell the operator when the
    answer came from a read replica (and how stale it may be) — on
    stderr so piped/table output stays parseable."""
    if getattr(client, "served_by_replica", False):
        lag = client.last_replica_lag_s
        detail = f", lag {lag:.2f}s behind leader" if lag is not None else ""
        print(f"(replica{detail})", file=sys.stderr)


def _add_server_flags(parser, server_help):
    """--server plus its credential/trust companions (the kubeconfig
    server/token/certificate-authority triple for the CLI)."""
    parser.add_argument("--server", help=server_help)
    parser.add_argument(
        "--token", default=os.environ.get("KUEUE_AUTH_TOKEN") or None,
        help="bearer token for a secured server (default: $KUEUE_AUTH_TOKEN)",
    )
    parser.add_argument(
        "--ca-cert",
        default=os.environ.get("KUEUE_CA_CERT") or None,
        help="CA bundle verifying an https:// server (the ca.crt from "
        "the server's --tls-cert-dir; default: $KUEUE_CA_CERT)",
    )
    parser.add_argument(
        "--insecure", action="store_true",
        help="skip TLS verification (dev only)",
    )


def cmd_create_cq(state: State, args) -> None:
    quotas = _parse_quotas(args.nominal_quota)
    borrowing = _parse_quotas(args.borrowing_limit) if args.borrowing_limit else {}
    lending = _parse_quotas(args.lending_limit) if args.lending_limit else {}
    for label, limits in (("borrowing-limit", borrowing), ("lending-limit", lending)):
        unknown = set(limits) - set(quotas)
        if unknown:
            raise SystemExit(
                f"error: --{label} for resources without nominal quota: {sorted(unknown)}"
            )
    resources = [
        {
            "name": r,
            "nominalQuota": _canon(r, q),
            "borrowingLimit": _canon(r, borrowing[r]) if r in borrowing else None,
            "lendingLimit": _canon(r, lending[r]) if r in lending else None,
        }
        for r, q in quotas.items()
    ]
    obj = {
        "name": args.name,
        "cohort": args.cohort,
        "queueingStrategy": args.queuing_strategy,
        "namespaceSelector": {},
        "stopPolicy": "None",
        "admissionChecks": [],
        "preemption": {
            "reclaimWithinCohort": args.reclaim_within_cohort,
            "withinClusterQueue": args.preemption_within_cluster_queue,
            "borrowWithinCohort": {"policy": "Never", "maxPriorityThreshold": None},
        },
        "resourceGroups": [
            {
                "coveredResources": list(quotas),
                "flavors": [{"name": args.flavor, "resources": resources}],
            }
        ],
    }
    ser.cq_from_dict(obj)  # validate
    state.upsert("clusterQueues", obj)
    state.save()
    print(f"clusterqueue.kueue.x-k8s.io/{args.name} created")


def _canon(resource: str, qty: str) -> int:
    from kueue_tpu.resources import quantity_to_int

    return quantity_to_int(resource, qty)


def cmd_create_lq(state: State, args) -> None:
    obj = {
        "name": args.name,
        "namespace": args.namespace,
        "clusterQueue": args.clusterqueue,
        "stopPolicy": "None",
    }
    ser.lq_from_dict(obj)
    state.upsert("localQueues", obj)
    state.save()
    print(f"localqueue.kueue.x-k8s.io/{args.name} created")


def cmd_create_rf(state: State, args) -> None:
    obj = {
        "name": args.name,
        "nodeLabels": _parse_labels(args.node_labels) if args.node_labels else {},
        "nodeTaints": [],
        "tolerations": [],
        "topologyName": args.topology,
    }
    ser.flavor_from_dict(obj)
    state.upsert("resourceFlavors", obj)
    state.save()
    print(f"resourceflavor.kueue.x-k8s.io/{args.name} created")


def cmd_create_topology(state: State, args) -> None:
    obj = {
        "name": args.name,
        "levels": [lv for lv in args.levels.split(",") if lv],
    }
    ser.topology_from_dict(obj)  # validate
    state.upsert("topologies", obj)
    state.save()
    print(f"topology.kueue.x-k8s.io/{args.name} created")


def cmd_create_node(state: State, args) -> None:
    obj = {
        "name": args.name,
        "labels": _parse_labels(args.labels),
        "allocatable": _parse_quotas(args.allocatable),
        "taints": [],
        "ready": not args.not_ready,
        "nonTasUsage": {},
    }
    ser.node_from_dict(obj)  # validate (canonicalizes quantities)
    state.upsert("nodes", obj)
    state.save()
    print(f"node/{args.name} created")


def cmd_create_workload(state: State, args) -> None:
    import time

    tr = None
    required = args.topology_required
    preferred = args.topology_preferred
    if required or preferred:
        from kueue_tpu.models.workload import PodSetTopologyRequest

        tr = PodSetTopologyRequest(
            mode="Required" if required else "Preferred",
            level=required or preferred,
        )
    wl = Workload(
        namespace=args.namespace,
        name=args.name,
        queue_name=args.localqueue,
        priority=args.priority,
        creation_time=time.time(),
        pod_sets=(
            PodSet(
                name="main",
                count=args.count,
                requests=requests_from_spec(_parse_quotas(args.requests)),
                topology_request=tr,
            ),
        ),
    )
    state.upsert("workloads", ser.workload_to_dict(wl))
    state.save()
    print(f"workload.kueue.x-k8s.io/{args.name} created")


# ---- list ----
def _print_table(headers: List[str], rows: List[List[str]]) -> None:
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    print("  ".join(h.ljust(w) for h, w in zip(headers, widths)).rstrip())
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())


def cmd_list_cq(state: State, args) -> None:
    rt = state.build_runtime()
    rows = []
    for c in state.data.get("clusterQueues", []):
        name = c["name"]
        pending = rt.queues.pending_workloads(name)
        admitted = rt.cache.admitted_count(name)
        rows.append([name, c.get("cohort") or "", str(pending), str(admitted)])
    _print_table(["NAME", "COHORT", "PENDING WORKLOADS", "ADMITTED WORKLOADS"], rows)


def cmd_list_lq(state: State, args) -> None:
    rows = [
        [l["namespace"], l["name"], l["clusterQueue"]]
        for l in state.data.get("localQueues", [])
        if not args.namespace or l["namespace"] == args.namespace
    ]
    _print_table(["NAMESPACE", "NAME", "CLUSTERQUEUE"], rows)


def cmd_list_rf(state: State, args) -> None:
    rows = [
        [f["name"], ",".join(f"{k}={v}" for k, v in f.get("nodeLabels", {}).items())]
        for f in state.data.get("resourceFlavors", [])
    ]
    _print_table(["NAME", "NODE LABELS"], rows)


def cmd_list_workload(state: State, args) -> None:
    rows = []
    for w in state.data.get("workloads", []):
        if args.namespace and w["namespace"] != args.namespace:
            continue
        wl = ser.workload_from_dict(w)
        status = "PENDING"
        if wl.is_finished:
            status = "FINISHED"
        elif wl.is_admitted:
            status = "ADMITTED"
        elif wl.has_quota_reservation:
            status = "QUOTARESERVED"
        elif not wl.active:
            status = "INACTIVE"
        rows.append([
            w["namespace"], w["name"], w.get("queueName", ""),
            wl.admission.cluster_queue if wl.admission else "", status,
        ])
    _print_table(
        ["NAMESPACE", "NAME", "LOCALQUEUE", "CLUSTERQUEUE", "STATUS"], rows
    )


def cmd_list_topology(state: State, args) -> None:
    rows = [
        [t["name"], ",".join(t.get("levels", []))]
        for t in state.data.get("topologies", [])
    ]
    _print_table(["NAME", "LEVELS"], rows)


def cmd_list_node(state: State, args) -> None:
    from kueue_tpu.resources import int_to_display

    rows = []
    for n in state.data.get("nodes", []):
        alloc = ",".join(
            # ints are canonical (server-exported state: cpu in milli);
            # strings are human-authored and render verbatim
            f"{r}={int_to_display(r, q) if isinstance(q, int) else q}"
            for r, q in n.get("allocatable", {}).items()
        )
        labels = ",".join(f"{k}={v}" for k, v in n.get("labels", {}).items())
        ready = "True" if n.get("ready", True) else "False"
        rows.append([n["name"], ready, alloc, labels])
    _print_table(["NAME", "READY", "ALLOCATABLE", "LABELS"], rows)


# ---- stop / resume ----
def cmd_stop(state: State, args) -> None:
    if args.kind == "workload":
        obj = state.find("workloads", args.name, args.namespace)
        obj["active"] = False
    elif args.kind == "clusterqueue":
        obj = state.find("clusterQueues", args.name)
        obj["stopPolicy"] = StopPolicy.HOLD_AND_DRAIN.value
    else:
        obj = state.find("localQueues", args.name, args.namespace)
        obj["stopPolicy"] = StopPolicy.HOLD_AND_DRAIN.value
    state.save()
    print(f"{args.kind}.kueue.x-k8s.io/{args.name} stopped")


def cmd_resume(state: State, args) -> None:
    if args.kind == "workload":
        obj = state.find("workloads", args.name, args.namespace)
        obj["active"] = True
    elif args.kind == "clusterqueue":
        obj = state.find("clusterQueues", args.name)
        obj["stopPolicy"] = StopPolicy.NONE.value
    else:
        obj = state.find("localQueues", args.name, args.namespace)
        obj["stopPolicy"] = StopPolicy.NONE.value
    state.save()
    print(f"{args.kind}.kueue.x-k8s.io/{args.name} resumed")


# ---- delete (cmd/kueuectl/app/delete) ----
_DELETE_SECTIONS = {
    "workload": ("workloads", "workloads"),
    "clusterqueue": ("clusterQueues", "clusterqueues"),
    "localqueue": ("localQueues", None),  # no server delete route
    "resourceflavor": ("resourceFlavors", "resourceflavors"),
    "node": ("nodes", "nodes"),  # TAS capacity inventory
}


def cmd_delete(state: State, args) -> None:
    section, server_section = _DELETE_SECTIONS[args.kind]
    # clusterqueue/resourceflavor are cluster-scoped: the namespace
    # default must not make State.find miss them
    namespaced = args.kind in ("workload", "localqueue")
    ns = getattr(args, "namespace", "") if namespaced else ""
    if getattr(args, "server", None):
        client = _server_client(args)
        if args.kind == "workload":
            client.delete_workload(ns, args.name)
        elif server_section is not None:
            client.delete(server_section, args.name)
        else:
            raise SystemExit(
                f"error: server delete not supported for {args.kind}"
            )
    else:
        obj = state.find(section, args.name, ns)
        state.data[section].remove(obj)
        state.save()
    if args.kind == "node":
        print(f"node/{args.name} deleted")  # Node is core/v1, no group
    else:
        print(f"{args.kind}.kueue.x-k8s.io/{args.name} deleted")


# ---- passthrough get (cmd/kueuectl/app/passthrough) ----
def cmd_get(state: State, args) -> None:
    section, server_section = _DELETE_SECTIONS[args.kind]
    # clusterqueue/resourceflavor are cluster-scoped: the namespace
    # default must not make State.find miss them
    namespaced = args.kind in ("workload", "localqueue")
    ns = getattr(args, "namespace", "") if namespaced else ""
    if getattr(args, "server", None):
        client = _server_client(args)
        if args.kind == "workload":
            obj = client.get_workload(ns, args.name)
        else:
            obj = client.get(server_section or section, args.name)
    else:
        obj = state.find(section, args.name, ns)
    json.dump(obj, sys.stdout, indent=1, sort_keys=True)
    print()


def cmd_version(state: State, args) -> None:
    from kueue_tpu import __version__

    print(f"kueuectl (kueue-tpu) {__version__}")


# ---- pending-workloads (visibility) ----
def _fmt_tta(v) -> str:
    return "-" if v is None else f"{float(v):.1f}s"


def cmd_pending_workloads(state: State, args) -> None:
    if getattr(args, "global_view", False):
        # federation-wide view: the global scheduler's read-only
        # rescore — every pending workload's current placement, the
        # forecast-best cluster, and whether the rebalancer would move
        # it (gain past hysteresis)
        if not getattr(args, "server", None):
            raise SystemExit(
                "error: `pending-workloads --global` reads a live "
                "federation manager; pass --server http://<manager>"
            )
        from kueue_tpu.server.client import ClientError

        client = _server_client(args)
        try:
            body = client.global_standings()
        except ClientError as e:
            if e.status == 404:
                raise SystemExit(
                    "error: the global scheduler is not enabled on "
                    "this server (start it with --federation-worker "
                    "NAME=URL --global-scheduler on)"
                )
            raise
        _replica_note(client)
        rows = []
        for row in body.get("workloads", []):
            tta = row.get("ttaByClusterS") or {}
            cur = row.get("current")
            best = row.get("best")
            rows.append(
                [
                    row["workload"],
                    cur or "-",
                    _fmt_tta(tta.get(cur)) if cur else "-",
                    best or "-",
                    _fmt_tta(tta.get(best)) if best else "-",
                    f"{float(row.get('gainS', 0.0)):.1f}s",
                    "yes" if row.get("rebalance") else "",
                ]
            )
        _print_table(
            ["WORKLOAD", "CURRENT", "TTA(CUR)", "BEST", "TTA(BEST)",
             "GAIN", "REBALANCE"],
            rows,
        )
        workers = body.get("workers", {})
        if workers:
            print()
            _print_table(
                ["CLUSTER", "READABLE", "SOURCE", "PENDING", "ADMITTED"],
                [
                    [
                        name,
                        "yes" if v.get("reachable") else "no",
                        v.get("source", ""),
                        str(v.get("pending", 0)),
                        str(v.get("admitted", 0)),
                    ]
                    for name, v in sorted(workers.items())
                ],
            )
        return
    if not args.clusterqueue:
        raise SystemExit(
            "error: pending-workloads needs a CLUSTERQUEUE (or "
            "--global against a federation manager)"
        )
    if getattr(args, "server", None):
        # live query against a running kueue_tpu.server (the reference's
        # kubectl plugin hitting the visibility apiserver)
        client = _server_client(args)
        summary = client.pending_workloads_cq(args.clusterqueue)
        _replica_note(client)
        rows = [
            [str(i["positionInClusterQueue"]), i["namespace"], i["name"],
             i["localQueueName"], str(i["priority"]),
             i.get("inadmissibleReason", "")]
            for i in summary["items"]
        ]
    else:
        from kueue_tpu.visibility import pending_workloads_in_cq

        rt = state.build_runtime()
        summary = pending_workloads_in_cq(
            rt.queues, args.clusterqueue, audit=rt.audit
        )
        rows = [
            [str(pw.position_in_cluster_queue), pw.namespace, pw.name,
             pw.local_queue_name, str(pw.priority), pw.inadmissible_reason]
            for pw in summary.items
        ]
    _print_table(
        ["POSITION", "NAMESPACE", "NAME", "LOCALQUEUE", "PRIORITY", "REASON"],
        rows,
    )


# ---- explain (the decision audit trail as a timeline) ----
def _render_decision_timeline(key: str, status: str, rows: List[dict]) -> None:
    """Render one workload's decision history (wire dicts, oldest
    first) the way `kubectl describe` renders conditions: one line per
    decision plus indented detail for flavors/rejections/victims."""
    print(f"Workload:      {key}")
    print(f"Status:        {status}")
    if not rows:
        print("Decisions:     <none recorded>")
        print(
            "  (the workload was never nominated — check that its "
            "LocalQueue exists and the ClusterQueue is active)"
        )
        return
    print("Decisions:")
    for d in rows:
        cycles = (
            f"cycle {d['cycle']}"
            if d.get("lastCycle", d["cycle"]) == d["cycle"]
            else f"cycles {d['cycle']}-{d['lastCycle']}"
        )
        seen = f" (seen x{d['count']})" if d.get("count", 1) > 1 else ""
        via = d.get("nominatedVia", "host")
        print(
            f"  {cycles} [{d.get('resolution', 'host')}/{via}] "
            f"{d['outcome']}: {d['reason']}{seen}"
        )
        if d.get("message"):
            print(f"      message:  {d['message']}")
        for ps_name, fmap in sorted(d.get("flavors", {}).items()):
            chosen = ", ".join(f"{r}->{f}" for r, f in sorted(fmap.items()))
            print(f"      podset {ps_name}: {chosen}")
        sc = d.get("scores")
        if sc:
            # admission-policy flavor score breakdown (kueue_tpu/policy)
            per = sc.get("perFlavor", {})
            ranked = sorted(per.items(), key=lambda t: (-t[1], t[0]))
            line = ", ".join(f"{f}={v}" for f, v in ranked)
            print(
                f"      scores [{sc.get('policy', '?')}]: {line} "
                f"(winner {sc.get('winner', '?')}, "
                f"margin {sc.get('margin', 0)})"
            )
        for ps_name, reasons in sorted(d.get("flavorReasons", {}).items()):
            for r in reasons:
                print(f"      rejected [{ps_name}]: {r}")
        pre = d.get("preemption")
        if pre:
            if pre.get("blocked"):
                print(f"      preemption blocked: {pre['blocked']}")
            for v in pre.get("victims", []):
                print(
                    f"      victim: {v['workload']} ({v['reason']})"
                )
        topo = d.get("topology")
        if topo:
            for ps_name, t in sorted(topo.items()):
                doms = "; ".join(
                    f"{'/'.join(dom['values'])} x{dom['count']}"
                    for dom in t.get("domains", [])
                )
                print(f"      topology [{ps_name}]: {doms}")


def _render_trace_summary(rows: List[dict], trace_payload: dict) -> None:
    """The explain footer (kueue_tpu/tracing): the workload's trace id
    plus per-span durations of the cycle that produced its LAST
    decision — where the time between enqueue and that decision went."""
    tid = trace_payload.get("traceId") or next(
        (d["traceId"] for d in reversed(rows) if d.get("traceId")), None
    )
    if not tid:
        return
    print(f"Trace:         {tid}")
    spans = trace_payload.get("spans", [])
    # the last decision span's cycle trace carries the durations
    cycle_tid = None
    for s in spans:
        if s.get("traceId") == tid and (s.get("attrs") or {}).get("cycleTrace"):
            cycle_tid = s["attrs"]["cycleTrace"]
    if cycle_tid is None:
        return
    cycle_spans = [s for s in spans if s.get("traceId") == cycle_tid]
    if not cycle_spans:
        return
    root = next((s for s in cycle_spans if s.get("name") == "cycle"), None)
    label = ""
    if root is not None:
        attrs = root.get("attrs") or {}
        label = (
            f" (cycle {attrs.get('cycle', '?')}, "
            f"{attrs.get('resolution', '?')})"
        )
    print(f"Trace spans{label}:")
    for s in sorted(cycle_spans, key=lambda x: x.get("start", 0.0)):
        indent = "  " if s.get("name") == "cycle" else "    "
        dur = s.get("durationMs")
        dur_str = f"{dur:.3f} ms" if dur is not None else "open"
        print(f"{indent}{s.get('name')}: {dur_str}")


def cmd_explain(state: State, args) -> None:
    """Why is this workload pending (or how was it admitted)? Renders
    the decision audit trail; --server reads a live control plane,
    otherwise the state file is loaded and scheduled in memory (no
    writes) to reproduce the decisions."""
    ns, name = args.namespace, args.name
    key = f"{ns}/{name}"
    trace_payload: dict = {}
    if getattr(args, "server", None):
        client = _server_client(args)
        wl_dict = client.get_workload(ns, name)
        wl = ser.workload_from_dict(wl_dict)
        rows = client.workload_decisions(ns, name).get("items", [])
        from kueue_tpu.server.client import ClientError

        try:
            trace_payload = client.workload_trace(ns, name)
        except (ClientError, OSError):
            trace_payload = {}  # pre-tracing server / evicted trace
        _replica_note(client)
    else:
        rt = state.build_runtime()
        rt.run_until_idle()  # in-memory only: state file is NOT saved
        wl = rt.workloads.get(key)
        if wl is None:
            raise SystemExit(f"error: workload {key!r} not found")
        rows = [r.to_dict() for r in rt.audit.for_workload(key)]
        from kueue_tpu.tracing import workload_trace_payload

        trace_payload = workload_trace_payload(rt, key)
    status = "PENDING"
    if wl.is_finished:
        status = "FINISHED"
    elif wl.is_admitted:
        status = "ADMITTED"
    elif wl.has_quota_reservation:
        status = "QUOTARESERVED"
    elif not wl.active:
        status = "INACTIVE"
    _render_decision_timeline(key, status, rows)
    _render_trace_summary(rows, trace_payload)
    # MultiKueue federation: the dispatcher stamps the winning worker
    # cluster into the local workload's labels
    from kueue_tpu.federation import WINNER_LABEL

    winner = (wl.labels or {}).get(WINNER_LABEL)
    if winner:
        print(f'Winning cluster: "{winner}" (MultiKueue federation)')


def cmd_trace(state: State, args) -> None:
    """`kueuectl trace <wl> [-o trace.json]` — the workload's full
    distributed trace: lifecycle spans plus every cycle span tree its
    decisions reference, as a text tree or (with -o) Chrome
    trace-event JSON loadable in Perfetto / chrome://tracing.
    --server reads a live control plane (leader OR replica — replicas
    mirror the leader's spans off the journal feed); otherwise the
    state file is scheduled in memory and ITS trace is rendered."""
    ns, name = args.namespace, args.name
    key = f"{ns}/{name}"
    if getattr(args, "server", None):
        client = _server_client(args)
        payload = client.workload_trace(ns, name)
        _replica_note(client)
    else:
        rt = state.build_runtime()
        rt.run_until_idle()  # in-memory only
        if key not in rt.workloads:
            raise SystemExit(f"error: workload {key!r} not found")
        from kueue_tpu.tracing import workload_trace_payload

        payload = workload_trace_payload(rt, key)
    spans = payload.get("spans", [])
    if not spans:
        print(f"Workload:      {key}")
        print("Trace:         <none recorded>")
        print(
            "  (traces are kept in a bounded in-memory store; an old "
            "workload's trace may have been evicted)"
        )
        return
    if getattr(args, "output", None):
        from kueue_tpu.tracing import to_chrome_trace

        with open(args.output, "w") as f:
            json.dump(to_chrome_trace(spans), f, indent=1)
        print(
            f"wrote {len(spans)} spans to {args.output} "
            "(Chrome trace-event JSON; open in Perfetto or "
            "chrome://tracing)"
        )
        return
    print(f"Workload:      {key}")
    print(f"Trace:         {payload.get('traceId')}")
    by_trace: Dict[str, List[dict]] = {}
    for s in spans:
        by_trace.setdefault(s.get("traceId", ""), []).append(s)
    # lifecycle trace first, referenced cycle traces after
    ordered = sorted(
        by_trace.items(),
        key=lambda kv: (kv[0] != payload.get("traceId"), kv[0]),
    )
    for tid, group in ordered:
        kind = "lifecycle" if tid == payload.get("traceId") else "cycle"
        print(f"  [{kind}] {tid}")
        roots = {s["spanId"] for s in group if not s.get("parentId")}
        for s in sorted(group, key=lambda x: (x.get("start", 0.0))):
            indent = "    " if s["spanId"] in roots else "      "
            dur = s.get("durationMs")
            dur_str = f"{dur:.3f} ms" if dur is not None else "open"
            attrs = s.get("attrs") or {}
            extra = ""
            if "outcome" in attrs:
                extra = f" [{attrs['outcome']}/{attrs.get('reason', '')}]"
            elif "event" in attrs:
                extra = f" [{attrs['event']}]"
            print(f"{indent}{s.get('name')}: {dur_str}{extra}")


def cmd_clusters(state: State, args) -> None:
    """`kueuectl clusters list|add|cordon|uncordon|drain|remove` — the
    federation worker-cluster roster and dynamic membership: list shows
    connectivity/quarantine/cordon state, add joins a worker at
    runtime, cordon stops new dispatches, drain moves every placement
    off under the fencing protocol, remove drains then drops the
    worker. Reads/mutates a live federation manager (--server)."""
    if not getattr(args, "server", None):
        raise SystemExit(
            f"error: `kueuectl clusters {args.action}` needs a live "
            "federation manager; pass --server http://<manager>"
        )
    from kueue_tpu.server.client import ClientError

    client = _server_client(args)
    try:
        if args.action == "list":
            items = client.federation_clusters().get("items", [])
        else:
            if not args.name:
                raise SystemExit(
                    f"error: `kueuectl clusters {args.action}` needs a "
                    "worker cluster NAME"
                )
            if args.action == "add":
                if not args.url:
                    raise SystemExit(
                        "error: `kueuectl clusters add NAME --url URL` "
                        "— the worker control plane's URL is required"
                    )
                out = client.federation_add_worker(
                    args.name, args.url, token=args.worker_token
                )
                print(f"joined worker cluster {out.get('joined', args.name)}")
                return
            if args.action == "cordon":
                client.federation_cordon(args.name)
                print(
                    f"worker cluster {args.name} cordoned "
                    "(no new dispatches; existing placements stay)"
                )
                return
            if args.action == "uncordon":
                client.federation_uncordon(args.name)
                print(f"worker cluster {args.name} uncordoned")
                return
            if args.action == "drain":
                out = client.federation_drain(args.name)
                print(
                    f"worker cluster {args.name} drained: "
                    f"{out.get('deposed', 0)} placement(s) deposed and "
                    "re-dispatching onto surviving capacity"
                )
                return
            out = client.federation_remove_worker(args.name)
            print(f"worker cluster {out.get('removed', args.name)} removed")
            return
    except ClientError as e:
        if e.status == 404:
            raise SystemExit(f"error: {e}")
        raise
    rows = []
    for c in items:
        status = "Active" if c.get("active") else "Lost"
        if c.get("quarantinedUntil") is not None:
            status = "Quarantined"
        if c.get("cordoned"):
            status += ",Cordoned"
        rtt_p95 = c.get("rttP95") if c.get("rttSamples") else None
        rows.append(
            [
                c.get("name", ""),
                status,
                # latency health (gray-failure plane): healthy worker
                # vs limping worker vs lost wire, plus the windowed
                # p95 RTT its adaptive deadlines derive from
                c.get("health", "healthy"),
                "-" if rtt_p95 is None else f"{rtt_p95 * 1000.0:.0f}ms",
                str(c.get("wins", 0)),
                str(c.get("dispatches", 0)),
                str(c.get("strikes", 0)),
                (
                    "-"
                    if c.get("lostSince") is None
                    else f"t={c['lostSince']:.0f}"
                ),
            ]
        )
    _print_table(
        [
            "NAME", "STATUS", "HEALTH", "RTT-P95", "WINS", "DISPATCHES",
            "STRIKES", "LOST-SINCE",
        ],
        rows,
    )


def cmd_capacity(state: State, args) -> None:
    """`kueuectl capacity` — elastic capacity plane standings: what the
    provider has granted per flavor/resource, the journaled grant
    requests, in-flight asks, and the last chooser decision."""
    if not getattr(args, "server", None):
        raise SystemExit(
            "error: `kueuectl capacity` reads a live control plane; "
            "pass --server http://<leader>"
        )
    from kueue_tpu.server.client import ClientError

    client = _server_client(args)
    try:
        out = client.capacity()
    except ClientError as e:
        if e.status == 404:
            raise SystemExit(
                "error: the elastic capacity plane is not enabled on "
                "this server (start it with --elastic on)"
            )
        raise
    granted = out.get("granted") or {}
    rows = [
        [flavor, resource, str(amount)]
        for flavor in sorted(granted)
        for resource, amount in sorted(granted[flavor].items())
    ]
    _print_table(["FLAVOR", "RESOURCE", "GRANTED"], rows or [["-", "-", "0"]])
    print(
        f"provider: {out.get('provider', '?')}  "
        f"applied grants: {len(out.get('appliedRequests') or [])}  "
        f"in-flight: {len(out.get('inFlight') or [])}  "
        f"chooser launches: {out.get('chooserLaunches', 0)}"
    )
    last = out.get("lastChoice")
    if last:
        scores = ", ".join(
            f"{name}={score}"
            for name, score in sorted(
                (last.get("scores") or {}).items()
            )
        )
        print(
            f"last chooser pass ({last.get('backend', '?')}, "
            f"{last.get('launches', 0)} launch(es)): "
            f"chose {last.get('chosen', '?')}"
            + (f" [{scores}]" if scores else "")
        )


def cmd_replicas(state: State, args) -> None:
    """`kueuectl replicas` — the read-replica roster: on a leader,
    every follower that polled the replication feed with how far
    behind it is; pointed at a replica, that replica's own tail
    status."""
    if not getattr(args, "server", None):
        raise SystemExit(
            "error: `kueuectl replicas` reads a live control plane; "
            "pass --server http://<leader-or-replica>"
        )
    client = _server_client(args)
    out = client.replicas()
    if out.get("role") == "replica":
        rows = [
            [
                s.get("id", ""),
                str(s.get("hop", 1)),
                str(s.get("appliedSeq", 0)),
                f"{s.get('lagSeconds', 0.0):.3f}s",
                "/".join(
                    f"{x:.3f}" for x in s.get("pathLagSeconds", [])
                ) or "-",
                str(s.get("resyncs", 0)),
                str(s.get("recordsApplied", 0)),
                s.get("lastError", "") or "-",
            ]
            for s in out.get("items", [])
        ]
        _print_table(
            ["ID", "HOP", "APPLIED-SEQ", "LAG", "PATH-LAG", "RESYNCS",
             "RECORDS", "LAST-ERROR"],
            rows,
        )
        print(f"(replica of {out['items'][0].get('leader', '?')})"
              if out.get("items") else "(replica)")
        children = out.get("children") or []
        if children:
            print()
            print("downstream replicas tailing this node:")
            _print_table(
                ["ID", "HOP", "APPLIED-SEQ", "BEHIND", "LAG", "LAST-POLL"],
                [
                    [
                        r.get("id", ""),
                        str(r.get("hop", "?")),
                        str(r.get("appliedSeq", 0)),
                        str(r.get("behind", 0)),
                        f"{r.get('lagSeconds', 0.0):.3f}s",
                        f"{r.get('lastSeenAgoS', 0.0):.1f}s ago",
                    ]
                    for r in children
                ],
            )
        return
    rows = [
        [
            r.get("id", ""),
            str(r.get("hop", 1)),
            str(r.get("appliedSeq", 0)),
            str(r.get("behind", 0)),
            f"{r.get('lagSeconds', 0.0):.3f}s",
            f"{r.get('lastSeenAgoS', 0.0):.1f}s ago",
        ]
        for r in out.get("items", [])
    ]
    _print_table(
        ["ID", "HOP", "APPLIED-SEQ", "BEHIND", "LAG", "LAST-POLL"], rows
    )
    print(f"leader journal head: seq {out.get('lastSeq', 0)}")


def cmd_slo(state: State, args) -> None:
    """`kueuectl slo` — admission-SLO standings: per-ClusterQueue p95
    queue-to-admission target, attainment ratio and error-budget burn
    rate (the kueue_slo_* family, rendered)."""
    if not getattr(args, "server", None):
        raise SystemExit(
            "error: `kueuectl slo` reads a live control plane; "
            "pass --server http://<leader>"
        )
    client = _server_client(args)
    out = client.slo()
    _replica_note(client)
    if not out.get("enabled"):
        print(
            "SLO tracking is not configured on this control plane "
            "(start the server with --slo-target-p95 / --slo-target)"
        )
        return
    rows = []
    for e in out.get("clusterQueues", []):
        burn = e.get("burnRate", 0.0)
        if e.get("degraded"):
            status = "BURNING"
        elif e.get("burningSinceS") is not None:
            status = "burning"
        else:
            status = "ok"
        rows.append(
            [
                e.get("clusterQueue", ""),
                f"{e.get('targetSeconds', 0.0):g}s",
                f"{e.get('attainment', 1.0) * 100:.2f}%",
                f"{out.get('objective', 0.95) * 100:g}%",
                f"{burn:.2f}x",
                str(e.get("admitted", 0)),
                status,
            ]
        )
    _print_table(
        ["CLUSTERQUEUE", "TARGET-P95", "ATTAINMENT", "OBJECTIVE",
         "BURN", "ADMITTED", "STATUS"],
        rows,
    )
    if not rows:
        print(
            "(no admissions observed yet for any targeted ClusterQueue)"
        )
    window = out.get("burnWindowSeconds", 0)
    print(
        f"burn window {window:g}s; threshold "
        f"{out.get('burnThreshold', 0):g}x sustained "
        f"{out.get('sustainSeconds', 0):g}s -> degraded"
        + ("  ** DEGRADED **" if out.get("degraded") else "")
    )


# ---- plan (the what-if capacity planner) ----
def _render_plan(report: dict, target: str) -> None:
    """Render one PlanReport (wire dict) as a ranked scenario table
    plus the recommendation line — the operator-facing half of the
    stuck-workload loop (`explain` says why; `plan` says what next)."""
    rows = []
    for s in report.get("scenarios", []):
        fc = s.get("forecast") or {}
        band = fc.get("band")
        rows.append([
            s["name"] + (" *" if s.get("baseline") else ""),
            str(len(s.get("admitted", []))),
            "+" + str(len(s.get("newlyAdmitted", []))),
            str(len(s.get("lost", []))),
            str(s.get("preemptionCandidates", 0)),
            str(s.get("borrowing", 0)),
            (
                f"{fc.get('mean', 0)}s [{band[0]}-{band[1]}]"
                if band
                else ""
            ),
        ])
    _print_table(
        ["SCENARIO", "ADMITS", "NEW", "LOST", "PREEMPT", "BORROW", "TTA FORECAST"],
        rows,
    )
    print("(* = baseline: the cluster as configured today)")
    baseline = report.get("baseline") or {}
    if target and target in (baseline.get("reasons") or {}):
        why = baseline["reasons"][target]
        print(f"Today:         {target} is pending: {why['reason']}")
    rec = report.get("recommended")
    if rec:
        scen = next(
            (s for s in report["scenarios"] if s["name"] == rec), None
        )
        newly = ", ".join(scen.get("newlyAdmitted", [])) if scen else ""
        print(f"Recommended:   {rec}")
        if scen:
            for d in scen.get("deltas", []):
                print(f"  apply:       {d}")
        if newly:
            print(f"  would admit: {newly}")
    else:
        print(
            "Recommended:   <none> — no evaluated scenario admits "
            "anything the baseline doesn't"
        )
    if report.get("unmodeled"):
        print(
            "Unmodeled (host-path-only heads, excluded from the sweep): "
            + ", ".join(report["unmodeled"])
        )


def cmd_plan(state: State, args) -> None:
    """What would it take to admit this workload (or drain this CQ's
    backlog)? --server plans against a live control plane; otherwise
    the state file is loaded and planned in memory (no writes), like
    `explain`'s offline mode."""
    target = f"{args.namespace}/{args.name}" if args.name else ""
    options: Dict[str, object] = {"includeReasons": "baseline"}
    if args.forecast:
        options["forecast"] = True
        options["runtimeHintSeconds"] = args.runtime_hint
    scenarios = None
    if args.scenarios:
        with open(args.scenarios) as f:
            scenarios = json.load(f)
    if getattr(args, "policy", ""):
        scenarios = list(scenarios or [])
        scenarios.append(
            {
                "name": f"policy {args.policy}",
                "deltas": [{"kind": "policy", "policy": args.policy}],
            }
        )
    if not target and not args.clusterqueue and not scenarios:
        raise SystemExit(
            "error: plan needs a workload name, --clusterqueue, or "
            "--scenarios"
        )
    if getattr(args, "server", None):
        client = _server_client(args)
        report = client.plan(
            scenarios=scenarios,
            workload=target or None,
            cluster_queue=args.clusterqueue or None,
            options=options,
        )
        # a replica's plan is best-effort-stale by design: its state
        # trails the leader by the tail poll interval
        _replica_note(client)
    else:
        from kueue_tpu.planner import Planner, scenario_from_dict

        rt = state.build_runtime()
        rt.run_until_idle()  # in-memory only: state file is NOT saved
        planner = Planner.for_runtime(rt)
        hint = args.runtime_hint
        report = planner.plan(
            scenarios=(
                [
                    scenario_from_dict(sd, default_name=f"scenario-{i}")
                    for i, sd in enumerate(scenarios)
                ]
                if scenarios
                else None
            ),
            target_workload=target,
            target_cq=args.clusterqueue or "",
            include_reasons="baseline",
            forecast=args.forecast,
            runtime_hint=(lambda wl: hint) if args.forecast else None,
        ).to_dict()
    _render_plan(report, target)


# ---- state (offline durability tooling: fsck + replay) ----
def cmd_state_verify(state, args) -> None:
    """Offline fsck of the durable state: checkpoint parseability,
    journal chain (CRC framing, seq monotonicity, fencing tokens),
    then a full recovery into memory and the control-plane invariant
    check. Nonzero exit on corruption — run it before trusting a
    volume after an incident."""
    from kueue_tpu.storage import recover, verify_chain

    failures: List[str] = []
    ckpt_data = None
    ckpt = args.state
    if os.path.isdir(ckpt):
        # delta-checkpoint chain directory (server --state-dir): walk
        # the anchor + delta chain file-by-file, then load it the same
        # way recovery would
        from kueue_tpu.storage import load_checkpoint_chain, verify_checkpoint_chain
        from kueue_tpu.storage.checkpoint import parse_chain_name

        info = verify_checkpoint_chain(ckpt)
        for name in info.files:
            kind, base, js = parse_chain_name(name)
            if kind == "full":
                print(f"chain {name}: anchor, journalSeq={js}: OK")
            else:
                print(f"chain {name}: delta, baseSeq={base} "
                      f"journalSeq={js}: OK")
        for name in info.orphans:
            print(f"chain {name}: ORPHAN (not linked from the newest "
                  "anchor; stale or mid-GC)")
        for err in info.errors:
            print(f"chain: {err}")
        failures.extend(info.errors)
        if info.files:
            ckpt_data, _ = load_checkpoint_chain(ckpt)
            print(
                f"checkpoint chain {ckpt}: "
                f"{'OK' if info.ok else 'BROKEN'} "
                f"({len(info.files)} files, "
                f"journalSeq={info.journal_seq} "
                f"resourceVersion={info.resource_version})"
            )
        else:
            print(f"checkpoint chain {ckpt}: empty")
    elif os.path.exists(ckpt):
        try:
            with open(ckpt) as f:
                ckpt_data = json.load(f)
            persistence = ckpt_data.get("persistence", {})
            print(
                f"checkpoint {ckpt}: OK "
                f"(workloads={len(ckpt_data.get('workloads', []))} "
                f"journalSeq={persistence.get('journalSeq', 0)} "
                f"resourceVersion={persistence.get('resourceVersion', 0)} "
                f"token={persistence.get('token')})"
            )
        except (json.JSONDecodeError, ValueError) as e:
            failures.append(f"checkpoint {ckpt}: unparsable ({e})")
            print(f"checkpoint {ckpt}: CORRUPT ({e})")
    else:
        print(f"checkpoint {ckpt}: absent")

    if args.journal:
        rep = verify_chain(args.journal)
        for seg in rep.segments:
            status = "OK"
            if seg.torn:
                status = f"TORN at byte {seg.bytes_valid} ({seg.error})"
            print(
                f"segment {os.path.basename(seg.path)}: {seg.records} "
                f"records, {seg.bytes_total} bytes, "
                f"seq {seg.first_seq}-{seg.last_seq}: {status}"
            )
        if rep.torn_tail:
            print(
                "torn tail on the final segment: benign (the expected "
                "crash shape; recovery truncates and continues)"
            )
        if rep.stale_token_records:
            print(
                f"stale-fencing-token records: {rep.stale_token_records} "
                "(a deposed leader's stray appends; replay refuses them)"
            )
        failures.extend(rep.errors)
        failures.extend(rep.seq_gaps)

    if ckpt_data is not None or args.journal:
        try:
            res = recover(
                ckpt if ckpt_data is not None else None,
                args.journal or os.path.join(os.path.dirname(ckpt) or ".",
                                             "_no_journal_"),
                strict=False, readonly=True,
            )
            print(f"recovery dry run: {res.summary()}")
            for violation in res.invariant_violations:
                failures.append(f"invariant: {violation}")
        except Exception as e:  # noqa: BLE001 — fsck reports, not crashes
            failures.append(f"recovery dry run failed: {e!r}")

    if failures:
        print("FAILED:")
        for f in failures:
            print(f"  {f}")
        raise SystemExit(2)
    print("verify: OK (invariants hold)")


def cmd_state_replay(state, args) -> None:
    """Materialize a state file from checkpoint + journal — what the
    server WOULD serve after recovery, written as a normal wire-format
    state file (stdout or -o)."""
    from kueue_tpu.storage import recover

    ckpt = args.state if os.path.exists(args.state) else None
    try:
        res = recover(ckpt, args.journal, strict=False, readonly=True)
    except (json.JSONDecodeError, ValueError) as e:
        raise SystemExit(
            f"error: checkpoint {args.state!r} is unparsable ({e}); "
            "run `kueuectl state verify` for the full report"
        )
    rt = res.runtime
    out = ser.runtime_to_state(rt)
    out["persistence"]["resourceVersion"] = res.resource_version
    text = json.dumps(out, indent=1, sort_keys=True)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(
            f"replayed {res.replayed} records onto "
            f"{'checkpoint' if res.checkpoint_loaded else 'empty state'} "
            f"-> {args.output}"
        )
    else:
        print(text)
    if res.invariant_violations:
        print("WARNING: recovered state violates invariants:")
        for violation in res.invariant_violations:
            print(f"  {violation}")
        raise SystemExit(2)


# ---- events (the `kubectl get events` / `--watch` analog) ----
def cmd_events(state: State, args) -> None:
    """List the control plane's recorded events, or follow them live
    (resourceVersion long-poll — the client blocks server-side until
    something newer lands; no polling loop)."""
    if not getattr(args, "server", None):
        raise SystemExit(
            "error: events requires --server (the live event stream "
            "exists only in a running kueue_tpu.server)"
        )
    client = _server_client(args)

    def row(e: dict) -> List[str]:
        return [
            str(e.get("resourceVersion", "")),
            e.get("reason", ""),
            e.get("object", ""),
            str(e.get("count", 1)),
            e.get("message", ""),
        ]

    headers = ["RV", "REASON", "OBJECT", "COUNT", "MESSAGE"]
    if args.watch:
        _print_table(headers, [])
        try:
            for e in client.watch(
                "events", resource_version=args.resource_version
            ):
                print("  ".join(row(e)))
        except KeyboardInterrupt:
            pass
        return
    out = client.events(args.resource_version)
    _replica_note(client)
    _print_table(headers, [row(e) for e in out.get("items", [])])
    print(f"resourceVersion: {out.get('resourceVersion', 0)}")


# ---- quarantine (core/guard.py poison-workload triage) ----
def cmd_quarantine(state: State, args) -> None:
    """``kueuectl quarantine list|clear`` — inspect and release the
    poison-workload quarantine. Server mode talks to the live control
    plane (/debug/quarantine); offline mode reads/edits the state
    file's ``quarantine`` section (the checkpointed entries)."""
    if getattr(args, "server", None):
        client = _server_client(args)
        if args.action == "clear":
            out = client.quarantine_clear(args.workload or None)
            cleared = out.get("cleared", [])
            print(
                f"cleared {len(cleared)} workload(s): "
                + (", ".join(cleared) if cleared else "<none>")
            )
            return
        out = client.quarantine_list()
        solver = out.get("solver", {})
        if solver:
            print(
                f"solver path: {solver.get('path')} "
                f"(breaker {solver.get('breaker')}, "
                f"{solver.get('failovers', 0)} failovers, "
                f"{solver.get('divergences', 0)} divergences)"
            )
        _print_table(
            ["WORKLOAD", "STRIKES", "SINCE", "UNTIL", "REASON"],
            [
                [
                    q.get("key", ""),
                    str(q.get("strikes", 0)),
                    f"{q.get('since', 0):.0f}",
                    f"{q.get('until', 0):.0f}",
                    q.get("message", ""),
                ]
                for q in out.get("items", [])
            ],
        )
        return
    entries = state.data.get("quarantine", [])
    if args.action == "clear":
        keep = [
            q for q in entries
            if args.workload and q.get("key") != args.workload
        ]
        cleared = [q["key"] for q in entries if q not in keep]
        state.data["quarantine"] = keep
        state.save()
        print(
            f"cleared {len(cleared)} workload(s): "
            + (", ".join(cleared) if cleared else "<none>")
        )
        return
    _print_table(
        ["WORKLOAD", "STRIKES", "SINCE", "UNTIL", "REASON"],
        [
            [
                q.get("key", ""),
                str(q.get("strikes", 0)),
                f"{q.get('since', 0):.0f}",
                f"{q.get('until', 0):.0f}",
                q.get("message", ""),
            ]
            for q in entries
        ],
    )


# ---- schedule ----
def cmd_schedule(state: State, args) -> None:
    rt = state.build_runtime()
    if getattr(args, "drain", False):
        # capacity what-if: the pending backlog planned in one device
        # dispatch (core/drain) and summarized; the cycle loop below
        # then takes the authoritative decisions (identical by the
        # drain parity suites, plus it handles fallbacks). Backlog
        # collection (ClusterRuntime.drain_backlog), scope selection
        # (classify_drain_scope) and dispatch (run_drain_for_scope) are
        # the SAME code the service bulk path runs, so the plan routes
        # exactly like production.
        from kueue_tpu.core.drain import (
            classify_drain_scope,
            run_drain_for_scope,
        )
        from kueue_tpu.core.queue_manager import queue_order_timestamp
        from kueue_tpu.core.snapshot import take_snapshot

        snapshot = take_snapshot(rt.cache)
        backlog = rt.drain_backlog(snapshot)
        tas_flavors = (
            set(rt.cache.tas_cache.flavors)
            if rt.cache.tas_cache is not None
            else set()
        )
        kind, pending = classify_drain_scope(
            snapshot, backlog, tas_flavors, rt.scheduler.fair_sharing
        )
        outcome = run_drain_for_scope(
            kind, snapshot, pending, rt.cache.flavors,
            tas_cache=rt.cache.tas_cache,
            fs_strategies=getattr(
                rt.scheduler.preemptor, "fs_strategies", None
            ),
            timestamp_fn=lambda wl: queue_order_timestamp(
                wl, rt.queues._ts_policy
            ),
            ordered_stamps=getattr(rt.clock, "tracks_wall", False),
        )
        evicted = len(getattr(outcome, "evictions", []) or [])
        # heads the classifier dropped to the cycle loop (TAS heads in
        # a preempt/fair backlog) were never planned — say so, or the
        # counts read as if they were rejected
        excluded = len(backlog) - len(pending)
        print(
            f"drain plan ({kind}): cycles={outcome.cycles} "
            f"admitted={len(outcome.admitted)} "
            f"evicted={evicted} "
            f"parked={len(outcome.parked)} "
            f"fallback={len(outcome.fallback)} "
            f"excluded={excluded}"
        )
    for _ in range(args.cycles):
        rt.run_until_idle()
    state.data["workloads"] = [
        ser.workload_to_dict(wl) for wl in rt.workloads.values()
    ]
    state.save()
    admitted = sum(1 for wl in rt.workloads.values() if wl.is_admitted)
    pending = sum(
        rt.queues.pending_workloads(name)
        for name in rt.queues.cluster_queues
    )
    print(f"admitted={admitted} pending={pending}")


# ---- importer (cmd/importer) ----
def cmd_import(state: State, args) -> None:
    """Bulk-import running pods: each becomes an admitted workload
    charging usage (cmd/importer/pod)."""
    with open(args.file) as f:
        pods = json.load(f)
    imported = 0
    skipped = 0
    lqs = {
        (l["namespace"], l["name"]): l["clusterQueue"]
        for l in state.data.get("localQueues", [])
    }
    for pod in pods:
        queue = pod.get("labels", {}).get("kueue.x-k8s.io/queue-name", "")
        cq = lqs.get((pod["namespace"], queue))
        if cq is None:
            skipped += 1
            continue
        requests = requests_from_spec(pod.get("requests", {}))
        wl = Workload(
            namespace=pod["namespace"],
            name=f"pod-{pod['name']}",
            queue_name=queue,
            pod_sets=(PodSet(name="main", count=1, requests=requests),),
        )
        # imported pods are already running: admit directly at the
        # first flavor of the CQ (importer/pod/pod.go)
        cq_obj = ser.cq_from_dict(state.find("clusterQueues", cq))
        flavor = cq_obj.resource_groups[0].flavors[0].name
        from kueue_tpu.models.workload import Admission, PodSetAssignment

        wl.admission = Admission(
            cluster_queue=cq,
            pod_set_assignments=(
                PodSetAssignment(
                    name="main",
                    flavors={r: flavor for r in requests},
                    resource_usage=dict(requests),
                    count=1,
                ),
            ),
        )
        wl.set_condition(WorkloadConditionType.QUOTA_RESERVED, True, "QuotaReserved")
        wl.set_condition(WorkloadConditionType.ADMITTED, True, "Admitted")
        state.upsert("workloads", ser.workload_to_dict(wl))
        imported += 1
    state.save()
    print(f"imported={imported} skipped={skipped}")


def cmd_lint(state: State, args) -> None:
    """kueuelint — the AST-based static analysis suite
    (kueue_tpu/analysis): kernel dtype/trace safety, journal<->replay
    symmetry, clock & lock discipline, registry lints. Exit 2 on
    findings the shrink-only baseline does not cover."""
    from kueue_tpu.analysis.__main__ import main as lint_main

    argv: List[str] = []
    for rule in args.rule or []:
        argv += ["--rule", rule]
    for flag in ("update_baseline", "allow_grow", "no_baseline",
                 "list_rules", "quiet"):
        if getattr(args, flag):
            argv.append("--" + flag.replace("_", "-"))
    rc = lint_main(argv)
    if rc != 0:
        raise SystemExit(rc)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="kueuectl")
    ap.add_argument("--state", default="kueue-state.json")
    sub = ap.add_subparsers(dest="cmd", required=True)

    create = sub.add_parser("create").add_subparsers(dest="kind", required=True)
    ccq = create.add_parser("clusterqueue", aliases=["cq"])
    ccq.add_argument("name")
    ccq.add_argument("--cohort")
    ccq.add_argument("--flavor", default="default")
    ccq.add_argument("--nominal-quota", required=True, help="cpu=10,memory=5Gi")
    ccq.add_argument("--borrowing-limit")
    ccq.add_argument("--lending-limit")
    ccq.add_argument("--queuing-strategy", default="BestEffortFIFO",
                     choices=["StrictFIFO", "BestEffortFIFO"])
    ccq.add_argument("--reclaim-within-cohort", default="Never",
                     choices=["Never", "LowerPriority", "Any"])
    ccq.add_argument("--preemption-within-cluster-queue", default="Never",
                     choices=["Never", "LowerPriority", "LowerOrNewerEqualPriority"])
    ccq.set_defaults(fn=cmd_create_cq)

    clq = create.add_parser("localqueue", aliases=["lq"])
    clq.add_argument("name")
    clq.add_argument("-n", "--namespace", default="default")
    clq.add_argument("-c", "--clusterqueue", required=True)
    clq.set_defaults(fn=cmd_create_lq)

    crf = create.add_parser("resourceflavor", aliases=["rf"])
    crf.add_argument("name")
    crf.add_argument("--node-labels")
    crf.add_argument("--topology")
    crf.set_defaults(fn=cmd_create_rf)

    cto = create.add_parser("topology")
    cto.add_argument("name")
    cto.add_argument(
        "--levels", required=True,
        help="comma-separated node label keys, top level first "
        "(e.g. block,rack,kubernetes.io/hostname)",
    )
    cto.set_defaults(fn=cmd_create_topology)

    cnode = create.add_parser("node")
    cnode.add_argument("name")
    cnode.add_argument(
        "--labels", required=True,
        help="topology-level labels, k=v comma-separated",
    )
    cnode.add_argument(
        "--allocatable", required=True,
        help="capacity, resource=quantity comma-separated "
        "(e.g. cpu=8,pods=32)",
    )
    cnode.add_argument("--not-ready", action="store_true")
    cnode.set_defaults(fn=cmd_create_node)

    cwl = create.add_parser("workload", aliases=["wl"])
    cwl.add_argument("name")
    cwl.add_argument("-n", "--namespace", default="default")
    cwl.add_argument("-q", "--localqueue", required=True)
    cwl.add_argument("--count", type=int, default=1)
    cwl.add_argument("--requests", required=True, help="cpu=1,memory=1Gi")
    cwl.add_argument("--priority", type=int, default=0)
    topo_group = cwl.add_mutually_exclusive_group()
    topo_group.add_argument(
        "--topology-required",
        help="gang placement: required topology level (node label key)",
    )
    topo_group.add_argument(
        "--topology-preferred",
        help="gang placement: preferred topology level (node label key)",
    )
    cwl.set_defaults(fn=cmd_create_workload)

    lst = sub.add_parser("list").add_subparsers(dest="kind", required=True)
    lcq = lst.add_parser("clusterqueue", aliases=["cq"])
    lcq.set_defaults(fn=cmd_list_cq)
    llq = lst.add_parser("localqueue", aliases=["lq"])
    llq.add_argument("-n", "--namespace", default="")
    llq.set_defaults(fn=cmd_list_lq)
    lto = lst.add_parser("topology")
    lto.set_defaults(fn=cmd_list_topology)
    lnode = lst.add_parser("node")
    lnode.set_defaults(fn=cmd_list_node)
    lrf = lst.add_parser("resourceflavor", aliases=["rf"])
    lrf.set_defaults(fn=cmd_list_rf)
    lwl = lst.add_parser("workload", aliases=["wl"])
    lwl.add_argument("-n", "--namespace", default="")
    lwl.set_defaults(fn=cmd_list_workload)

    for verb, fn in (("stop", cmd_stop), ("resume", cmd_resume)):
        p = sub.add_parser(verb)
        p.add_argument("kind", choices=["workload", "clusterqueue", "localqueue"])
        p.add_argument("name")
        p.add_argument("-n", "--namespace", default="default")
        p.set_defaults(fn=fn)

    dele = sub.add_parser("delete")
    dele.add_argument("kind", choices=sorted(_DELETE_SECTIONS))
    dele.add_argument("name")
    dele.add_argument("-n", "--namespace", default="default")
    _add_server_flags(dele, "delete on a running kueue_tpu.server instead of --state")
    dele.set_defaults(fn=cmd_delete)

    get = sub.add_parser("get")
    get.add_argument("kind", choices=sorted(_DELETE_SECTIONS))
    get.add_argument("name")
    get.add_argument("-n", "--namespace", default="default")
    _add_server_flags(get, "read from a running kueue_tpu.server instead of --state")
    get.set_defaults(fn=cmd_get)

    ver = sub.add_parser("version")
    ver.set_defaults(fn=cmd_version)

    lnt = sub.add_parser(
        "lint",
        help="kueuelint static analysis over the kueue_tpu package",
    )
    lnt.add_argument(
        "--rule", "-r", action="append", metavar="RULE",
        help="run only this rule (repeatable)",
    )
    lnt.add_argument("--update-baseline", action="store_true")
    lnt.add_argument("--allow-grow", action="store_true")
    lnt.add_argument("--no-baseline", action="store_true")
    lnt.add_argument("--list-rules", action="store_true")
    lnt.add_argument("-q", "--quiet", action="store_true")
    lnt.set_defaults(fn=cmd_lint)

    st = sub.add_parser(
        "state",
        help="durable-state tooling: offline fsck and journal replay",
    )
    stsub = st.add_subparsers(dest="verb", required=True)
    sv = stsub.add_parser(
        "verify",
        help="fsck checkpoint + journal chain (CRC, fencing tokens, "
        "invariants); nonzero exit on corruption",
    )
    sv.add_argument(
        "--journal",
        help="journal directory (omit to verify the checkpoint alone)",
    )
    sv.set_defaults(fn=cmd_state_verify, tolerates_corrupt_state=True)
    sr = stsub.add_parser(
        "replay",
        help="materialize a state file from checkpoint + journal "
        "(what the server would serve after recovery)",
    )
    sr.add_argument("--journal", required=True, help="journal directory")
    sr.add_argument("-o", "--output", help="write here instead of stdout")
    sr.set_defaults(fn=cmd_state_replay, tolerates_corrupt_state=True)

    ev = sub.add_parser("events")
    ev.add_argument(
        "-w", "--watch", action="store_true",
        help="follow the stream live (resourceVersion long-poll; "
        "Ctrl-C to stop)",
    )
    ev.add_argument(
        "--resource-version", type=int, default=0,
        help="only events newer than this resourceVersion",
    )
    _add_server_flags(ev, "read events from a running kueue_tpu.server")
    ev.set_defaults(fn=cmd_events)

    qr = sub.add_parser(
        "quarantine",
        help="poison-workload quarantine triage: list sidelined "
        "workloads or clear (requeue) them",
    )
    qr.add_argument("action", choices=["list", "clear"])
    qr.add_argument(
        "workload", nargs="?", default="",
        help="ns/name to clear (clear with no workload releases all)",
    )
    _add_server_flags(
        qr, "live control plane to triage (default: the --state file's "
        "checkpointed quarantine section)",
    )
    qr.set_defaults(fn=cmd_quarantine)

    pw = sub.add_parser("pending-workloads")
    pw.add_argument("clusterqueue", nargs="?", default=None)
    pw.add_argument(
        "--global", dest="global_view", action="store_true",
        help="federation-wide view (needs --server pointing at a "
        "manager running --global-scheduler on): every pending "
        "workload's per-cluster forecast, current vs best placement, "
        "and per-worker standings",
    )
    _add_server_flags(pw, "query a running kueue_tpu.server instead of --state")
    pw.set_defaults(fn=cmd_pending_workloads)

    exp = sub.add_parser(
        "explain",
        help="render a workload's admission-decision history "
        "(why pending / how admitted)",
    )
    exp.add_argument("name")
    exp.add_argument("-n", "--namespace", default="default")
    _add_server_flags(
        exp, "read the decision trail from a running kueue_tpu.server"
    )
    exp.set_defaults(fn=cmd_explain)

    tr = sub.add_parser(
        "trace",
        help="render a workload's distributed trace (lifecycle + "
        "cycle span trees); -o exports Chrome trace-event JSON for "
        "Perfetto",
    )
    tr.add_argument("name")
    tr.add_argument("-n", "--namespace", default="default")
    tr.add_argument(
        "-o", "--output",
        help="write Chrome trace-event JSON here instead of printing "
        "the span tree (load in Perfetto / chrome://tracing)",
    )
    _add_server_flags(tr, "read traces from a running kueue_tpu.server")
    tr.set_defaults(fn=cmd_trace)

    cl = sub.add_parser(
        "clusters",
        help="MultiKueue federation: worker-cluster roster "
        "(connectivity, quarantine, cordon state) and dynamic "
        "membership (add / cordon / uncordon / drain / remove)",
    )
    cl.add_argument(
        "action",
        choices=["list", "add", "cordon", "uncordon", "drain", "remove"],
    )
    cl.add_argument(
        "name", nargs="?", default="",
        help="worker cluster name (every action except list)",
    )
    cl.add_argument(
        "--url", default="",
        help="worker control plane URL (clusters add)",
    )
    cl.add_argument(
        "--worker-token", default=None,
        help="bearer token the manager presents to the new worker "
        "(clusters add)",
    )
    _add_server_flags(cl, "federation manager to query (required)")
    cl.set_defaults(fn=cmd_clusters)

    cap = sub.add_parser(
        "capacity",
        help="elastic capacity plane: provider grants per "
        "flavor/resource, journaled grant requests, in-flight asks "
        "and the last chooser decision",
    )
    _add_server_flags(cap, "control plane to query (required)")
    cap.set_defaults(fn=cmd_capacity)

    repl = sub.add_parser(
        "replicas",
        help="read-replica roster: followers tailing this leader's "
        "journal and how far behind each is (or, against a replica, "
        "its own tail status)",
    )
    _add_server_flags(repl, "leader (or replica) to query (required)")
    repl.set_defaults(fn=cmd_replicas)

    slo = sub.add_parser(
        "slo",
        help="admission-SLO standings: per-ClusterQueue p95 "
        "queue-to-admission target, attainment ratio and error-budget "
        "burn rate (kueue_slo_* rendered)",
    )
    _add_server_flags(slo, "control plane to query (required)")
    slo.set_defaults(fn=cmd_slo)

    pl = sub.add_parser(
        "plan",
        help="what-if capacity planner: which config change would "
        "admit this workload (or this ClusterQueue's backlog), and "
        "when",
    )
    pl.add_argument(
        "name", nargs="?", default="",
        help="target workload name (omit with --clusterqueue)",
    )
    pl.add_argument("-n", "--namespace", default="default")
    pl.add_argument(
        "--clusterqueue", default="",
        help="plan a quota sweep for this ClusterQueue instead of one "
        "workload",
    )
    pl.add_argument(
        "--scenarios",
        help="JSON file with explicit scenarios "
        '([{"name", "deltas": [{"kind": "quota", ...}]}])',
    )
    pl.add_argument(
        "--policy", default="",
        help="what-if an admission-policy switch (kueue_tpu/policy "
        "registry, e.g. gavel): adds a policy scenario next to the "
        "baseline — run with --forecast to compare makespan/TTA "
        "before enabling --policy on the server",
    )
    pl.add_argument(
        "--forecast", action="store_true",
        help="include the virtual-time time-to-admission forecast",
    )
    pl.add_argument(
        "--runtime-hint", type=float, default=600.0,
        help="assumed per-workload runtime seconds for the forecast "
        "(default 600)",
    )
    _add_server_flags(pl, "plan against a running kueue_tpu.server")
    pl.set_defaults(fn=cmd_plan)

    sch = sub.add_parser("schedule")
    sch.add_argument("--cycles", type=int, default=1)
    sch.add_argument(
        "--drain", action="store_true",
        help="print a bulk what-if plan (whole backlog in one device "
        "dispatch) before the cycle loop decides",
    )
    sch.set_defaults(fn=cmd_schedule)

    imp = sub.add_parser("import")
    imp.add_argument("--file", required=True)
    imp.set_defaults(fn=cmd_import)

    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        state = State(args.state)
    except (json.JSONDecodeError, ValueError) as e:
        if getattr(args, "tolerates_corrupt_state", False):
            # `state verify`/`state replay` must run AGAINST corruption
            # — they load (and report) the file themselves
            state = None
        else:
            raise SystemExit(
                f"error: cannot parse state file {args.state!r}: {e}"
            )
    try:
        args.fn(state, args)
    except BrokenPipeError:
        # stdout closed early (e.g. piped through `head`): exit quietly
        # the way kubectl-style tools do
        try:
            sys.stdout.close()
        except OSError:
            pass
        return 141  # 128 + SIGPIPE
    return 0


if __name__ == "__main__":
    from kueue_tpu._jax import use_compile_cache

    use_compile_cache()
    sys.exit(main())
