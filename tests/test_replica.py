"""Journal-tailing read replicas: incremental tailing over local and
HTTP sources, segment-index skip + offset-cursor feed reads, torn-tail
/ rotation / compaction / fencing-handover handling, replica serving
surfaces (visibility, watch resourceVersion contract, explain, plan,
307 write redirects, healthz/metrics/dashboard/SIGUSR2), and the
byte-identical quiescent-convergence property the ISSUE-9 acceptance
names — with chaos via the ``replica.tail_gap`` / ``replica.resync``
fault points.
"""

import json
import os
import threading

import pytest

from kueue_tpu import serialization as ser
from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.models import LocalQueue, ResourceFlavor, Workload
from kueue_tpu.models.workload import PodSet
from kueue_tpu.storage import (
    HTTPTailSource,
    Journal,
    JournalTailer,
    LocalTailSource,
    TailSourceError,
)
from kueue_tpu.storage.journal import select_segments
from kueue_tpu.testing import faults
from kueue_tpu.utils.clock import FakeClock


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---- scenario helpers (test_storage idiom) ----
def cq_dict(name, quota="4"):
    return {
        "name": name,
        "namespaceSelector": {},
        "resourceGroups": [
            {
                "coveredResources": ["cpu"],
                "flavors": [
                    {
                        "name": "default",
                        "resources": [{"name": "cpu", "nominalQuota": quota}],
                    }
                ],
            }
        ],
    }


def fresh_rt(clock_start=0.0):
    return ClusterRuntime(
        clock=FakeClock(clock_start), use_solver=False,
        bulk_drain_threshold=None,
    )


def leader_with_journal(tmp_path, name="journal", **journal_kw):
    rt = fresh_rt()
    journal = Journal(str(tmp_path / name), **journal_kw).open()
    rt.attach_journal(journal)
    rt.add_flavor(ResourceFlavor(name="default"))
    rt.add_cluster_queue(ser.cq_from_dict(cq_dict("cq-0")))
    rt.add_local_queue(
        LocalQueue(namespace="ns", name="lq-0", cluster_queue="cq-0")
    )
    return rt, journal


def submit(rt, name, cpu="1", prio=0):
    rt.add_workload(
        Workload(
            namespace="ns", name=name, queue_name="lq-0", priority=prio,
            pod_sets=(PodSet.build("main", 1, {"cpu": cpu}),),
        )
    )
    rt.run_until_idle()


def state_of(rt) -> str:
    out = ser.runtime_to_state(rt)
    out.pop("persistence")
    return json.dumps(out, sort_keys=True)


def local_tailer(tmp_path, state_path=None, name="journal"):
    return JournalTailer(
        LocalTailSource(
            str(tmp_path / name),
            state_path=str(state_path) if state_path else None,
        ),
        build_runtime=fresh_rt,
    )


def checkpoint_to(path, rt, token=None):
    state = ser.runtime_to_state(rt)
    if token is not None:
        state["persistence"]["token"] = token
    path.write_text(json.dumps(state))


# ---- satellite: segment-index skip in Journal.records(min_seq) ----
class TestSegmentIndex:
    def _journal(self, tmp_path, n=30, segment_max_bytes=400):
        j = Journal(
            str(tmp_path / "j"), segment_max_bytes=segment_max_bytes
        ).open()
        for i in range(n):
            j.append("object_upsert", {"section": "x", "object": {"i": i}})
        return j

    def test_select_segments_skips_covered(self, tmp_path):
        j = self._journal(tmp_path)
        names = sorted(
            n for n in os.listdir(j.path) if n.endswith(".wal")
        )
        assert len(names) > 3, "scenario must rotate several segments"
        # min_seq far into the chain: every fully-covered segment drops
        kept = select_segments(names, 25)
        assert kept == names[-len(kept):], "kept set must be a suffix"
        assert len(kept) < len(names)
        # the segments holding seq 26..30 must all be kept
        first_kept = int(kept[0][len("journal-"):-len(".wal")])
        assert first_kept <= 26
        # min_seq 0 keeps everything; huge min_seq keeps only the tail
        assert select_segments(names, 0) == names
        assert len(select_segments(names, 10 ** 9)) >= 1

    def test_records_equal_full_scan(self, tmp_path):
        j = self._journal(tmp_path)
        for min_seq in (0, 1, 7, 15, 29, 30, 99):
            via_index = [r.seq for r in j.records(min_seq)]
            expected = [s for s in range(1, 31) if s > min_seq]
            assert via_index == expected

    def test_tail_records_cursor_matches_cold_scan(self, tmp_path):
        j = self._journal(tmp_path, n=10)
        first = j.tail_records(0)
        assert [r.seq for r in first] == list(range(1, 11))
        # warm repeat at the head: nothing new, cursor holds
        assert j.tail_records(10) == []
        # appends (with rotation) land incrementally via the cursor
        for i in range(10, 16):
            j.append("object_upsert", {"section": "x", "object": {"i": i}})
        warm = [r.seq for r in j.tail_records(10)]
        assert warm == list(range(11, 17))
        # a cold cursor (different seq) still answers correctly
        assert [r.seq for r in j.tail_records(3)][:3] == [4, 5, 6]

    def test_tail_records_survives_compaction(self, tmp_path):
        j = self._journal(tmp_path, n=20)
        assert [r.seq for r in j.tail_records(18)] == [19, 20]
        j.compact(15)
        # cursor segment may be gone; the indexed cold path answers
        assert [r.seq for r in j.tail_records(18)] == [19, 20]
        assert j.first_available_seq() > 1

    def test_first_available_seq(self, tmp_path):
        j = Journal(str(tmp_path / "j")).open()
        assert j.first_available_seq() == 1
        for i in range(5):
            j.append("object_upsert", {"section": "x", "object": {"i": i}})
        j.compact(5)
        assert j.first_available_seq() == 6


# ---- local tailing ----
class TestLocalTailer:
    def test_incremental_apply_converges(self, tmp_path):
        rt, _ = leader_with_journal(tmp_path)
        tailer = local_tailer(tmp_path)
        res = tailer.poll_once()
        assert res.applied > 0 and res.caught_up and not res.error
        submit(rt, "wl-0")
        submit(rt, "wl-1", cpu="8")  # does not fit: stays pending
        res = tailer.poll_once()
        assert res.applied > 0
        assert state_of(tailer.runtime) == state_of(rt)
        assert tailer.runtime.workloads["ns/wl-0"].is_admitted
        assert not tailer.runtime.workloads["ns/wl-1"].is_admitted
        assert tailer.runtime.check_invariants() == []
        # replica rv mirrors the leader's mutation counter
        assert tailer.runtime.resource_version == rt.resource_version

    def test_segment_rotation_is_invisible(self, tmp_path):
        rt, journal = leader_with_journal(
            tmp_path, segment_max_bytes=500
        )
        tailer = local_tailer(tmp_path)
        tailer.poll_once()
        for i in range(12):
            submit(rt, f"wl-{i}")
        assert journal.stats().segments > 1
        tailer.poll_once()
        assert state_of(tailer.runtime) == state_of(rt)

    def test_torn_tail_not_applied_then_retried(self, tmp_path):
        rt, journal = leader_with_journal(tmp_path)
        tailer = local_tailer(tmp_path)
        tailer.poll_once()
        before = tailer.applied_seq
        submit(rt, "wl-0")
        # tear the newest frame: the tailer must stop cleanly before it
        seg = journal.segment_paths()[-1]
        full = open(seg, "rb").read()
        faults.corrupt_tail(seg, 5)
        applied_torn = tailer.poll_once().applied
        torn_seq = tailer.applied_seq
        assert torn_seq < journal.last_seq
        # the write completes (leader finishes the frame): applied now
        with open(seg, "wb") as f:
            f.write(full)
        tailer.poll_once()
        assert tailer.applied_seq == journal.last_seq
        assert state_of(tailer.runtime) == state_of(rt)
        assert applied_torn + tailer.records_applied >= before

    def test_compaction_jump_resyncs_from_checkpoint(self, tmp_path):
        rt, journal = leader_with_journal(
            tmp_path, segment_max_bytes=400
        )
        ckpt = tmp_path / "state.json"
        tailer = local_tailer(tmp_path, state_path=ckpt)
        tailer.poll_once()
        for i in range(10):
            submit(rt, f"wl-{i}")
        # leader checkpoints + compacts: the tailer's resume segment is
        # deleted out from under it
        checkpoint_to(ckpt, rt)
        deleted = journal.compact(journal.last_seq)
        assert deleted > 0
        res = tailer.poll_once()
        assert res.resynced and tailer.resyncs == 1
        assert tailer.applied_seq == journal.last_seq
        assert state_of(tailer.runtime) == state_of(rt)
        # post-resync tailing continues incrementally
        submit(rt, "wl-after")
        res = tailer.poll_once()
        assert res.applied > 0 and not res.resynced
        assert state_of(tailer.runtime) == state_of(rt)

    def test_compaction_jump_without_checkpoint_reports_error(self, tmp_path):
        rt, journal = leader_with_journal(
            tmp_path, segment_max_bytes=400
        )
        tailer = local_tailer(tmp_path)  # no state_path
        tailer.poll_once()
        for i in range(10):
            submit(rt, f"wl-{i}")
        journal.compact(journal.last_seq)
        res = tailer.poll_once()
        assert res.error and "resync" in res.error
        assert tailer.last_error
        # the previous consistent state keeps serving
        assert tailer.runtime.check_invariants() == []

    def test_stale_fence_records_refused(self, tmp_path):
        rt, journal = leader_with_journal(tmp_path)
        journal.token_provider = lambda: 5
        submit(rt, "wl-0")
        tailer = local_tailer(tmp_path)
        tailer.poll_once()
        assert tailer.max_token == 5
        reference = state_of(tailer.runtime)
        # a deposed leader's stray append lands with an older token
        journal.append(
            "workload_upsert",
            ser.workload_to_dict(
                Workload(
                    namespace="ns", name="stray", queue_name="lq-0",
                    pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
                )
            ),
            token=1,
        )
        res = tailer.poll_once()
        assert res.skipped_stale == 1
        assert "ns/stray" not in tailer.runtime.workloads
        assert state_of(tailer.runtime) == reference
        # but the cursor advanced past it: newer records still apply
        journal.token_provider = lambda: 5
        submit(rt, "wl-1")
        tailer.poll_once()
        assert "ns/wl-1" in tailer.runtime.workloads

    def test_fence_handover_reanchors_on_checkpoint(self, tmp_path):
        rt, journal = leader_with_journal(tmp_path)
        journal.token_provider = lambda: 1
        submit(rt, "wl-0")
        ckpt = tmp_path / "state.json"
        tailer = local_tailer(tmp_path, state_path=ckpt)
        tailer.poll_once()
        assert tailer.max_token == 1
        # leader handover: the new leader's records carry a HIGHER
        # token; the replica must re-anchor on the new checkpoint
        # rather than trust its own pre-handover prefix
        journal.token_provider = lambda: 7
        submit(rt, "wl-1")
        checkpoint_to(ckpt, rt, token=7)
        res = tailer.poll_once()
        assert res.resynced and tailer.resyncs == 1
        assert tailer.max_token == 7
        assert state_of(tailer.runtime) == state_of(rt)
        assert tailer.runtime.check_invariants() == []

    def test_chaos_crash_at_fault_points_recovers(self, tmp_path):
        rt, journal = leader_with_journal(
            tmp_path, segment_max_bytes=400
        )
        ckpt = tmp_path / "state.json"
        tailer = local_tailer(tmp_path, state_path=ckpt)
        tailer.poll_once()
        for i in range(8):
            submit(rt, f"wl-{i}")
        checkpoint_to(ckpt, rt)
        journal.compact(journal.last_seq)
        # crash the replica INSIDE the gap-detection window
        faults.arm("replica.tail_gap", action="crash")
        with pytest.raises(faults.InjectedCrash):
            tailer.poll_once()
        faults.reset()
        # crash it INSIDE the resync rebuild
        faults.arm("replica.resync", action="crash")
        with pytest.raises(faults.InjectedCrash):
            tailer.poll_once()
        faults.reset()
        # next poll completes the resync and converges byte-identical
        res = tailer.poll_once()
        assert res.resynced
        assert state_of(tailer.runtime) == state_of(rt)
        assert tailer.runtime.check_invariants() == []

    def test_inconsistent_feed_reanchors_after_grace(self, tmp_path):
        """A feed claiming a head PAST the cursor while shipping zero
        records and no compaction marker (journal dir deleted under a
        live leader) must re-anchor on a checkpoint after a short
        grace (one empty poll can be a torn in-flight frame)."""
        rt, _ = leader_with_journal(tmp_path)
        submit(rt, "wl-0")
        ckpt = tmp_path / "state.json"
        checkpoint_to(ckpt, rt)

        from kueue_tpu.storage.tailer import TailBatch

        class LyingSource:
            def __init__(self):
                self.local = LocalTailSource(
                    str(tmp_path / "journal"), state_path=str(ckpt)
                )
                self.lying = False

            def fetch(self, since_seq, since_event_rv=0,
                      since_audit_seq=0, status=None):
                if self.lying:
                    return TailBatch(last_seq=since_seq + 50)
                return self.local.fetch(since_seq)

            def checkpoint(self):
                return self.local.checkpoint()

        src = LyingSource()
        tailer = JournalTailer(src, build_runtime=fresh_rt)
        assert tailer.poll_once().caught_up
        src.lying = True
        # two empty-behind polls are tolerated (torn-frame grace)...
        assert not tailer.poll_once().resynced
        assert not tailer.poll_once().resynced
        # ...the third re-anchors on the checkpoint
        res = tailer.poll_once()
        assert res.resynced and tailer.resyncs == 1
        assert state_of(tailer.runtime) == state_of(rt)

    def test_resync_failure_keeps_previous_runtime(self, tmp_path):
        rt, journal = leader_with_journal(
            tmp_path, segment_max_bytes=400
        )
        ckpt = tmp_path / "state.json"
        tailer = local_tailer(tmp_path, state_path=ckpt)
        tailer.poll_once()
        reference = state_of(tailer.runtime)
        for i in range(8):
            submit(rt, f"wl-{i}")
        journal.compact(journal.last_seq)  # no checkpoint written yet
        ckpt.write_text("{ definitely not json")
        res = tailer.poll_once()
        assert res.error
        assert state_of(tailer.runtime) == reference  # still serving
        checkpoint_to(ckpt, rt)  # checkpoint lands: next poll heals
        res = tailer.poll_once()
        assert res.resynced
        assert state_of(tailer.runtime) == state_of(rt)


# ---- recorder / audit replication primitives ----
class TestIngestPrimitives:
    def test_event_ingest_preserves_resource_version(self):
        from kueue_tpu.core.events import EventRecorder

        leader = EventRecorder()
        replica = EventRecorder()
        leader.record("Admitted", "ns/a", "fits")
        leader.record("Pending", "ns/b", "no quota")
        items, _ = leader.since(0)
        for item in items:
            replica.ingest(item)
        assert replica.resource_version == leader.resource_version
        mirrored, too_old = replica.since(0)
        assert not too_old
        assert [e["resourceVersion"] for e in mirrored] == [
            e["resourceVersion"] for e in items
        ]
        # count-dedup restamp mirrors as an update, not a duplicate
        leader.record("Pending", "ns/b", "no quota")
        items2, _ = leader.since(replica.resource_version)
        for item in items2:
            replica.ingest(item)
        final, _ = replica.since(0)
        assert len(final) == 2
        assert final[-1]["count"] == 2
        assert replica.resource_version == leader.resource_version

    def test_event_note_gap_forces_relist(self):
        from kueue_tpu.core.events import EventRecorder

        replica = EventRecorder()
        replica.ingest(
            {"reason": "Admitted", "object": "ns/a", "message": "",
             "regarding": {"kind": "Workload"}, "resourceVersion": 50}
        )
        replica.note_gap(49)
        _, too_old = replica.since(10)
        assert too_old  # a watcher resumed below the gap must relist
        _, ok = replica.since(50)
        assert not ok

    def test_audit_since_and_ingest_round_trip(self):
        from kueue_tpu.core.audit import DecisionAuditLog, DecisionRecord
        from kueue_tpu.models.constants import InadmissibleReason

        leader = DecisionAuditLog()
        replica = DecisionAuditLog()
        for i in range(3):
            leader.record(
                DecisionRecord(
                    workload=f"ns/w-{i}", cluster_queue="cq", cycle=i,
                    outcome="Pending",
                    reason=InadmissibleReason.INSUFFICIENT_QUOTA,
                )
            )
        # dedup merge restamps: the merged record re-ships
        leader.record(
            DecisionRecord(
                workload="ns/w-0", cluster_queue="cq", cycle=9,
                outcome="Pending",
                reason=InadmissibleReason.INSUFFICIENT_QUOTA,
            )
        )
        delta = leader.since(0)
        assert [d["seq"] for d in delta] == sorted(d["seq"] for d in delta)
        for item in delta:
            replica.ingest(item)
        assert replica.seq == leader.seq
        assert len(replica.for_workload("ns/w-0")) == 1
        assert replica.for_workload("ns/w-0")[0].count == 2
        # incremental: nothing new -> empty delta; fast path == cold
        assert leader.since(leader.seq) == []
        cold = sorted(
            (r.seq for ring in leader._records.values() for r in ring)
        )
        fast = [d["seq"] for d in leader.since(0)]
        assert fast == cold


# ---- HTTP replica serving (the --replica-of surface) ----
def _wl_wire(name, cpu="1000m"):
    return {
        "namespace": "ns", "name": name, "queueName": "lq-0",
        "podSets": [{"name": "main", "count": 1,
                     "requests": {"cpu": cpu}}],
    }


@pytest.fixture()
def http_pair(tmp_path):
    """A live journaled leader server + an attached HTTP read replica
    server (tail driven MANUALLY via pair.sync() — no background
    thread, so tests are deterministic)."""
    from kueue_tpu.replica import ReadReplica
    from kueue_tpu.server import KueueServer
    from kueue_tpu.server.client import KueueClient

    class Pair:
        def __init__(self):
            self.rt = fresh_rt()
            self.journal = Journal(
                str(tmp_path / "journal"), segment_max_bytes=100 << 10
            ).open()
            self.rt.attach_journal(self.journal)
            self.srv = KueueServer(runtime=self.rt)
            port = self.srv.start()
            self.leader_url = f"http://127.0.0.1:{port}"
            self.leader = KueueClient(self.leader_url)
            self.rep = ReadReplica(
                self.leader_url, replica_id="t-rep",
                build_runtime=fresh_rt,
            )
            self.rsrv = KueueServer(replica=self.rep)
            rport = self.rsrv.start()
            self.replica_url = f"http://127.0.0.1:{rport}"
            self.replica = KueueClient(self.replica_url)
            self.leader.apply("resourceflavors", {"name": "default"})
            self.leader.apply("clusterqueues", cq_dict("cq-0"))
            self.leader.apply(
                "localqueues",
                {"namespace": "ns", "name": "lq-0", "clusterQueue": "cq-0"},
            )
            self.rep.sync(resync=True)

        def sync(self):
            return self.rep.sync()

        def close(self):
            self.rsrv.stop()
            self.srv.stop()
            self.journal.close()

    pair = Pair()
    yield pair
    pair.close()


class TestHTTPReplica:
    def test_reads_follow_leader_and_converge_byte_identical(self, http_pair):
        p = http_pair
        for i in range(5):
            p.leader.apply("workloads", _wl_wire(f"wl-{i}"))
        p.sync()
        # visibility + state served from replayed state
        pending = p.replica.pending_workloads_cq("cq-0")["items"]
        assert [i["name"] for i in pending] == ["wl-4"]
        assert p.replica.served_by_replica
        assert p.replica.last_replica_lag_s is not None
        # the quiescence acceptance check: BYTE-identical state dumps
        assert json.dumps(p.leader.state(), sort_keys=True) == json.dumps(
            p.replica.state(), sort_keys=True
        )

    def test_watch_resource_version_contract_across_the_wire(self, http_pair):
        p = http_pair
        p.leader.apply("workloads", _wl_wire("wl-0"))
        p.sync()
        leader_events = p.leader.events()
        replica_events = p.replica.events()
        assert (
            replica_events["resourceVersion"]
            == leader_events["resourceVersion"]
        )
        assert [
            (e["resourceVersion"], e["reason"], e["object"])
            for e in replica_events["items"]
        ] == [
            (e["resourceVersion"], e["reason"], e["object"])
            for e in leader_events["items"]
        ]
        # a resume cursor taken on the LEADER works on the REPLICA:
        # long-poll returns exactly the events past the cursor
        cursor = leader_events["items"][0]["resourceVersion"]
        out = p.replica._request(
            "GET",
            "/apis/kueue/v1beta1/events?watch=1"
            f"&resourceVersion={cursor}&timeoutSeconds=2",
        )
        assert out["items"]
        assert all(
            e["resourceVersion"] > cursor for e in out["items"]
        )

    def test_explain_and_plan_served_from_replica(self, http_pair):
        p = http_pair
        p.leader.apply("workloads", _wl_wire("wl-big", cpu="8000m"))
        p.sync()
        rows = p.replica.workload_decisions("ns", "wl-big")["items"]
        assert rows and rows[-1]["reason"] == "RequestExceedsMaxCapacity"
        assert rows == p.leader.workload_decisions("ns", "wl-big")["items"]
        # plan is best-effort-stale but SERVED (leader-only pre-replica)
        report = p.replica.plan(workload="ns/wl-big")
        assert report["scenarios"]
        assert p.replica.served_by_replica

    def test_writes_redirect_and_client_follows(self, http_pair):
        p = http_pair
        out = p.replica.apply("workloads", _wl_wire("wl-via-replica"))
        assert out["applied"]["name"] == "wl-via-replica"
        assert p.replica.last_redirected_to.startswith(p.leader_url)
        p.sync()
        assert "ns/wl-via-replica" in [
            f"{w['namespace']}/{w['name']}"
            for w in p.replica.list("workloads")
        ]
        # delete + reconcile redirect too
        p.replica.delete_workload("ns", "wl-via-replica")
        p.replica.reconcile()
        p.sync()
        assert "wl-via-replica" not in [
            w["name"] for w in p.replica.list("workloads")
        ]

    def test_redirect_without_follow_is_307_with_location(self, http_pair):
        import urllib.request

        p = http_pair
        req = urllib.request.Request(
            f"{p.replica_url}/reconcile", data=b"{}", method="POST",
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            raise AssertionError("expected a 307")
        except urllib.error.HTTPError as e:
            assert e.code == 307
            assert e.headers["Location"] == f"{p.leader_url}/reconcile"

    def test_roster_health_metrics_and_dump(self, http_pair):
        from kueue_tpu.debugger import dump

        p = http_pair
        p.leader.apply("workloads", _wl_wire("wl-0"))
        p.sync()
        # the roster holds the appliedSeq AS OF each poll request (the
        # replica reports its pre-poll position); after a caught-up
        # second poll the leader sees it fully current
        p.sync()
        roster = p.leader.replicas()
        assert roster["role"] == "leader"
        assert [r["id"] for r in roster["items"]] == ["t-rep"]
        assert roster["items"][0]["behind"] == 0
        mine = p.replica.replicas()
        assert mine["role"] == "replica"
        assert mine["items"][0]["appliedSeq"] == p.journal.last_seq
        health = p.replica.healthz()
        assert health["replication"]["role"] == "replica"
        assert health["replication"]["appliedSeq"] == p.journal.last_seq
        assert health["status"] == "ok"
        metrics = p.replica.metrics_text()
        assert (
            f"kueue_replica_applied_seq {p.journal.last_seq}" in metrics
        )
        assert "kueue_replica_lag_seconds" in metrics
        assert "kueue_replica_resyncs_total" in metrics
        # leader metrics materialize the same series at zero
        assert "kueue_replica_applied_seq 0" in p.leader.metrics_text()
        # dashboard + SIGUSR2 replication sections
        board = p.replica.dashboard()
        assert board["replication"]["role"] == "replica"
        text = dump(p.rep.runtime)
        assert "-- replication (journal-tailing read replicas) --" in text
        assert "role=replica" in text

    def test_tail_during_compaction_over_http(self, http_pair):
        p = http_pair
        for i in range(6):
            p.leader.apply("workloads", _wl_wire(f"wl-{i}"))
        # leader compacts everything (the checkpoint IS /state here):
        # the replica's resume prefix is gone mid-tail
        p.journal.sync()
        p.journal.compact(p.journal.last_seq)
        res = p.sync()
        assert res.resynced
        assert p.rep.tailer.resyncs >= 1  # initial anchor + this one
        assert json.dumps(p.leader.state(), sort_keys=True) == json.dumps(
            p.replica.state(), sort_keys=True
        )
        # and incremental tailing resumes afterwards
        p.leader.apply("workloads", _wl_wire("wl-post"))
        res = p.sync()
        assert res.applied > 0 and not res.resynced

    def test_sse_stream_serves_mirrored_events(self, http_pair):
        p = http_pair
        p.leader.apply("workloads", _wl_wire("wl-0"))
        p.sync()
        got = []
        gen = p.replica.stream_events(resource_version=0)

        def pull():
            for ev in gen:
                got.append(ev)
                if len(got) >= 2:
                    return

        t = threading.Thread(target=pull, daemon=True)
        t.start()
        t.join(timeout=10)
        assert len(got) >= 2
        assert all(ev["resourceVersion"] > 0 for ev in got)


# ---- serve plumbing (unit level) ----
class TestServeBenchPlumbing:
    def test_http_source_against_live_leader(self, http_pair):
        p = http_pair
        tailer = JournalTailer(
            HTTPTailSource(p.leader_url, replica_id="unit-src"),
            build_runtime=fresh_rt,
        )
        p.leader.apply("workloads", _wl_wire("wl-0"))
        res = tailer.poll_once()
        assert res.applied > 0
        assert state_of(tailer.runtime) == state_of(p.rt)

    def test_http_source_unreachable_is_contained(self):
        tailer = JournalTailer(
            HTTPTailSource("http://127.0.0.1:1", timeout=0.5),
            build_runtime=fresh_rt,
        )
        res = tailer.poll_once()
        assert res.error and tailer.last_error
        with pytest.raises(TailSourceError):
            tailer.source.checkpoint()


class TestTailerClockAndLocking:
    """kueuelint satellites: LocalTailSource stamps leader_time through
    its injected ``now_fn``, and the tailer's cursors/accounting are
    written under ``lock`` so a status() racing a poll never tears."""

    def test_local_source_leader_time_is_injected(self, tmp_path):
        rt, journal = leader_with_journal(tmp_path)
        submit(rt, "wl-0")
        clock = FakeClock(500.0)
        src = LocalTailSource(
            str(tmp_path / "journal"), now_fn=clock.now
        )
        batch = src.fetch(0)
        assert batch.leader_time == 500.0
        clock.advance(7.0)
        assert src.fetch(batch.last_seq).leader_time == 507.0
        journal.close()

    def test_status_is_consistent_under_concurrent_polls(self, tmp_path):
        """Hammer poll_once from one thread while reading status from
        another: every snapshot must be internally consistent (cursor
        never behind recordsApplied progress seen earlier)."""
        rt, journal = leader_with_journal(tmp_path)
        tailer = local_tailer(tmp_path)
        errors = []
        stop = threading.Event()

        def reader():
            last_applied = -1
            while not stop.is_set():
                st = tailer.status()
                if st["appliedSeq"] < last_applied:
                    errors.append(
                        f"appliedSeq regressed: {st['appliedSeq']} < "
                        f"{last_applied}"
                    )
                last_applied = st["appliedSeq"]

        t = threading.Thread(target=reader)
        t.start()
        try:
            for i in range(20):
                submit(rt, f"wl-{i}")
                tailer.poll_once()
        finally:
            stop.set()
            t.join()
        assert not errors, errors
        assert tailer.status()["appliedSeq"] == journal.last_seq
        journal.close()


class TestServerClockInjection:
    """kueuelint clock-discipline satellite: the serving surface's
    timestamps (feed leaderTime, roster staleness) come from the
    runtime's injected clock, so a FakeClock pins them."""

    def test_feed_leader_time_and_roster_staleness_use_runtime_clock(
        self, tmp_path
    ):
        from kueue_tpu.server import KueueServer
        from kueue_tpu.server.client import KueueClient

        rt, journal = leader_with_journal(tmp_path)
        rt.clock.set(1000.0)
        srv = KueueServer(runtime=rt)
        assert srv.clock is rt.clock
        port = srv.start()
        try:
            client = KueueClient(f"http://127.0.0.1:{port}")
            out = client.journal_tail(
                since_seq=0, replica="rep-a", applied_seq=0, lag_s=0.0
            )
            assert out["leaderTime"] == 1000.0
            rt.clock.advance(12.0)
            roster = client.replicas()
            item = [i for i in roster["items"] if i["id"] == "rep-a"][0]
            assert item["lastSeenAgoS"] == 12.0
        finally:
            srv.stop()
            journal.close()
