"""Bounded-retention regression tests: every in-memory ring, cursor
and on-disk structure the state plane grows under sustained
arrival + completion churn must hold its configured bound — RSS and
journal size flat at steady state is the million-workload operating
contract (sustained operation, not just a burst).

Plus a fixed run of churn through the whole durable stack (gateway
ingest, delta checkpoints, journal compaction, a shared-volume
replica), counted in operations on a FakeClock, not in wall time.
"""

import os

import numpy as np
import pytest

from kueue_tpu.controllers import ClusterRuntime
from kueue_tpu.models import (
    ClusterQueue,
    LocalQueue,
    ResourceFlavor,
    Workload,
)
from kueue_tpu.models.cluster_queue import FlavorQuotas, ResourceGroup
from kueue_tpu.models.workload import PodSet
from kueue_tpu.storage import (
    DeltaCheckpointer,
    Journal,
    JournalTailer,
    LocalTailSource,
)
from kueue_tpu.testing import faults
from kueue_tpu.utils.clock import FakeClock


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def churn_rt(tmp_path, segment_max_bytes=64 * 1024):
    clock = FakeClock(0.0)
    rt = ClusterRuntime(
        clock=clock, use_solver=False, bulk_drain_threshold=None
    )
    journal = Journal(
        str(tmp_path / "journal"),
        fsync_policy="interval",
        segment_max_bytes=segment_max_bytes,
        clock=clock,
    ).open()
    rt.attach_journal(journal)
    rt.add_flavor(ResourceFlavor(name="default"))
    rt.add_cluster_queue(
        ClusterQueue(
            name="cq", namespace_selector={},
            resource_groups=(
                ResourceGroup(
                    ("cpu",),
                    (FlavorQuotas.build("default", {"cpu": "64"}),),
                ),
            ),
        )
    )
    rt.add_local_queue(
        LocalQueue(namespace="churn", name="lq-cq", cluster_queue="cq")
    )
    return rt, journal, clock


def make_wl(k, t):
    return Workload(
        namespace="churn", name=f"wl-{k}", queue_name="lq-cq",
        creation_time=t,
        pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
    )


CHURN_N = 10_000
BATCH = 500


class TestRingBoundsUnderChurn:
    def test_rings_and_cursors_hold_bounds_at_10k_churn(self, tmp_path):
        """10k workloads arrive, admit and complete; every ring must
        end bounded and the live set empty — nothing retains
        per-workload state for completed work."""
        rt, journal, clock = churn_rt(tmp_path)
        state_dir = str(tmp_path / "state")
        os.makedirs(state_dir)
        ckpt = DeltaCheckpointer(state_dir, anchor_every=8).open()
        rt.checkpointer = ckpt
        tailer = JournalTailer(
            LocalTailSource(
                str(tmp_path / "journal"), state_path=state_dir,
                now_fn=clock.now,
            ),
            now_fn=clock.now,
        )
        tailer.ensure_runtime()

        for start in range(0, CHURN_N, BATCH):
            for k in range(start, start + BATCH):
                rt.add_workload(make_wl(k, float(k)))
            rt.run_until_idle()
            for k in range(start, start + BATCH):
                wl = rt.workloads.get(f"churn/wl-{k}")
                if wl is not None:
                    rt.delete_workload(wl)
            rt.run_until_idle()
            clock.advance(1.0)
            ckpt.checkpoint(rt)
            journal.sync()
            tailer.poll_once()

        assert not rt.workloads  # everything completed
        # event ring: newest ring_size only
        assert len(rt.events._ring) <= rt.events.ring_size
        # audit: per-workload rings LRU-capped across workloads
        assert len(rt.audit._records) <= rt.audit.max_workloads
        for ring in rt.audit._records.values():
            assert len(ring) <= rt.audit.per_workload
        assert (
            len(rt.audit._stamp_log) <= rt.audit._stamp_log.maxlen
        )
        # tracer: newest max_traces trace trees only
        assert len(rt.tracer._traces) <= rt.tracer.max_traces
        assert (
            len(rt.tracer._stamp_log) <= rt.tracer._stamp_log.maxlen
        )
        # replica ingest log bounded
        assert len(tailer.feed_log) <= tailer.feed_log_max
        # replica cursor caught up (not pinned behind compaction)
        assert tailer.applied_seq >= journal.last_seq
        assert set(tailer.runtime.workloads) == set(rt.workloads)
        journal.close()

    def test_journal_segments_bounded_by_checkpoint_compaction(
        self, tmp_path
    ):
        """Small segments + churn would grow the journal without
        bound; checkpoint-driven compaction must hold the segment
        count flat and account every reclaimed byte."""
        rt, journal, clock = churn_rt(tmp_path, segment_max_bytes=16 * 1024)
        state_dir = str(tmp_path / "state")
        os.makedirs(state_dir)
        ckpt = DeltaCheckpointer(state_dir, anchor_every=4).open()
        rt.checkpointer = ckpt

        peak_segments = 0
        for start in range(0, 4_000, BATCH):
            for k in range(start, start + BATCH):
                rt.add_workload(make_wl(k, float(k)))
            rt.run_until_idle()
            for k in range(start, start + BATCH):
                wl = rt.workloads.get(f"churn/wl-{k}")
                if wl is not None:
                    rt.delete_workload(wl)
            rt.run_until_idle()
            ckpt.checkpoint(rt)
            peak_segments = max(peak_segments, journal.stats().segments)

        st = journal.stats()
        # each round rotates several 16 KiB segments; without
        # compaction 4k add+delete rounds leave dozens on disk
        assert peak_segments <= 4
        assert st.segments <= 4
        assert st.reclaimed_bytes > 0
        assert rt.metrics.journal_reclaimed_bytes_total.value() == float(
            st.reclaimed_bytes
        )
        # disk usage itself is bounded, not just the count
        jdir = str(tmp_path / "journal")
        on_disk = sum(
            os.path.getsize(os.path.join(jdir, f))
            for f in os.listdir(jdir)
        )
        assert on_disk <= 4 * 16 * 1024 + 64 * 1024
        journal.close()

    def test_gateway_shed_keeps_queue_bounded(self, tmp_path):
        """A stalled flusher must not let the ingest queue grow
        unboundedly — the gateway sheds at max_queue and the tenant
        fair-share cap."""
        from kueue_tpu.gateway import GatewayThrottled, WriteGateway

        rt, journal, clock = churn_rt(tmp_path)
        gw = WriteGateway(max_batch=64, max_queue=256, clock=clock)
        shed = 0
        for k in range(2_000):
            try:
                gw._enqueue(
                    "workloads",
                    {"namespace": "churn", "name": f"q-{k}",
                     "queueName": "lq-cq"},
                )
            except GatewayThrottled:
                shed += 1
        assert shed > 0
        assert gw.status()["queueDepth"] <= 256
        journal.close()


SOAK_TICKS = 400  # 40 s of simulated churn at 0.1 s a tick
SOAK_RATE = 15  # mean arrivals and completions a tick


class TestDurableChurn:
    def test_churn_replica_chain_and_slo_hold(self, tmp_path):
        """A fixed run of Poisson arrival and completion churn through
        the durable stack on a FakeClock: gateway ingest, the journal,
        delta checkpoints every 2 simulated seconds whose commits
        compact the journal, and a journal-tailing replica that
        re-anchors from the chain. The chain stays within one anchor
        plus its deltas at every checkpoint, the SLO plane is never
        degraded, and at the end the replica's state is the leader's."""
        from kueue_tpu import serialization as ser
        from kueue_tpu.gateway import WriteGateway
        from kueue_tpu.server import KueueServer

        rng = np.random.default_rng(7)
        clock = FakeClock(0.0)
        rt = ClusterRuntime(
            clock=clock, use_solver=False, bulk_drain_threshold=None
        )
        journal = Journal(
            str(tmp_path / "journal"), fsync_policy="interval",
            segment_max_bytes=256 * 1024, clock=clock,
        ).open()
        rt.attach_journal(journal)
        rt.add_flavor(ResourceFlavor(name="default"))
        n_cq = 4
        for i in range(n_cq):
            rt.add_cluster_queue(
                ClusterQueue(
                    name=f"scq-{i}", namespace_selector={},
                    resource_groups=(
                        ResourceGroup(
                            ("cpu",),
                            (FlavorQuotas.build("default", {"cpu": "128"}),),
                        ),
                    ),
                )
            )
            rt.add_local_queue(
                LocalQueue(
                    namespace="soak", name=f"lq-scq-{i}",
                    cluster_queue=f"scq-{i}",
                )
            )
        state_dir = tmp_path / "state"
        state_dir.mkdir()
        anchor_every = 8
        ckpt = DeltaCheckpointer(str(state_dir), anchor_every=anchor_every)
        rt.checkpointer = ckpt.open()
        rt.slo.configure(default_target_s=30.0)
        gateway = WriteGateway(max_batch=4096, max_queue=65536, clock=clock)
        # the server attaches itself as the gateway's sink
        KueueServer(runtime=rt, auto_reconcile=True, gateway=gateway)
        tailer = JournalTailer(
            LocalTailSource(
                str(tmp_path / "journal"), state_path=str(state_dir),
                now_fn=clock.now,
            ),
            now_fn=clock.now,
        )
        tailer.ensure_runtime()

        def assert_replica_is_leader():
            journal.sync()
            for _ in range(64):
                tailer.poll_once()
                if tailer.applied_seq >= journal.last_seq:
                    break
            with tailer.lock:
                replica = ser.runtime_to_state(tailer.runtime)
            leader = ser.runtime_to_state(rt)
            replica.pop("persistence")
            leader.pop("persistence")
            assert replica == leader

        arrived = completed = deltas = peak_chain = 0
        for tick in range(SOAK_TICKS):
            for _ in range(int(rng.poisson(SOAK_RATE))):
                gateway._enqueue(
                    "workloads",
                    ser.workload_to_dict(
                        Workload(
                            namespace="soak", name=f"swl-{arrived}",
                            queue_name=f"lq-scq-{arrived % n_cq}",
                            priority=int(rng.integers(0, 4)) * 10,
                            creation_time=clock.now(),
                            pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
                        )
                    ),
                )
                arrived += 1
            admitted = [wl for wl in rt.workloads.values() if wl.is_admitted]
            n_done = min(len(admitted), int(rng.poisson(SOAK_RATE)))
            for i in rng.permutation(len(admitted))[:n_done]:
                rt.delete_workload(admitted[int(i)])
                completed += 1
            clock.advance(0.1)
            gateway.flush_once()
            if tick % 20 == 19:
                # the replica has tailed 20 ticks since its last re-anchor
                assert_replica_is_leader()
                assert ckpt.checkpoint(rt)
                deltas += ckpt.last_kind == "delta"
                peak_chain = max(peak_chain, ckpt.status()["chainFiles"])
                rt.slo.refresh()
                assert not rt.slo.report()["degraded"], rt.slo.report()
            journal.sync()
            tailer.poll_once()

        gateway.flush_once()
        assert_replica_is_leader()
        assert arrived > 0 and completed > 0 and deltas > anchor_every
        # chain GC: one anchor and the deltas chained off it
        assert peak_chain <= 1 + anchor_every
        assert journal.stats().reclaimed_bytes > 0
        rt.slo.refresh()
        report = rt.slo.report()
        assert report["clusterQueues"] and not report["degraded"]
        journal.close()
