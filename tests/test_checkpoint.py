"""Delta-checkpoint chain tests (storage/checkpoint.py): O(changed)
incremental checkpoints chained off periodic full anchors, byte-
identical chain recovery, bounded chain GC + journal compaction, and
resource-exhaustion (ENOSPC) degradation that leaves the previous
chain valid and self-heals.

The byte-identity contract under test: merging the anchor + delta
chain reproduces EXACTLY the JSON a full ``runtime_to_state`` dump of
the live leader would serialize — same objects, same insertion order,
same bytes — so every consumer of checkpoint files (recovery, standby
promote-reload, replica re-anchor, ``kueuectl state verify``) is
agnostic to which checkpoint mode produced them.
"""

import dataclasses
import json
import os

import pytest

from kueue_tpu import serialization as ser
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
    DeltaTracker,
    Journal,
    load_checkpoint_chain,
    load_state_any,
    recover,
    verify_checkpoint_chain,
)
from kueue_tpu.testing import faults
from kueue_tpu.utils.clock import FakeClock


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def fresh_rt(clock_start=0.0):
    return ClusterRuntime(
        clock=FakeClock(clock_start), use_solver=False,
        bulk_drain_threshold=None,
    )


def make_wl(name, cq_index=0, prio=0, t=0.0):
    return Workload(
        namespace="ns", name=name, queue_name=f"lq-cq-{cq_index}",
        priority=prio, creation_time=t,
        pod_sets=(PodSet.build("main", 1, {"cpu": "1"}),),
    )


def chain_rt(tmp_path, n_cq=3, n_wl=12, anchor_every=4, retain_chains=1):
    """Runtime + journal + DeltaCheckpointer over a seeded config."""
    rt = fresh_rt()
    journal = Journal(str(tmp_path / "journal")).open()
    rt.attach_journal(journal)
    rt.add_flavor(ResourceFlavor(name="default"))
    for i in range(n_cq):
        name = f"cq-{i}"
        rt.add_cluster_queue(
            ClusterQueue(
                name=name, namespace_selector={},
                resource_groups=(
                    ResourceGroup(
                        ("cpu",),
                        (FlavorQuotas.build("default", {"cpu": "8"}),),
                    ),
                ),
            )
        )
        rt.add_local_queue(
            LocalQueue(namespace="ns", name=f"lq-{name}", cluster_queue=name)
        )
    for k in range(n_wl):
        rt.add_workload(make_wl(f"wl-{k}", k % n_cq, t=float(k)))
    state_dir = str(tmp_path / "state")
    os.makedirs(state_dir, exist_ok=True)
    ckpt = DeltaCheckpointer(
        state_dir, anchor_every=anchor_every, retain_chains=retain_chains
    ).open()
    rt.checkpointer = ckpt
    return rt, journal, ckpt, state_dir


def assert_chain_matches_live(rt, state_dir):
    """THE acceptance assertion: chain-merged state == full dump,
    byte for byte (journalSeq/token are persistence bookkeeping the
    full dump does not carry — aligned before comparing)."""
    chain, info = load_checkpoint_chain(state_dir)
    assert info.ok, info.errors
    full = ser.runtime_to_state(rt)
    full["persistence"]["journalSeq"] = chain["persistence"]["journalSeq"]
    full["persistence"]["token"] = chain["persistence"]["token"]
    assert json.dumps(chain, sort_keys=False) == json.dumps(
        full, sort_keys=False
    )
    return chain, info


def churn_round(rt, r):
    """One deterministic round of update + delete + add + re-add."""
    for k in range(3):
        wl = rt.workloads.get(f"ns/wl-{(r * 3 + k) % 12}")
        if wl is not None:
            rt.add_workload(dataclasses.replace(wl, priority=10 + r))
    wl = rt.workloads.get(f"ns/wl-{(r * 2 + 5) % 12}")
    if wl is not None:
        rt.delete_workload(wl)
    rt.add_workload(make_wl(f"new-{r}", r % 3, t=100.0 + r))
    # delete + re-add in the same window: the merge's append-at-end
    # order contract (dict delete/re-add moves the key to the end)
    wl = rt.workloads.get("ns/wl-1")
    if wl is not None:
        rt.delete_workload(wl)
        rt.add_workload(dataclasses.replace(wl, priority=99))
    rt.run_until_idle()


class TestDeltaTracker:
    def test_born_full_dirty(self):
        t = DeltaTracker()
        assert not t.clean()
        cs = t.snapshot()
        assert cs.need_full

    def test_marks_and_tombstones(self):
        t = DeltaTracker()
        t.clear(t.snapshot(), full=True)  # discharge the birth full
        t.note("workload_upsert", {"namespace": "ns", "name": "a"})
        t.note("workload_delete", {"key": "ns/b"})
        cs = t.snapshot()
        assert not cs.need_full
        assert cs.changed == {"workloads": ["ns/a"]}
        assert cs.removed == {"workloads": ["ns/b"]}

    def test_delete_pops_pending_change(self):
        t = DeltaTracker()
        t.clear(t.snapshot(), full=True)
        t.note("workload_upsert", {"namespace": "ns", "name": "a"})
        t.note("workload_delete", {"key": "ns/a"})
        cs = t.snapshot()
        assert cs.changed == {}
        assert cs.removed == {"workloads": ["ns/a"]}

    def test_generation_bounded_clear(self):
        """Marks noted AFTER a snapshot survive that snapshot's clear —
        the concurrent periodic + shutdown checkpoint race."""
        t = DeltaTracker()
        t.clear(t.snapshot(), full=True)
        t.note("workload_upsert", {"namespace": "ns", "name": "a"})
        cs = t.snapshot()
        t.note("workload_upsert", {"namespace": "ns", "name": "b"})
        t.clear(cs, full=False)
        assert not t.clean()
        cs2 = t.snapshot()
        assert cs2.changed == {"workloads": ["ns/b"]}

    def test_unknown_vocabulary_forces_full(self):
        t = DeltaTracker()
        t.clear(t.snapshot(), full=True)
        t.note("some_future_record_kind", {})
        assert t.snapshot().need_full

    def test_non_state_kinds_are_ignored(self):
        t = DeltaTracker()
        t.clear(t.snapshot(), full=True)
        t.note("solver_verdict", {"key": "x"})
        t.note("checkpoint_anchor", {"name": "anchor-0.ckpt"})
        t.note("checkpoint_delta", {"name": "delta-0-1.ckpt"})
        assert t.clean()


class TestDeltaChain:
    def test_first_checkpoint_is_full_anchor(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        assert ckpt.last_kind == "full"
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    def test_deltas_byte_identical_across_churn(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path, anchor_every=8)
        assert ckpt.checkpoint(rt)
        for r in range(5):
            churn_round(rt, r)
            assert ckpt.checkpoint(rt)
            assert ckpt.last_kind == "delta"
            assert_chain_matches_live(rt, state_dir)
        journal.close()

    @pytest.mark.parametrize("n_live", [60, 500, 2_500])
    def test_delta_serializes_only_changed(self, tmp_path, n_live):
        """O(changed): the same 32 touched workloads at 60, 500 and
        2,500 live — the delta carries 32 objects at every size, not
        the live count."""
        touched = 32
        rt, journal, ckpt, state_dir = chain_rt(tmp_path, n_wl=n_live)
        assert ckpt.checkpoint(rt)
        for k in range(touched):
            wl = rt.workloads[f"ns/wl-{k}"]
            rt.add_workload(dataclasses.replace(wl, priority=7))
        assert ckpt.checkpoint(rt)
        assert ckpt.last_kind == "delta"
        assert ckpt.last_objects == touched
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    def test_anchor_cadence_rolls_to_full(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path, anchor_every=3)
        assert ckpt.checkpoint(rt)
        kinds = []
        for r in range(7):
            churn_round(rt, r)
            assert ckpt.checkpoint(rt)
            kinds.append(ckpt.last_kind)
        # 3 deltas, then the cadence forces a fresh anchor
        assert kinds[:4] == ["delta", "delta", "delta", "full"]
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    def test_clean_tracker_is_a_noop(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        files_before = sorted(os.listdir(state_dir))
        assert ckpt.checkpoint(rt)  # nothing changed since
        assert sorted(os.listdir(state_dir)) == files_before
        journal.close()

    def test_chain_gc_bounds_files_and_compacts_journal(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path, anchor_every=4)
        assert ckpt.checkpoint(rt)
        for r in range(20):
            churn_round(rt, r)
            assert ckpt.checkpoint(rt)
        files = sorted(os.listdir(state_dir))
        # retain_chains=1: one active anchor + at most anchor_every
        # deltas; superseded chains are deleted
        anchors = [f for f in files if f.startswith("anchor-")]
        assert len(anchors) == 1
        assert len(files) <= 1 + 4
        # checkpoint-driven compaction: sealed covered segments gone,
        # reclaimed bytes accounted (the retention metric)
        st = journal.stats()
        assert st.segments <= 2
        assert st.reclaimed_bytes > 0
        assert rt.metrics.journal_reclaimed_bytes_total.value() == float(
            st.reclaimed_bytes
        )
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    def test_checkpoint_metrics_materialized(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        ckpt.metrics = rt.metrics
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        assert ckpt.checkpoint(rt)
        m = rt.metrics
        assert m.checkpoints_total.value(kind="full") == 1
        assert m.checkpoints_total.value(kind="delta") == 1
        assert m.checkpoint_bytes_total.value(kind="delta") > 0
        assert m.checkpoint_degraded.value() == 0
        assert m.checkpoint_chain_files.value() == 2
        journal.close()

    def test_journal_less_runtime_always_anchors(self, tmp_path):
        """No journal = no replayable suffix to chain deltas over: the
        checkpointer must refuse to emit a delta."""
        rt = fresh_rt()
        rt.add_flavor(ResourceFlavor(name="default"))
        state_dir = str(tmp_path / "state")
        os.makedirs(state_dir)
        ckpt = DeltaCheckpointer(state_dir, anchor_every=8).open()
        assert ckpt.checkpoint(rt)
        assert ckpt.last_kind == "full"
        rt.add_flavor(ResourceFlavor(name="other"))
        assert ckpt.checkpoint(rt)
        assert ckpt.last_kind == "full"


class TestChainRecovery:
    def test_recover_replays_chain_plus_journal_suffix(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        rt.run_until_idle()
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        assert ckpt.checkpoint(rt)
        # a journal suffix NEWER than the chain head (no checkpoint)
        rt.add_workload(make_wl("tail-0", 0, t=200.0))
        rt.run_until_idle()
        live_admitted = {
            k for k, wl in rt.workloads.items() if wl.is_admitted
        }
        live_keys = set(rt.workloads)
        journal.close()

        res = recover(
            state_dir, str(tmp_path / "journal"), runtime=fresh_rt()
        )
        assert res.checkpoint_loaded
        assert res.replayed > 0  # the suffix
        rt2 = res.runtime
        assert set(rt2.workloads) == live_keys
        assert {
            k for k, wl in rt2.workloads.items() if wl.is_admitted
        } == live_admitted
        assert rt2.check_invariants() == []
        res.journal.close()

    def test_resumed_checkpointer_anchors_then_chains(self, tmp_path):
        """A restarted leader lost its in-memory dirty-set, so its
        first checkpoint MUST be a fresh full anchor (the tracker is
        born full-dirty by design); subsequent checkpoints chain
        deltas off that new anchor."""
        rt, journal, ckpt, state_dir = chain_rt(tmp_path, anchor_every=8)
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        assert ckpt.checkpoint(rt)
        journal.close()

        res = recover(
            state_dir, str(tmp_path / "journal"), runtime=fresh_rt()
        )
        rt2 = res.runtime
        rt2.attach_journal(res.journal)
        ckpt2 = DeltaCheckpointer(state_dir, anchor_every=8).open()
        rt2.checkpointer = ckpt2
        churn_round(rt2, 1)
        assert ckpt2.checkpoint(rt2)
        assert ckpt2.last_kind == "full"
        churn_round(rt2, 2)
        assert ckpt2.checkpoint(rt2)
        assert ckpt2.last_kind == "delta"
        assert_chain_matches_live(rt2, state_dir)
        res.journal.close()

    def test_broken_link_keeps_valid_prefix(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path, anchor_every=8)
        assert ckpt.checkpoint(rt)
        for r in range(3):
            churn_round(rt, r)
            assert ckpt.checkpoint(rt)
        files = sorted(os.listdir(state_dir))
        deltas = [f for f in files if f.startswith("delta-")]
        assert len(deltas) == 3
        # corrupt the MIDDLE delta: the chain is valid up to it
        with open(os.path.join(state_dir, deltas[1]), "w") as f:
            f.write("{ torn")
        info = verify_checkpoint_chain(state_dir)
        assert not info.ok
        assert info.errors
        state, info2 = load_checkpoint_chain(state_dir)
        assert state is not None  # anchor + first delta still load
        assert info2.files == [f for f in files if f not in deltas[1:]]
        journal.close()

    def test_load_state_any_handles_both_shapes(self, tmp_path):
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        from_chain = load_state_any(state_dir)
        assert from_chain is not None
        flat = str(tmp_path / "state.json")
        with open(flat, "w") as f:
            json.dump(from_chain, f)
        assert load_state_any(flat) == from_chain
        assert load_state_any(str(tmp_path / "missing")) is None
        journal.close()


class TestResourceExhaustion:
    def test_enospc_delta_write_degrades_chain_stays_valid(self, tmp_path):
        """ENOSPC mid-chain-write: the failed checkpoint reports
        False, flips degraded, leaves NO torn file, and the previous
        chain recovers byte-identically; the next successful
        checkpoint self-heals (nothing dirtied was lost)."""
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        ckpt.metrics = rt.metrics
        assert ckpt.checkpoint(rt)
        pre_files = sorted(os.listdir(state_dir))
        pre_chain, _ = load_checkpoint_chain(state_dir)

        churn_round(rt, 0)
        faults.arm("checkpoint.delta_write", faults.make_failing_fsync())
        assert not ckpt.checkpoint(rt)
        assert ckpt.degraded
        assert "No space left" in ckpt.last_error
        assert rt.metrics.checkpoint_degraded.value() == 1
        assert rt.metrics.checkpoints_total.value(kind="failed") == 1
        # no torn tmp file, previous chain untouched and green
        assert sorted(os.listdir(state_dir)) == pre_files
        info = verify_checkpoint_chain(state_dir)
        assert info.ok
        assert load_checkpoint_chain(state_dir)[0] == pre_chain

        # the volume recovers: the SAME dirt lands in the next delta
        faults.reset()
        assert ckpt.checkpoint(rt)
        assert not ckpt.degraded
        assert rt.metrics.checkpoint_degraded.value() == 0
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    def test_enospc_segment_rotation_degrades_journal(self, tmp_path):
        """ENOSPC creating the rotated segment: the append that
        triggered rotation degrades the journal instead of raising,
        and appends keep landing once the volume recovers."""
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        faults.arm("journal.rotate", faults.make_failing_fsync())
        journal.segment_max_bytes = 1  # force rotation on next append
        rt.add_workload(make_wl("rot-0", 0, t=50.0))
        assert journal.degraded
        faults.reset()
        rt.add_workload(make_wl("rot-1", 0, t=51.0))
        assert not journal.degraded
        journal.close()

    def test_enospc_rotation_does_not_fail_the_checkpoint(self, tmp_path):
        """compact()'s rotation hitting ENOSPC must not fail the
        checkpoint that triggered it — the chain file already landed."""
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        faults.arm("journal.rotate", faults.make_failing_fsync())
        assert ckpt.checkpoint(rt)  # checkpoint still succeeds
        assert not ckpt.degraded
        assert journal.degraded  # the rotation failure is the journal's
        faults.reset()
        rt.add_workload(make_wl("after", 0, t=60.0))
        assert not journal.degraded
        rt.run_until_idle()
        assert ckpt.checkpoint(rt)
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    @pytest.mark.parametrize("fault_point", [
        "checkpoint.delta_write", "journal.rotate",
    ])
    @pytest.mark.parametrize("occurrence", [0, 1, 2])
    def test_crash_sweep_chain_recovers_byte_identical(
        self, tmp_path, fault_point, occurrence
    ):
        """Hard crash (InjectedCrash, simulated process death) at each
        registered occurrence of each new fault point: recovery from
        the surviving chain + journal must reproduce the live state,
        and the chain must verify green."""
        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        faults.arm(fault_point, "crash", skip=occurrence)
        crashed = False
        for r in range(6):
            churn_round(rt, r)
            try:
                ckpt.checkpoint(rt)
            except faults.InjectedCrash:
                crashed = True
                break
        if not crashed:
            pytest.skip(
                f"{fault_point} occurrence {occurrence} not reached"
            )
        faults.reset()
        live_keys = set(rt.workloads)
        live_admitted = {
            k for k, wl in rt.workloads.items() if wl.is_admitted
        }
        journal.close()
        # the dead process's chain verifies green (a crash mid-write
        # leaves no torn chain file: unique tmp + os.replace); a crash
        # before the FIRST anchor leaves no chain at all and recovery
        # is journal-only
        info = verify_checkpoint_chain(state_dir)
        if info.files:
            assert info.ok, info.errors
        else:
            assert not info.errors
        res = recover(
            state_dir, str(tmp_path / "journal"), runtime=fresh_rt()
        )
        rt2 = res.runtime
        assert set(rt2.workloads) == live_keys
        assert {
            k for k, wl in rt2.workloads.items() if wl.is_admitted
        } == live_admitted
        assert rt2.check_invariants() == []
        res.journal.close()


class TestStateVerifyCLI:
    def test_verify_green_on_chain_dir(self, tmp_path, capsys):
        from kueue_tpu.cli.__main__ import main

        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        assert ckpt.checkpoint(rt)
        journal.close()
        rc = main([
            "--state", state_dir, "state", "verify",
            "--journal", str(tmp_path / "journal"),
        ])
        assert not rc
        out = capsys.readouterr().out
        assert "anchor" in out and "delta" in out
        assert "verify: OK" in out

    def test_verify_fails_on_torn_chain_file(self, tmp_path, capsys):
        from kueue_tpu.cli.__main__ import main

        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        assert ckpt.checkpoint(rt)
        journal.close()
        delta = [
            f for f in os.listdir(state_dir) if f.startswith("delta-")
        ][0]
        with open(os.path.join(state_dir, delta), "w") as f:
            f.write("{ torn")
        with pytest.raises(SystemExit) as ei:
            main([
                "--state", state_dir, "state", "verify",
                "--journal", str(tmp_path / "journal"),
            ])
        assert ei.value.code == 2

    def test_state_replay_materializes_from_chain(self, tmp_path, capsys):
        from kueue_tpu.cli.__main__ import main

        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        assert ckpt.checkpoint(rt)
        live_keys = set(rt.workloads)
        journal.close()
        out_file = str(tmp_path / "replayed.json")
        rc = main([
            "--state", state_dir, "state", "replay",
            "--journal", str(tmp_path / "journal"), "-o", out_file,
        ])
        assert not rc
        with open(out_file) as f:
            state = json.load(f)
        keys = {
            f"{w['namespace']}/{w['name']}" for w in state["workloads"]
        }
        assert keys == live_keys


class TestHealthzCheckpointPosture:
    def test_degraded_checkpoint_flips_healthz(self, tmp_path):
        import urllib.request

        from kueue_tpu.server import KueueServer

        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        assert ckpt.checkpoint(rt)
        srv = KueueServer(runtime=rt)
        port = srv.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz"
            ) as r:
                body = json.loads(r.read())
            assert body["status"] == "ok"
            detail = body["persistence"]["checkpoint"]
            assert detail["mode"] == "delta"
            assert not detail["degraded"]

            churn_round(rt, 0)
            faults.arm(
                "checkpoint.delta_write", faults.make_failing_fsync()
            )
            assert not ckpt.checkpoint(rt)
            # degraded but LIVE: the probe stays 200 (the previous
            # chain is valid; paging comes from the posture fields)
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz"
            ) as r:
                body = json.loads(r.read())
            assert body["status"] == "degraded"
            assert body["persistence"]["checkpoint"]["degraded"]
            assert body["persistence"]["checkpoint"]["lastError"]

            faults.reset()
            assert ckpt.checkpoint(rt)
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz"
            ) as r:
                body = json.loads(r.read())
            assert body["status"] == "ok"
        finally:
            srv.stop()
            journal.close()


class TestFencedDeltaCheckpoint:
    def test_serialize_under_lock_commit_outside(self, tmp_path):
        from kueue_tpu.server import KueueServer
        from kueue_tpu.server.__main__ import fenced_delta_checkpoint

        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        srv = KueueServer(runtime=rt)
        assert fenced_delta_checkpoint(srv)
        churn_round(rt, 0)
        assert fenced_delta_checkpoint(srv)
        assert ckpt.last_kind == "delta"
        assert_chain_matches_live(rt, state_dir)
        journal.close()

    def test_stale_prepare_abandoned(self, tmp_path):
        """Two overlapping prepares: the one sequenced LATER wins; the
        stale one must not clobber the newer chain head, and its marks
        survive for the next round (abandon is mark-preserving)."""
        from kueue_tpu.server import KueueServer

        rt, journal, ckpt, state_dir = chain_rt(tmp_path)
        srv = KueueServer(runtime=rt)
        assert ckpt.checkpoint(rt)
        churn_round(rt, 0)
        with srv.lock:
            prep_old = ckpt.prepare(rt)
        churn_round(rt, 1)
        with srv.lock:
            prep_new = ckpt.prepare(rt)
        assert ckpt.commit(prep_new)
        head_after = ckpt.status()["headJournalSeq"]
        ckpt.abandon(prep_old)
        assert ckpt.status()["headJournalSeq"] == head_after
        assert_chain_matches_live(rt, state_dir)
        journal.close()
