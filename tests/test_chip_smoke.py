"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The phases run their device paths on the CPU backend here, so these
check the smoke's plumbing and its drain-vs-host comparisons, not the
chip. ``main()`` itself must refuse the CPU.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

_SMOKE = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_plain_phase_matches_host_reference(smoke):
    report = smoke.run_plain_phase(seed=0, n_cq=20, n_cohort=5, wl_per_cq=20)
    assert report["matchesHostReference"]
    assert report["admitted"] > 0
    assert [r["solverPath"] for r in report["runs"]] == ["auto", "device"]
    for run in report["runs"]:
        assert run["admitted"] == report["admitted"]
        assert run["drainRounds"] >= 1
        assert run["firstRounds"][0]["heads"] == 400
        assert run["deviceSolves"] >= 1 and run["failovers"] == 0


def test_preempt_phase_matches_host_reference(smoke):
    # 30 reclaimers x 10 = 300 pending: above the bulk-drain threshold
    report = smoke.run_preempt_phase(seed=1, n_cq=60)
    assert report["matchesHostReference"]
    assert report["evicted"] > 0
    assert report["drainRounds"] >= 1


@pytest.mark.parametrize("fair", [False, True])
def test_contended_drain_on_a_running_clock(smoke, fair):
    """On a wall clock the host cycle loop stamps its admissions one
    after another, so among equal-priority reclaim candidates the later
    admission goes first; the classic and the fair preempt drains must
    rank their own admissions the same way and choose the same victims."""
    from kueue_tpu import serialization as ser
    from kueue_tpu.controllers import ClusterRuntime

    def run(**kw):
        rt = ClusterRuntime(fair_sharing=fair, **kw)
        smoke.populate_contended(rt, 1, 60)
        evicted = smoke.run_deleting_preempted(rt)
        return rt, evicted, smoke.admitted_set(ser.runtime_to_state(rt))

    rt, evicted, admitted = run(solver_path="device")
    assert rt.scheduler.last_traces[0].resolution == "drain"
    _, host_evicted, host_admitted = run(
        solver_path="host", use_solver=False, use_preempt_solver=False,
        bulk_drain_threshold=None,
    )
    assert evicted and evicted == host_evicted
    assert admitted == host_admitted


@pytest.mark.parametrize("mode", ["serial", "on"])
@pytest.mark.parametrize("chunk", [1, 2])
def test_chunked_rounds_resume_flavor_walks(smoke, mode, chunk):
    """Drain rounds cut heads off mid flavor walk; the next round must
    resume each walk where the host cycle loop would (LastAssignment),
    or the chunked drain admits a different set."""
    from kueue_tpu import serialization as ser
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.server import KueueServer

    body = smoke.plain_deployment(0, n_cq=40, n_cohort=5, wl_per_cq=20)

    def admitted(**kw):
        rt = ClusterRuntime(**kw)
        KueueServer(runtime=rt, auto_reconcile=False).apply_batch(body)
        rt.run_until_idle(max_iterations=100_000)
        return smoke.admitted_set(ser.runtime_to_state(rt))

    host = admitted(
        solver_path="host", use_solver=False, use_preempt_solver=False,
        bulk_drain_threshold=None,
    )
    assert admitted(
        bulk_drain_threshold=64, drain_pipeline=mode,
        pipeline_chunk_cycles=chunk,
    ) == host


@pytest.mark.parametrize("seed", [0, 3])
def test_preempt_drain_exact_past_the_search_width(smoke, seed):
    """At these seeds a cohort's victim pool outgrows the exact 32-slot
    panel and a search misses in the truncated window; the drain must
    still decide what the host cycle loop decides."""
    from kueue_tpu.controllers import ClusterRuntime
    from kueue_tpu.core.drain import run_drain_preempt
    from kueue_tpu.core.queue_manager import queue_order_timestamp
    from kueue_tpu.core.snapshot import take_snapshot
    from kueue_tpu.utils.clock import FakeClock

    def runtime():
        rt = ClusterRuntime(
            clock=FakeClock(0.0), solver_path="host", use_solver=False,
            use_preempt_solver=False, bulk_drain_threshold=None,
        )
        smoke.populate_contended(rt, seed, 30)
        return rt

    host = runtime()
    host_evicted = smoke.run_deleting_preempted(host)
    host_admitted = {k for k, wl in host.workloads.items()
                     if wl.has_quota_reservation}

    rt = runtime()
    rt.reconcile_once()
    held = {k for k, wl in rt.workloads.items() if wl.has_quota_reservation}
    snapshot = take_snapshot(rt.cache)
    out = run_drain_preempt(
        snapshot, rt.drain_backlog(snapshot), rt.cache.flavors,
        timestamp_fn=lambda wl: queue_order_timestamp(wl, rt.queues._ts_policy),
    )
    evicted = {wl.key for wl, _, _ in out.preempted}
    admitted = (held | {wl.key for wl, _, _, _ in out.admitted}) - evicted
    assert evicted == host_evicted
    assert admitted == host_admitted


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert json.loads(out.splitlines()[0].split(": ", 1)[1])["platform"] == "cpu"


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the script exits non-zero without a result."""
    shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
