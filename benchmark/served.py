"""The system under test: ``KueueServer`` over a ``ClusterRuntime``.

Built in this process, as ``python -m kueue_tpu.server`` builds it with
its default flags (auto-reconcile on, no write gateway, pipelined drain,
megaloop off, solver path ``auto``, 4,096 traces), plus whatever runtime
options the configuration names under ``"server"``. Users' writes go
over HTTP; the job reconciler's work (a finished job's workload is
deleted) runs in-process under the server's lock, as a controller
beside the scheduler would.

Every call into the server is timed here, from the benchmark's side:

- ``ingest``: a batch POST, less the ``run_until_idle`` time the
  server's auto-reconcile spends inside it;
- ``loop``: every ``run_until_idle``, inside POSTs or not;
- ``release``: the in-process deletes of finished workloads;
- the program's own ``CycleTrace`` spans of every cycle, collected after
  each call before the 128-entry ring can roll over.
"""

from __future__ import annotations

import json
import time
import urllib.request
from typing import Dict, List, Optional, Sequence, Tuple


def _span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(f"bench.{name}")


class Served:
    def __init__(self, server_options: Optional[dict] = None):
        from kueue_tpu.controllers import ClusterRuntime
        from kueue_tpu.server import KueueServer
        from kueue_tpu.tas import TASCache

        rt = ClusterRuntime(tas_cache=TASCache(), **(server_options or {}))
        rt.tracer.max_traces = 4096
        self.rt = rt
        self.srv = KueueServer(runtime=rt)
        self._loop_s = 0.0
        inner = rt.run_until_idle

        def timed_run_until_idle(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with _span("run_until_idle"):
                    return inner(*args, **kwargs)
            finally:
                self._loop_s += time.perf_counter() - t0

        rt.run_until_idle = timed_run_until_idle
        self.url = f"http://127.0.0.1:{self.srv.start()}"
        self._last_cycle = max(
            (t.cycle for t in rt.scheduler.last_traces), default=0
        )
        self.spans: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.cycles: List[dict] = []
        self.cycles_lost = False
        self.rejected = 0

    def close(self) -> None:
        self.srv.stop()

    # ---- bookkeeping ----
    def reset_counters(self) -> None:
        """Start a new pass's spans, counts and cycles."""
        self.spans = {"ingest": 0.0, "loop": 0.0, "release": 0.0}
        self.counts = {"ingested": 0, "released": 0, "reconciles": 0}
        self.cycles = []
        self.cycles_lost = False

    def _collect_cycles(self) -> None:
        ring = self.rt.scheduler.last_traces
        new = [t for t in list(ring) if t.cycle > self._last_cycle]
        if new:
            if len(new) == ring.maxlen:
                self.cycles_lost = True
            self._last_cycle = new[-1].cycle
            self.cycles.extend(t.to_dict() for t in new)

    def _loop_since(self, mark: float) -> float:
        return self._loop_s - mark

    def _post(self, path: str, payload: str = "{}") -> dict:
        req = urllib.request.Request(
            self.url + path, data=payload.encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=1800) as resp:
            return json.loads(resp.read())

    # ---- the system interface the pass loop drives ----
    def load(self, body: dict) -> None:
        """Set-up: the whole configuration applied in-process."""
        out = self.srv.apply_batch(body)
        self._check_applied(out)
        with self.srv.lock:
            self._collect_cycles()

    def submit(self, payload: str, count: int) -> None:
        """One batch POST of ``payload``, the JSON of ``count`` workloads."""
        if not count:
            return
        mark = self._loop_s
        t0 = time.perf_counter()
        with _span("submit"):
            out = self._post("/apis/kueue/v1beta1/batch", payload)
        wall = time.perf_counter() - t0
        inside = self._loop_since(mark)
        self.spans["ingest"] = self.spans.get("ingest", 0.0) + wall - inside
        self.spans["loop"] = self.spans.get("loop", 0.0) + inside
        self.counts["ingested"] = self.counts.get("ingested", 0) + count
        self._check_applied(out)
        with self.srv.lock:
            self._collect_cycles()

    def _check_applied(self, out: dict) -> None:
        self.rejected += sum(out.get("rejected", {}).values())

    def settle(self) -> None:
        """``POST /reconcile`` until a reconcile changes nothing and no
        ClusterQueue has an active head left, or until three reconciles
        in a row change nothing and leave the active count as it was."""
        stalled, last_active = 0, None
        while True:
            mark = self._loop_s
            with _span("reconcile"):
                cycles = self._post("/reconcile")["cycles"]
            self.spans["loop"] = self.spans.get("loop", 0.0) + self._loop_since(mark)
            self.counts["reconciles"] = self.counts.get("reconciles", 0) + 1
            with self.srv.lock:
                self._collect_cycles()
                active = sum(
                    pq.pending_active()
                    for pq in self.rt.queues.cluster_queues.values()
                )
            if cycles <= 1 and active == 0:
                return
            stalled = stalled + 1 if cycles <= 1 and active == last_active else 0
            if stalled >= 3:
                return
            last_active = active

    def remove(self, keys: Sequence[str]) -> None:
        t0 = time.perf_counter()
        with _span("release"), self.srv.lock:
            for key in keys:
                wl = self.rt.workloads.get(key)
                if wl is not None:  # one the server never took is gone
                    self.rt.delete_workload(wl)
            self._collect_cycles()
        self.spans["release"] = self.spans.get("release", 0.0) + time.perf_counter() - t0
        self.counts["released"] = self.counts.get("released", 0) + len(keys)

    def admitted(self) -> Dict[str, Tuple[str, tuple, Optional[float]]]:
        """(ClusterQueue, flavors, QuotaReserved time) by key of every
        workload holding quota."""
        from kueue_tpu.models import WorkloadConditionType

        out = {}
        with _span("read"), self.srv.lock:
            for key, wl in self.rt.workloads.items():
                if not wl.has_quota_reservation:
                    continue
                cond = wl.conditions.get(WorkloadConditionType.QUOTA_RESERVED)
                flavors = tuple(
                    sorted(
                        (psa.name, r, f)
                        for psa in wl.admission.pod_set_assignments
                        for r, f in psa.flavors.items()
                    )
                )
                out[key] = (
                    wl.admission.cluster_queue,
                    flavors,
                    cond.last_transition_time if cond else None,
                )
        return out

    def force_device(self, on: bool) -> None:
        """Decide every cycle on the device (``True``), or let the
        scheduler's latency gate choose again, as by default."""
        self.rt.scheduler.use_solver = True if on else None

    def gate_estimates(self) -> dict:
        """The latency gates' readings, seconds: the bulk drain's per
        head, the per-cycle device dispatch, the host's per head."""
        sched = self.rt.scheduler
        with self.srv.lock:
            return {"drainPerHead": self.rt._drain_est.value,
                    "deviceDispatch": sched._device_dispatch_est.value,
                    "hostPerHead": sched._host_assign_ema}

    def guard_health(self) -> dict:
        with self.srv.lock:
            return dict(self.rt.scheduler.guard.health())
