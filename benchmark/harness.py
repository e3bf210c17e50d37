"""Run one cell once and print its result.

    python3 -m benchmark --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. The device: JAX must find a TPU, and at least as many chips as the
   cell asks for, of a kind that ``peaks.json`` lists; otherwise the run
   exits 2 and prints no result. Nothing falls back to the CPU.
2. Set-up (``setup_s``, from the start of the process): JAX's persistent
   compilation cache in the checkout, the configuration generated from
   the seed, the server built, the configuration loaded and settled, and
   the mix's warm-up passes, the last of them with every cycle decided
   on the device.
3. The window: whole passes of the mix until ``--seconds`` have been
   spent in the server (a pass's batch is generated before its clock
   starts and its decisions are read after it stops). With ``--trace 1``
   the profiler records it, and the run reports the cell's per-layer
   metrics instead of its end-to-end ones.
4. The check, once the window has closed, the peak device memory has
   been read and the server is gone: the configuration's plain reference
   replays every logged pass, set-up's included, and each pass's
   admissions (workload, ClusterQueue, flavors) must equal the
   program's. The number compared, beside its limit, is the last line on
   standard error and the last key of the result.

The result is the last line of standard output: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks`` last.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from collections import Counter
from typing import Callable, Optional

from benchmark.registry import CHECKOUT, HERE, Registry

TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or too few chips, or a kind with no peaks."""


def device_info(chips: int, require_tpu: bool = True) -> dict:
    from kueue_tpu._jax import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if not require_tpu:
        return info
    if info["platform"] != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {info['platform']!r}")
    if info["count"] < chips:
        raise NoAccelerator(f"needs {chips} chips; JAX found {info['count']}")
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if info["kind"] not in peaks:
        raise NoAccelerator(f"device kind {info['kind']!r} is not in peaks.json")
    return info


def _memory_peak() -> int:
    from kueue_tpu._jax import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _compile_seconds() -> float:
    from kueue_tpu.core.guard import compile_seconds

    return compile_seconds(all_threads=True)


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    registry: Optional[Registry] = None,
    require_tpu: bool = True,
    t0: Optional[float] = None,
    system_factory: Optional[Callable] = None,
) -> dict:
    """One run of one cell; returns the result object."""
    t0 = time.perf_counter() if t0 is None else t0
    reg = registry or Registry()
    cell = reg.cell(workload)
    config, traffic = cell["config"], cell["traffic"]
    device = device_info(cell["workload"]["chips"], require_tpu)

    def say(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    if require_tpu:
        from kueue_tpu._jax import jax, use_compile_cache

        # every program goes to the cache, however short its compile,
        # so a run finds all of set-up's programs there
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        say(f"compile cache: {use_compile_cache()}")
    generator = reg.module("generators", config["generator"])
    loop = reg.module("loops", traffic["loop"]).PassLoop(
        config, traffic, seed, generator
    )
    if system_factory is None:
        from benchmark.served import Served

        system_factory = Served
    system = system_factory(config.get("server"))
    try:
        loop.load(system)
        for _ in range(int(traffic.get("warmup_passes", 0))):
            loop.one_pass(system)
        # passes with every cycle decided on the device: the per-cycle
        # programs are loaded, and the scheduler's gate holds a dispatch
        # reading without their compile, as a long-running server holds
        # once its gate has re-probed
        for _ in range(int(traffic.get("device_warmup_passes", 0))):
            system.force_device(True)
            try:
                loop.one_pass(system)
            finally:
                system.force_device(False)
        setup_passes = len(loop.log)
        health0 = system.guard_health()
        gates0 = system.gate_estimates()
        compiled0 = _compile_seconds()
        passes = []
        profiler = None
        if trace:
            from benchmark.trace import Profiler

            profiler = Profiler(TRACE_DIR).__enter__()
        w0 = time.perf_counter()
        setup_s = w0 - t0
        window_s = 0.0
        try:
            while window_s < seconds:
                system.reset_counters()
                out = loop.one_pass(system)
                window_s += out["served_s"]
                passes.append({
                    "spans": dict(system.spans),
                    "counts": dict(system.counts),
                    "cycles": list(system.cycles),
                    "cycles_lost": system.cycles_lost,
                    **out,
                })
        finally:
            wall_s = time.perf_counter() - w0
            if profiler is not None:
                profiler.__exit__(None, None, None)
        window_compile_s = _compile_seconds() - compiled0
        health1 = system.guard_health()
        gates1 = system.gate_estimates()
        rejected = system.rejected
        device["memory_peak_bytes"] = _memory_peak() if require_tpu else 0
    finally:
        system.close()
    del system
    gc.collect()

    # ---- the check: the plain reference replays every pass ----
    r0 = time.perf_counter()
    reference = reg.module("references", config["reference"]).Reference()
    compared = reg.module("loops", traffic["loop"]).replay(reference, loop.log)
    reference_s = time.perf_counter() - r0
    # replay stops at the first pass that differs, so every pass is
    # compared unless one already counts as mismatched
    mismatched = sum(c["mismatched"] for c in compared)
    checks = {"mismatched_admissions": {"value": mismatched, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    record = {"passes": passes, "window_s": window_s, "setup_s": setup_s,
              "trace": None}
    breakdown = None
    if trace:
        from benchmark.trace import Trace, read_xplane

        tr = Trace(read_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        record["trace"] = tr
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": tr.device_ops(), "idle_gaps": tr.idle_gaps()}
    # a traced run reports the per-layer metrics, an untraced one the
    # end-to-end ones; each is read by its own file under metrics/
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        value = reg.module("metrics", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # ---- what the run saw, then the numbers compared, last ----
    say(f"passes: set-up {setup_passes}, window {len(passes)}, served s "
        f"{[round(p['served_s'], 3) for p in passes]}, window {window_s:.3f} s "
        f"of {wall_s:.3f} s wall")
    say("per pass: " + json.dumps([
        {"admitted": p["admitted"], "submitted": p["submitted"],
         "cycles": dict(Counter(c["resolution"] for c in p["cycles"])),
         "cyclesLost": p["cycles_lost"], "spans": p["spans"], "counts": p["counts"]}
        for p in passes
    ]))
    say(f"compile seconds inside the window: {window_compile_s}")
    say(f"guard health before/after the window: {json.dumps(health0)} / "
        f"{json.dumps(health1)}")
    say(f"latency gates before/after the window: {json.dumps(gates0)} / "
        f"{json.dumps(gates1)}")
    say(f"reference: {reference_s:.3f} s, passes: {json.dumps(compared)}")
    for name, c in checks.items():
        say(f"check {name} {c['value']} limit {c['limit']}")

    attempted = sum(p["submitted"] for p in passes)
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": rejected,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None, t0: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=t0)
    except NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0
