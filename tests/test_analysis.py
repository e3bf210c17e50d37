"""kueuelint (kueue_tpu/analysis) — tier-1 suite.

Three layers:

- **fixture snippets per rule**: each rule must flag its known-bad
  snippet, pass the clean twin, and honor ``# kueuelint: disable=``
  pragmas. The kernel-dtype bad fixture reproduces the TAS s64/s32
  dynamic-update-slice mix (the PR-8 GSPMD miscompile) and the
  journal-symmetry bad fixture deletes a recovery handler (the PR-9
  convergence-bug shape) — both acceptance criteria of ISSUE 11.
- **engine units**: pragmas, Finding ordering, baseline parse/match/
  shrink-only ratchet, CLI exit codes.
- **the package gate**: the full rule suite over the real tree must
  be clean modulo the checked-in baseline, and every baseline entry
  must still resolve to a real file:line AND a current finding
  (stale-baseline check).
"""

import os

import pytest

from kueue_tpu.analysis import (
    Baseline,
    BaselineEntry,
    Finding,
    lint,
    repo_root,
    rule_names,
    run_analysis,
)
from kueue_tpu.analysis.baseline import DEFAULT_BASELINE_PATH
from kueue_tpu.analysis.core import SourceFile


def write_tree(root, files):
    for rel, text in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)


def run_fixture(tmp_path, files, rules, config=None):
    # each call gets a fresh tree so one test's bad fixture cannot
    # leak into its clean twin's run
    n = len(os.listdir(str(tmp_path)))
    root = os.path.join(str(tmp_path), f"case{n}")
    write_tree(root, files)
    cfg = {"require_call_sites": False}
    cfg.update(config or {})
    return run_analysis(root, rules=rules, subdir="", config=cfg)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---- kernel-dtype ----
TAS_DUS_BAD = '''\
import jax.numpy as jnp
from jax import lax


def tas_step(free):
    cur = jnp.zeros((4,), dtype=jnp.int32)
    adm = jnp.zeros((4, 8), dtype=jnp.int64)
    row = jnp.zeros((1, 8), dtype=jnp.int32)
    adm = lax.dynamic_update_slice(adm, row, (cur[0], 0))
    hit = cur[0] == adm[0, 0]
    mix = cur[0] + adm[0, 0]
    return adm, hit, mix
'''

TAS_DUS_GOOD = '''\
import jax.numpy as jnp
from jax import lax


def tas_step(free):
    cur = jnp.zeros((4,), dtype=jnp.int32)
    adm = jnp.zeros((4, 8), dtype=jnp.int64)
    row = jnp.zeros((1, 8), dtype=jnp.int32)
    adm = lax.dynamic_update_slice(adm, row.astype(jnp.int64), (cur[0], 0))
    cur64 = cur.astype(jnp.int64)
    hit = cur64[0] == adm[0, 0]
    mix = cur64[0] + adm[0, 0]
    return adm, hit, mix
'''


class TestKernelDtypeRule:
    def test_flags_the_tas_s64_s32_dus_mix(self, tmp_path):
        """ISSUE-11 acceptance: the exact historical miscompile shape
        is caught at lint time."""
        findings = run_fixture(
            tmp_path, {"ops/tas_fixture_kernel.py": TAS_DUS_BAD},
            rules=["kernel-dtype"],
        )
        messages = "\n".join(f.message for f in findings)
        assert any("dynamic_update_slice" in f.message for f in findings)
        assert any("comparison" in f.message for f in findings)
        assert any("promotion" in f.message for f in findings)
        assert all(f.rule == "kernel-dtype" for f in findings), messages

    def test_passes_the_astype_aligned_twin(self, tmp_path):
        assert run_fixture(
            tmp_path, {"ops/tas_fixture_kernel.py": TAS_DUS_GOOD},
            rules=["kernel-dtype"],
        ) == []

    def test_at_update_sugar_is_covered(self, tmp_path):
        src = (
            "import jax.numpy as jnp\n\n\n"
            "def k():\n"
            "    a = jnp.zeros((4,), dtype=jnp.int64)\n"
            "    v = jnp.ones((4,), dtype=jnp.int32)\n"
            "    return a.at[0].set(v[0])\n"
        )
        findings = run_fixture(
            tmp_path, {"ops/at_kernel.py": src}, rules=["kernel-dtype"]
        )
        assert len(findings) == 1 and ".at[...]" in findings[0].message

    def test_scoped_to_kernel_files(self, tmp_path):
        # the same bad source OUTSIDE ops/*_kernel.py is host code
        assert run_fixture(
            tmp_path, {"core/host.py": TAS_DUS_BAD}, rules=["kernel-dtype"]
        ) == []

    def test_pragma_suppresses(self, tmp_path):
        src = TAS_DUS_BAD.replace(
            "    adm = lax.dynamic_update_slice(adm, row, (cur[0], 0))",
            "    # kueuelint: disable=kernel-dtype — fixture-justified\n"
            "    adm = lax.dynamic_update_slice(adm, row, (cur[0], 0))",
        )
        findings = run_fixture(
            tmp_path, {"ops/tas_fixture_kernel.py": src},
            rules=["kernel-dtype"],
        )
        assert not any(
            "dynamic_update_slice" in f.message for f in findings
        )


# ---- trace-safety ----
TRACE_BAD = '''\
import random
import time

import jax
import jax.numpy as jnp


@jax.jit
def solve(x):
    t0 = time.time()
    jitter = random.random()
    if jnp.any(x > 0):
        x = x + 1
    n = int(jnp.sum(x))
    y = x.item()
    return x, t0, jitter, n, y


def body(c):
    time.monotonic()
    return c


stepper = jax.vmap(body)
'''

TRACE_GOOD = '''\
import time

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def solve(x):
    x = jnp.where(jnp.any(x > 0), x + 1, x)
    return lax.cond(x.sum() > 0, lambda v: v, lambda v: v * 0, x)


def host_loop(x):
    # host code may read clocks freely — it is not traced
    t0 = time.monotonic()
    return solve(x), t0
'''


class TestTraceSafetyRule:
    def test_flags_host_calls_in_jitted_fn(self, tmp_path):
        findings = run_fixture(
            tmp_path, {"ops/jitted.py": TRACE_BAD}, rules=["trace-safety"]
        )
        msgs = [f.message for f in findings]
        assert any("time.time()" in m for m in msgs)
        assert any("random.random()" in m for m in msgs)
        assert any("`if` on a traced value" in m for m in msgs)
        assert any("int() over a traced value" in m for m in msgs)
        assert any(".item()" in m for m in msgs)
        # the vmapped-by-name body is traced too
        assert any("time.monotonic()" in m and "body" in m for m in msgs)

    def test_passes_clean_kernel_and_host_code(self, tmp_path):
        assert run_fixture(
            tmp_path, {"ops/clean.py": TRACE_GOOD}, rules=["trace-safety"]
        ) == []

    def test_pragma_suppresses(self, tmp_path):
        src = TRACE_BAD.replace(
            "    t0 = time.time()",
            "    t0 = time.time()  # kueuelint: disable=trace-safety",
        )
        findings = run_fixture(
            tmp_path, {"ops/jitted.py": src}, rules=["trace-safety"]
        )
        assert not any("time.time()" in f.message for f in findings)


# host-side effects reachable inside a lax.while_loop body in the
# kernel package — the megaloop's io_callback-free contract: nothing
# inside a fused device loop may journal, record or fire fault points
TRACE_EFFECT_BAD = '''\
import jax.numpy as jnp
from jax import lax

from kueue_tpu.testing import faults


def solve_fused(tree, state):
    def body(s):
        faults.fire("cycle.inside_loop")
        return s + jnp.int32(1)

    def cond(s):
        return s < 8

    return lax.while_loop(cond, body, state)


def solve_logged(journal, state):
    def logging_body(s):
        journal.record("round", {"s": 0})
        return s + 1

    return lax.while_loop(lambda s: s < 4, logging_body, state)
'''

TRACE_EFFECT_GOOD = '''\
import jax.numpy as jnp
from jax import lax

from kueue_tpu.testing import faults


def solve_fused(tree, state):
    def body(s):
        return s + jnp.int32(1)

    return lax.while_loop(lambda s: s < 8, body, state)


def launch_and_apply(journal, state):
    # host glue OUTSIDE the trace journals freely: the effect sits on
    # the host side of the launch/fetch split
    out = solve_fused(None, state)
    journal.record("round", {"s": 1})
    faults.fire("cycle.post_solve_pre_apply")
    return out
'''


class TestTraceSafetyHostEffects:
    def test_flags_effects_in_while_loop_bodies(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            {"ops/fused.py": TRACE_EFFECT_BAD},
            rules=["trace-safety"],
        )
        msgs = [f.message for f in findings]
        assert any(
            "faults.fire()" in m and "io_callback-free" in m for m in msgs
        ), msgs
        assert any("journal.record()" in m for m in msgs), msgs

    def test_host_side_effects_outside_trace_pass(self, tmp_path):
        assert run_fixture(
            tmp_path,
            {"ops/fused.py": TRACE_EFFECT_GOOD},
            rules=["trace-safety"],
        ) == []

    def test_scope_is_kernel_package_and_drain_glue(self, tmp_path):
        # the same body outside ops/ + core/drain.py is not in scope
        # for the effect contract (server/event code fires freely)
        assert run_fixture(
            tmp_path,
            {"controllers/loopy.py": TRACE_EFFECT_BAD},
            rules=["trace-safety"],
        ) == []
        findings = run_fixture(
            tmp_path,
            {"core/drain.py": TRACE_EFFECT_BAD},
            rules=["trace-safety"],
        )
        assert findings, "core/drain.py must be in the effect scope"

    def test_real_tree_fused_loops_clean(self):
        """The production contract: the real ops/ kernels (incl. the
        megaloop while_loop) and core/drain.py carry no host effects
        inside traced scope."""
        from kueue_tpu.analysis import lint

        findings = [
            f
            for f in lint(rules=["trace-safety"])
            if "io_callback-free" in f.message
        ]
        assert findings == [], findings


# ---- journal-symmetry ----
SYM_PRODUCER = '''\
UPSERT = "workload_upsert"


class Runtime:
    def _journal_append(self, rtype, data):
        pass

    def add_workload(self, wl):
        self._journal_append(UPSERT, {"wl": wl})

    def quarantine(self, key):
        self._journal_append("quarantine_set", {"key": key})
'''

SYM_RECOVERY = '''\
WORKLOAD_UPSERT = "workload_upsert"
QUARANTINE_SET = "quarantine_set"


def apply_record(rt, rec):
    if rec.type == WORKLOAD_UPSERT:
        rt.add(rec.data)
    elif rec.type in (QUARANTINE_SET,):
        rt.q(rec.data)
'''

SYM_TAILER = '''\
from storage.recovery import apply_record


def poll(rt, recs):
    for rec in recs:
        apply_record(rt, rec)
'''

# The elastic capacity plane journals its grants/revokes from its own
# module (not the runtime funnel file) — the rule must still pair them
# with the recovery handler's membership-tuple dispatch.
SYM_ELASTIC_PRODUCER = '''\
ELASTIC_GRANT = "elastic_grant"
ELASTIC_REVOKE = "elastic_revoke"


class Plane:
    def _grant(self, data):
        self.runtime._journal_append(ELASTIC_GRANT, data)

    def _revoke(self, data):
        self.runtime._journal_append(ELASTIC_REVOKE, data)
'''

SYM_ELASTIC_RECOVERY = '''\
WORKLOAD_UPSERT = "workload_upsert"
QUARANTINE_SET = "quarantine_set"
ELASTIC_GRANT = "elastic_grant"
ELASTIC_REVOKE = "elastic_revoke"
_ELASTIC_TYPES = (ELASTIC_GRANT, ELASTIC_REVOKE)


def apply_record(rt, rec):
    if rec.type == WORKLOAD_UPSERT:
        rt.add(rec.data)
    elif rec.type in (QUARANTINE_SET,):
        rt.q(rec.data)
    elif rec.type in _ELASTIC_TYPES:
        rt.capacity(rec.type, rec.data)
'''


# The delta checkpointer appends its chain marks with kinds IMPORTED
# from the recovery module rather than defined locally — the rule must
# resolve them through the cross-module constants map.
SYM_CKPT_PRODUCER = '''\
from storage.recovery import CHECKPOINT_ANCHOR, CHECKPOINT_DELTA


class DeltaCheckpointer:
    def prepare(self, runtime, full):
        if full:
            runtime._journal_append(CHECKPOINT_ANCHOR, {"name": "a"})
        else:
            runtime._journal_append(CHECKPOINT_DELTA, {"name": "d"})
'''

SYM_CKPT_RECOVERY = '''\
WORKLOAD_UPSERT = "workload_upsert"
QUARANTINE_SET = "quarantine_set"
CHECKPOINT_ANCHOR = "checkpoint_anchor"
CHECKPOINT_DELTA = "checkpoint_delta"
_CHECKPOINT_TYPES = (CHECKPOINT_ANCHOR, CHECKPOINT_DELTA)


def apply_record(rt, rec):
    if rec.type == WORKLOAD_UPSERT:
        rt.add(rec.data)
    elif rec.type in (QUARANTINE_SET,):
        rt.q(rec.data)
    elif rec.type in _CHECKPOINT_TYPES:
        rt.mark(rec.type, rec.data)
'''


class TestJournalSymmetryRule:
    def _tree(self, recovery=SYM_RECOVERY, tailer=SYM_TAILER, extra=None):
        files = {
            "controllers/cluster.py": SYM_PRODUCER,
            "storage/recovery.py": recovery,
        }
        if tailer is not None:
            files["storage/tailer.py"] = tailer
        if extra:
            files.update(extra)
        return files

    def test_symmetric_tree_is_clean(self, tmp_path):
        assert run_fixture(
            tmp_path, self._tree(), rules=["journal-symmetry"]
        ) == []

    def test_deleting_a_handler_fails(self, tmp_path):
        """ISSUE-11 acceptance: remove the quarantine_set handler and
        the appended kind no longer replays — a finding at the append
        site."""
        broken = SYM_RECOVERY.replace(
            "    elif rec.type in (QUARANTINE_SET,):\n        rt.q(rec.data)\n",
            "",
        )
        findings = run_fixture(
            tmp_path, self._tree(recovery=broken),
            rules=["journal-symmetry"],
        )
        assert len(findings) == 1
        f = findings[0]
        assert "quarantine_set" in f.message
        assert f.file == "controllers/cluster.py"

    def test_handler_without_producer_fails(self, tmp_path):
        orphan = SYM_RECOVERY.replace(
            'QUARANTINE_SET = "quarantine_set"',
            'QUARANTINE_SET = "quarantine_set"\nGHOST = "ghost_kind"',
        ).replace(
            "    elif rec.type in (QUARANTINE_SET,):",
            "    elif rec.type in (QUARANTINE_SET, GHOST):",
        )
        findings = run_fixture(
            tmp_path, self._tree(recovery=orphan),
            rules=["journal-symmetry"],
        )
        assert len(findings) == 1
        assert "ghost_kind" in findings[0].message
        assert "dead vocabulary" in findings[0].message

    def test_missing_tailer_path_fails(self, tmp_path):
        findings = run_fixture(
            tmp_path, self._tree(tailer=None), rules=["journal-symmetry"]
        )
        assert len(findings) == 1
        assert "tailer" in findings[0].message

    def test_elastic_kinds_symmetric_tree_is_clean(self, tmp_path):
        """ISSUE-18: elastic_grant/elastic_revoke journaled from the
        capacity plane's own module, replayed via the recovery
        membership tuple — symmetric, no findings."""
        assert run_fixture(
            tmp_path,
            self._tree(
                recovery=SYM_ELASTIC_RECOVERY,
                extra={"elastic/plane.py": SYM_ELASTIC_PRODUCER},
            ),
            rules=["journal-symmetry"],
        ) == []

    def test_elastic_handler_missing_fails_both_kinds(self, tmp_path):
        """Producer present, recovery never taught the elastic kinds:
        one finding per kind, each anchored at the plane's append
        site (crash-recovery would silently drop granted capacity)."""
        findings = run_fixture(
            tmp_path,
            self._tree(extra={"elastic/plane.py": SYM_ELASTIC_PRODUCER}),
            rules=["journal-symmetry"],
        )
        assert len(findings) == 2
        kinds = {("elastic_grant" in f.message, "elastic_revoke" in f.message)
                 for f in findings}
        assert kinds == {(True, False), (False, True)}
        assert all(f.file == "elastic/plane.py" for f in findings)

    def test_elastic_producer_deleted_is_dead_vocabulary(self, tmp_path):
        """Recovery still dispatches the elastic kinds but nothing
        journals them — dead vocabulary findings on the handler."""
        findings = run_fixture(
            tmp_path,
            self._tree(recovery=SYM_ELASTIC_RECOVERY),
            rules=["journal-symmetry"],
        )
        assert len(findings) == 2
        assert all("dead vocabulary" in f.message for f in findings)
        assert all(f.file == "storage/recovery.py" for f in findings)

    def test_checkpoint_kinds_imported_constants_clean(self, tmp_path):
        """ISSUE-19: the checkpointer appends chain marks with kinds
        imported from the recovery module (no local literal) — the
        cross-module constants map pairs them with the recovery
        membership tuple; symmetric, no findings."""
        assert run_fixture(
            tmp_path,
            self._tree(
                recovery=SYM_CKPT_RECOVERY,
                extra={"storage/checkpoint.py": SYM_CKPT_PRODUCER},
            ),
            rules=["journal-symmetry"],
        ) == []

    def test_checkpoint_handler_deleted_fails_both_kinds(self, tmp_path):
        """Delete the _CHECKPOINT_TYPES dispatch arm (constants stay):
        one finding per mark kind, anchored at the checkpointer's
        append sites — replay would drop the chain marks."""
        broken = SYM_CKPT_RECOVERY.replace(
            "    elif rec.type in _CHECKPOINT_TYPES:\n"
            "        rt.mark(rec.type, rec.data)\n",
            "",
        )
        findings = run_fixture(
            tmp_path,
            self._tree(
                recovery=broken,
                extra={"storage/checkpoint.py": SYM_CKPT_PRODUCER},
            ),
            rules=["journal-symmetry"],
        )
        assert len(findings) == 2
        kinds = {("checkpoint_anchor" in f.message,
                  "checkpoint_delta" in f.message)
                 for f in findings}
        assert kinds == {(True, False), (False, True)}
        assert all(f.file == "storage/checkpoint.py" for f in findings)

    def test_checkpoint_producer_deleted_is_dead_vocabulary(self, tmp_path):
        """Recovery still dispatches the checkpoint mark kinds but the
        checkpointer module is gone — dead vocabulary on the handler."""
        findings = run_fixture(
            tmp_path,
            self._tree(recovery=SYM_CKPT_RECOVERY),
            rules=["journal-symmetry"],
        )
        assert len(findings) == 2
        assert all("dead vocabulary" in f.message for f in findings)
        assert all(f.file == "storage/recovery.py" for f in findings)

    def test_real_tree_checkpoint_kinds_paired(self):
        """The production contract: the real storage/checkpoint.py
        appends checkpoint_anchor/checkpoint_delta marks via imported
        constants, and the real recovery module replays them — the
        rule resolves the pairing across modules with no findings."""
        from kueue_tpu.analysis import lint

        assert [f for f in lint(rules=["journal-symmetry"])] == []


# ---- clock-discipline ----
class TestClockDisciplineRule:
    def test_flags_naked_clocks_and_aliases(self, tmp_path):
        src = (
            "import time as _time\n"
            "from datetime import datetime\n\n\n"
            "def stamp():\n"
            "    return _time.time(), datetime.now()\n"
        )
        findings = run_fixture(
            tmp_path, {"core/x.py": src}, rules=["clock-discipline"],
            config={"clock_allowlist": {}},
        )
        assert len(findings) == 2
        assert all("naked" in f.message for f in findings)

    def test_injected_clock_is_clean(self, tmp_path):
        src = (
            "class Thing:\n"
            "    def __init__(self, clock):\n"
            "        self.clock = clock\n\n"
            "    def stamp(self):\n"
            "        return self.clock.now()\n"
        )
        assert run_fixture(
            tmp_path, {"core/x.py": src}, rules=["clock-discipline"],
            config={"clock_allowlist": {}},
        ) == []

    def test_allowlist_scopes_and_stale_entries(self, tmp_path):
        src = (
            "import time\n\n\n"
            "def fallback():\n"
            "    return time.time()\n"
        )
        allow = {"core/x.py::fallback": "documented fallback"}
        assert run_fixture(
            tmp_path, {"core/x.py": src}, rules=["clock-discipline"],
            config={"clock_allowlist": dict(allow)},
        ) == []
        # a stale entry (nothing naked left in scope) is itself flagged
        allow["core/x.py::gone"] = "rotted justification"
        findings = run_fixture(
            tmp_path, {"core/x.py": src}, rules=["clock-discipline"],
            config={"clock_allowlist": allow},
        )
        assert len(findings) == 1 and "stale" in findings[0].message

    def test_every_real_allowlist_entry_is_justified(self):
        from kueue_tpu.analysis.rules_clock import CLOCK_ALLOWLIST

        for scope, why in CLOCK_ALLOWLIST.items():
            assert isinstance(why, str) and len(why) > 20, (
                f"{scope}: allowlist entries carry real justifications"
            )


class TestClockStrictFederationScope:
    """The federation strict sub-scope (ISSUE 15 satellite): under
    kueue_tpu/federation/, duration measurement and sleeps are ALSO
    findings — the FakeClock chaos suites drive that code end to end,
    so even telemetry timing must be allowlisted deliberately."""

    BAD = (
        "import time\n\n\n"
        "def pump():\n"
        "    t0 = time.perf_counter()\n"
        "    time.sleep(0.1)\n"
        "    return time.perf_counter() - t0\n"
    )

    def test_strict_scope_flags_perf_counter_and_sleep(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            {"kueue_tpu/federation/x.py": self.BAD},
            rules=["clock-discipline"],
            config={"clock_allowlist": {}},
        )
        assert len(findings) == 3
        assert all("strict scope" in f.message for f in findings)

    def test_outside_federation_perf_counter_stays_allowed(self, tmp_path):
        assert run_fixture(
            tmp_path,
            {"kueue_tpu/core/x.py": self.BAD},
            rules=["clock-discipline"],
            config={"clock_allowlist": {}},
        ) == []

    def test_strict_scope_honors_allowlist(self, tmp_path):
        allow = {
            "kueue_tpu/federation/x.py::pump": (
                "RTT measurement, reported never scheduled on"
            )
        }
        assert run_fixture(
            tmp_path,
            {"kueue_tpu/federation/x.py": self.BAD},
            rules=["clock-discipline"],
            config={"clock_allowlist": allow},
        ) == []

    def test_strict_prefixes_configurable(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            {"kueue_tpu/core/x.py": self.BAD},
            rules=["clock-discipline"],
            config={
                "clock_allowlist": {},
                "clock_strict_prefixes": ("kueue_tpu/core/",),
            },
        )
        assert len(findings) == 3

    def test_real_federation_tree_is_strict_clean(self):
        """The shipped federation package passes its own strict rule
        (dispatcher RTT + rescore timing ride allowlist entries)."""
        findings = [
            f
            for f in run_analysis(
                repo_root(), rules=["clock-discipline"]
            )
            if f.file.startswith("kueue_tpu/federation/")
        ]
        assert findings == []


# ---- lock-discipline ----
LOCK_BAD = '''\
import threading


class Cursor:
    def __init__(self):
        self._lock = threading.Lock()
        self.pos = 0  # guarded by: _lock

    def advance(self):
        self.pos += 1

    def push(self, item):
        self.items.append(item)
'''

LOCK_GOOD = '''\
import threading


class Cursor:
    def __init__(self):
        self._lock = threading.Lock()
        self.pos = 0  # guarded by: _lock

    def advance(self):
        with self._lock:
            self.pos += 1

    def _bump_locked(self):
        self.pos += 1

    def reset(self):  # kueuelint: holds=_lock
        self.pos = 0
'''


class TestLockDisciplineRule:
    def test_flags_unlocked_writes(self, tmp_path):
        findings = run_fixture(
            tmp_path, {"core/c.py": LOCK_BAD}, rules=["lock-discipline"]
        )
        assert len(findings) == 1
        assert "self.pos" in findings[0].message

    def test_locked_suffix_and_holds_marker_exempt(self, tmp_path):
        assert run_fixture(
            tmp_path, {"core/c.py": LOCK_GOOD}, rules=["lock-discipline"]
        ) == []

    def test_cross_class_write_is_flagged(self, tmp_path):
        other = (
            "from core.c import Cursor\n\n\n"
            "def hammer(cur):\n"
            "    cur.pos += 1\n"
        )
        findings = run_fixture(
            tmp_path,
            {"core/c.py": LOCK_GOOD, "core/other.py": other},
            rules=["lock-discipline"],
        )
        assert len(findings) == 1
        assert findings[0].file == "core/other.py"
        assert "outside class Cursor" in findings[0].message

    def test_ambiguous_attr_names_skip_cross_class_check(self, tmp_path):
        ambiguous = (
            "class Result:\n"
            "    def __init__(self):\n"
            "        self.pos = 0\n\n\n"
            "def fill(res):\n"
            "    res.pos = 5\n"
        )
        assert run_fixture(
            tmp_path,
            {"core/c.py": LOCK_GOOD, "core/res.py": ambiguous},
            rules=["lock-discipline"],
        ) == []

    def test_mutating_container_calls_count_as_writes(self, tmp_path):
        src = LOCK_GOOD.replace(
            "        self.pos = 0  # guarded by: _lock",
            "        self.pos = 0  # guarded by: _lock\n"
            "        self.items = []  # guarded by: _lock",
        ) + (
            "\n    def push(self, item):\n"
            "        self.items.append(item)\n"
        )
        findings = run_fixture(
            tmp_path, {"core/c.py": src}, rules=["lock-discipline"]
        )
        assert len(findings) == 1 and ".append()" in findings[0].message


# ---- registry rules ----
class TestRegistryRules:
    def test_reason_enum(self, tmp_path):
        bad = 'def f(r):\n    r.record("BadReason", "x", "msg")\n'
        good = 'def f(r):\n    r.record("GoodReason", "x", "msg")\n'
        cfg = {"event_reasons": {"GoodReason"}}
        assert run_fixture(
            tmp_path, {"a.py": bad}, rules=["reason-enum"], config=dict(cfg)
        )[0].message.startswith("ad-hoc event reason 'BadReason'")
        assert run_fixture(
            tmp_path, {"b.py": good}, rules=["reason-enum"], config=dict(cfg)
        ) == []
        pragma = bad.replace(
            '    r.record(', '    # kueuelint: disable=reason-enum\n'
            '    r.record(',
        )
        assert run_fixture(
            tmp_path, {"c.py": pragma}, rules=["reason-enum"],
            config=dict(cfg),
        ) == []

    def test_span_name(self, tmp_path):
        cfg = {"span_names": {"cycle.solve"}}
        bad = 'def f(tr):\n    tr.add_cycle_span("cycle.bogus")\n'
        good = 'def f(tr):\n    tr.add_cycle_span("cycle.solve")\n'
        assert "cycle.bogus" in run_fixture(
            tmp_path, {"a.py": bad}, rules=["span-name"], config=dict(cfg)
        )[0].message
        assert run_fixture(
            tmp_path, {"b.py": good}, rules=["span-name"], config=dict(cfg)
        ) == []

    def test_span_name_phase_literals(self, tmp_path):
        cfg = {"span_names": {"cycle.solve"},
               "phase_names": {"cycle.solve", "loop.report"}}
        bad = (
            "from kueue_tpu.tracing import Phase\n"
            'P = Phase("loop.bogus")\n'
            'Q = tracing.Phase("cycle.bogus")\n'
        )
        good = 'P = Phase("loop.report")\nQ = Phase("cycle.solve")\n'
        findings = run_fixture(
            tmp_path, {"a.py": bad}, rules=["span-name"], config=dict(cfg)
        )
        assert sorted(f.line for f in findings) == [2, 3]
        assert all("PHASE_NAMES" in f.message for f in findings)
        assert run_fixture(
            tmp_path, {"b.py": good}, rules=["span-name"], config=dict(cfg)
        ) == []
        # a runtime phase is not a span: recording it is an ad-hoc span
        span = 'def f(tr):\n    tr.add_cycle_span("loop.report")\n'
        assert "SPAN_NAMES" in run_fixture(
            tmp_path, {"c.py": span}, rules=["span-name"], config=dict(cfg)
        )[0].message

    def test_span_name_pattern_rot_guard(self, tmp_path):
        cfg = {"span_names": {"cycle.solve"}, "require_call_sites": True}
        findings = run_fixture(
            tmp_path, {"a.py": "x = 1\n"}, rules=["span-name"],
            config=cfg,
        )
        assert len(findings) == 1 and "rotted" in findings[0].message

    def test_fault_point(self, tmp_path):
        cfg = {"fault_points": {"a.b": "doc"}}
        bad = 'def f(faults):\n    faults.fire("z.q")\n'
        good = (
            "def f(faults, run):\n"
            '    faults.fire("a.b")\n'
            '    run(fault_point="a.b")\n'
        )
        assert "z.q" in run_fixture(
            tmp_path, {"a.py": bad}, rules=["fault-point"], config=dict(cfg)
        )[0].message
        assert run_fixture(
            tmp_path, {"b.py": good}, rules=["fault-point"],
            config=dict(cfg),
        ) == []

    def test_fault_point_unfired_registry_entry(self, tmp_path):
        cfg = {
            "fault_points": {"a.b": "doc", "never.fired": "doc"},
            "require_call_sites": True,
        }
        findings = run_fixture(
            tmp_path, {"a.py": 'def f(faults):\n    faults.fire("a.b")\n'},
            rules=["fault-point"], config=cfg,
        )
        assert len(findings) == 1 and "never.fired" in findings[0].message

    def test_metrics_families(self, tmp_path):
        src = (
            'NS = "kueue"\n\n\n'
            "def build(r):\n"
            '    a = r.counter(f"{NS}_good_total", "help text")\n'
            '    b = r.gauge("unprefixed_thing", "help")\n'
            '    c = r.histogram("kueue_dup_seconds", "help")\n'
            '    d = r.counter("kueue_dup_seconds", "help")\n'
            '    e = r.counter("kueue_empty_total", "")\n'
            "    return a, b, c, d, e\n"
        )
        findings = run_fixture(
            tmp_path, {"metrics/metrics.py": src},
            rules=["metrics-families"],
        )
        msgs = [f.message for f in findings]
        assert any("unprefixed_thing" in m and "prefix" in m for m in msgs)
        assert any("duplicate" in m for m in msgs)
        assert any("empty HELP" in m for m in msgs)
        assert not any("kueue_good_total" in m for m in msgs)

    def test_kernel_mirrors_good_and_bad(self, tmp_path):
        anchor = SourceFile(
            "<mem>", "ops/__init__.py", "KERNEL_MIRRORS = {}\n"
        )
        good = run_analysis(
            repo_root(), rules=["kernel-mirrors"], sources=[anchor],
            config={
                "kernel_stems": {"foo_kernel"},
                "kernel_mirrors": {
                    "foo_kernel": (
                        "kueue_tpu.ops.drain_np:solve_drain_np",
                        "tests/test_drain_parity.py",
                    )
                },
                "sharded_kernels": {},
            },
        )
        assert good == []
        bad = run_analysis(
            repo_root(), rules=["kernel-mirrors"], sources=[anchor],
            config={
                "kernel_stems": {"foo_kernel", "bar_kernel"},
                "kernel_mirrors": {
                    "foo_kernel": (
                        "kueue_tpu.no_such_module:missing",
                        "tests/no_such_test.py",
                    )
                },
                "sharded_kernels": {"baz_kernel": "kueue_tpu.x:y"},
            },
        )
        msgs = [f.message for f in bad]
        assert any("bar_kernel" in m and "no registered" in m for m in msgs)
        assert any("does not import" in m for m in msgs)
        assert any("no_such_test.py" in m for m in msgs)
        assert any("baz_kernel" in m and "sharded" in m for m in msgs)


# ---- deadline-discipline ----
DEADLINE_BAD = '''\
from kueue_tpu.server.client import KueueClient


class Pump:
    def __init__(self, url):
        self.client = KueueClient(url)

    def sync(self, cluster, key):
        return cluster.call("get_workload", key)

    def poll(self):
        return self.client.journal_tail(since_seq=0)
'''

DEADLINE_GOOD = '''\
from kueue_tpu.server.client import KueueClient


class Pump:
    def __init__(self, url):
        self.client = KueueClient(url, timeout=10.0)

    def sync(self, cluster, key, deadline):
        return cluster.call("get_workload", key, deadline_s=deadline)

    def forward(self, cluster, key, **kw):
        return cluster.call("get_workload", key, **kw)

    def poll(self, deadline):
        return self.client.journal_tail(since_seq=0, timeout_s=deadline)
'''


class TestDeadlineDisciplineRule:
    """The gray-failure habit fix (ISSUE 20 satellite): control-loop
    call sites under federation/, replica/ and admissionchecks/ must
    name their per-call deadline instead of riding whatever timeout
    the transport constructor baked in."""

    def test_flags_default_timeout_call_sites(self, tmp_path):
        findings = run_fixture(
            tmp_path,
            {"kueue_tpu/federation/x.py": DEADLINE_BAD},
            rules=["deadline-discipline"],
        )
        msgs = [f.message for f in findings]
        assert len(findings) == 3
        assert any("KueueClient" in m and "timeout=" in m for m in msgs)
        assert any(".call(" in m and "deadline_s=" in m for m in msgs)
        assert any(".journal_tail(" in m and "timeout_s=" in m for m in msgs)

    def test_explicit_deadlines_and_splats_are_clean(self, tmp_path):
        assert run_fixture(
            tmp_path,
            {"kueue_tpu/federation/x.py": DEADLINE_GOOD},
            rules=["deadline-discipline"],
        ) == []

    def test_out_of_scope_files_are_ignored(self, tmp_path):
        # the discipline binds control loops; CLI one-shots, bench
        # scripts and the server glue stay out of scope
        assert run_fixture(
            tmp_path,
            {"kueue_tpu/cli/x.py": DEADLINE_BAD},
            rules=["deadline-discipline"],
        ) == []

    def test_allowlist_scopes_and_stale_entries(self, tmp_path):
        allow = {
            "kueue_tpu/federation/x.py::Pump.sync": "caller-bounded",
            "kueue_tpu/federation/x.py::Pump.__init__": "script glue",
            "kueue_tpu/federation/x.py::Pump.poll": "long-poll wire",
        }
        assert run_fixture(
            tmp_path,
            {"kueue_tpu/federation/x.py": DEADLINE_BAD},
            rules=["deadline-discipline"],
            config={"deadline_allowlist": dict(allow)},
        ) == []
        # a stale entry (scope now clean) is itself a finding
        allow["kueue_tpu/federation/x.py::Pump.gone"] = "rotted"
        findings = run_fixture(
            tmp_path,
            {"kueue_tpu/federation/x.py": DEADLINE_BAD},
            rules=["deadline-discipline"],
            config={"deadline_allowlist": allow},
        )
        assert len(findings) == 1 and "stale" in findings[0].message

    def test_real_tree_is_deadline_clean(self):
        """The production contract: every .call/journal_tail/transport
        construction in the scoped control loops already names its
        bound — no allowlist debt at introduction time."""
        assert lint(rules=["deadline-discipline"]) == []


# ---- engine units ----
class TestEngine:
    def test_parse_error_is_a_finding_not_a_crash(self, tmp_path):
        findings = run_fixture(
            tmp_path, {"bad.py": "def broken(:\n"}, rules=["reason-enum"],
            config={"event_reasons": set()},
        )
        assert len(findings) == 1 and findings[0].rule == "parse-error"

    def test_disable_file_pragma(self, tmp_path):
        src = (
            "# kueuelint: disable-file=clock-discipline\n"
            "import time\n\n\n"
            "def a():\n    return time.time()\n\n\n"
            "def b():\n    return time.time()\n"
        )
        assert run_fixture(
            tmp_path, {"x.py": src}, rules=["clock-discipline"],
            config={"clock_allowlist": {}},
        ) == []

    def test_unknown_rule_rejected(self):
        with pytest.raises(ValueError, match="unknown rule"):
            run_analysis(repo_root(), rules=["no-such-rule"], sources=[])

    def test_finding_str_is_clickable(self):
        f = Finding("kernel-dtype", "kueue_tpu/ops/x.py", 12, "boom")
        assert str(f) == "kueue_tpu/ops/x.py:12: [kernel-dtype] boom"

    def test_rule_registry_is_closed_and_complete(self):
        assert rule_names() == sorted(
            [
                "kernel-dtype", "trace-safety", "journal-symmetry",
                "clock-discipline", "lock-discipline", "reason-enum",
                "span-name", "fault-point", "metrics-families",
                "kernel-mirrors", "policy-name", "deadline-discipline",
            ]
        )


class TestBaseline:
    def _finding(self, msg="m", line=3):
        return Finding("clock-discipline", "kueue_tpu/a.py", line, msg)

    def test_entry_round_trip(self):
        e = BaselineEntry.from_finding(self._finding())
        assert BaselineEntry.parse(e.format()) == e

    def test_split_and_line_drift_tolerance(self):
        base = Baseline([BaselineEntry.from_finding(self._finding())])
        drifted = self._finding(line=99)  # same rule/file/message
        new, suppressed, stale = base.split([drifted])
        assert new == [] and suppressed == [drifted] and stale == []
        other = self._finding(msg="different")
        new, suppressed, stale = base.split([other])
        assert new == [other] and len(stale) == 1

    def test_shrink_never_grows(self):
        base = Baseline([BaselineEntry.from_finding(self._finding())])
        grown_input = [self._finding(), self._finding(msg="new debt")]
        shrunk = base.shrink(grown_input)
        assert len(shrunk) == 1  # the new finding did NOT enter
        assert base.shrink([]).entries == []  # fixed findings drop out
        assert len(base.grown(grown_input)) == 2  # explicit intake only

    def test_stale_locations(self, tmp_path):
        ok = BaselineEntry("r", "real.py", 1, "m")
        gone = BaselineEntry("r", "missing.py", 1, "m")
        far = BaselineEntry("r", "real.py", 99, "m")
        (tmp_path / "real.py").write_text("x = 1\n")
        problems = Baseline([ok, gone, far]).stale_locations(str(tmp_path))
        assert len(problems) == 2
        assert any("does not exist" in p for p in problems)
        assert any("out of range" in p for p in problems)


class TestCLI:
    def _fixture_root(self, tmp_path):
        write_tree(
            str(tmp_path),
            {
                "kueue_tpu/core/x.py": (
                    "import time\n\n\ndef f():\n    return time.time()\n"
                )
            },
        )
        return str(tmp_path)

    def test_exit_2_on_findings_and_0_when_baselined(self, tmp_path, capsys):
        from kueue_tpu.analysis.__main__ import main

        root = self._fixture_root(tmp_path)
        bl = str(tmp_path / "bl.txt")
        rc = main(
            ["--root", root, "--rule", "clock-discipline",
             "--baseline", bl]
        )
        assert rc == 2
        out = capsys.readouterr().out
        assert "[clock-discipline]" in out and "1 new" in out
        # reviewed debt intake -> clean run
        rc = main(
            ["--root", root, "--rule", "clock-discipline",
             "--baseline", bl, "--update-baseline", "--allow-grow"]
        )
        assert rc == 0
        rc = main(
            ["--root", root, "--rule", "clock-discipline",
             "--baseline", bl]
        )
        assert rc == 0  # the intaken entry now suppresses the finding

    def test_update_baseline_is_shrink_only(self, tmp_path, capsys):
        from kueue_tpu.analysis.__main__ import main

        root = self._fixture_root(tmp_path)
        bl = str(tmp_path / "bl.txt")
        main(
            ["--root", root, "--rule", "clock-discipline", "--baseline",
             bl, "--update-baseline", "--allow-grow", "-q"]
        )
        assert len(Baseline.load(bl)) == 1
        # fix the code: the entry must shrink away, plain update only
        write_tree(
            str(tmp_path), {"kueue_tpu/core/x.py": "def f():\n    pass\n"}
        )
        rc = main(
            ["--root", root, "--rule", "clock-discipline", "--baseline",
             bl]
        )
        assert rc == 2  # stale entry: the ratchet demands a shrink
        rc = main(
            ["--root", root, "--rule", "clock-discipline", "--baseline",
             bl, "--update-baseline", "-q"]
        )
        assert rc == 0
        assert len(Baseline.load(bl)) == 0

    def test_list_rules(self, capsys):
        from kueue_tpu.analysis.__main__ import main

        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for name in rule_names():
            assert name in out


# ---- the package gate (tier-1 acceptance) ----
class TestPackageGate:
    def test_full_suite_clean_modulo_baseline(self):
        """`python -m kueue_tpu.analysis` exits 0 over the tree: every
        finding is either fixed or a justified baseline entry."""
        offenders = lint()
        assert offenders == [], "\n".join(str(f) for f in offenders)

    def test_baseline_entries_resolve_and_match(self):
        """Stale-baseline check: every checked-in entry points at a
        real file:line AND matches a current finding (shrink-only —
        fixed findings must leave the baseline)."""
        baseline = Baseline.load(DEFAULT_BASELINE_PATH)
        problems = baseline.stale_locations(repo_root())
        assert problems == [], "\n".join(problems)
        findings = run_analysis(repo_root())
        _new, _suppressed, stale = baseline.split(findings)
        assert stale == [], (
            "baseline entries with no matching finding (run "
            "--update-baseline):\n"
            + "\n".join(e.format() for e in stale)
        )

    def test_cli_exit_zero_over_the_tree(self, capsys):
        from kueue_tpu.analysis.__main__ import main

        assert main([]) == 0
        assert "kueuelint:" in capsys.readouterr().out
