"""Bench compact-line schema lint (over the stage registry): every
``bench.py`` invocation's final compact JSON line must carry
``headline_ms`` + ``device`` — the ``BENCH_*.json`` contract the
growth driver tail-parses — so ``--serve`` and future stages cannot
silently drift from it. The stage registry, the single-stage CLI
modes, the headline promotion and the compact-line builder are
exercised on synthetic records; the driver's no-fallback contract on
stubbed stages and, once, on a real CPU-only child. No benchmark runs.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_module", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCompactLineContract:
    def test_every_single_stage_mode_names_a_registered_stage(self, bench):
        for flag, stages in bench.SINGLE_STAGE_MODES.items():
            assert stages, f"{flag} runs no stages"
            for name in stages:
                assert name in bench.STAGES, (
                    f"{flag} names unregistered stage {name!r}"
                )
            # a one-stage mode must be able to promote its metric to
            # the headline slot, or its compact line ships headline_ms
            # null on a SUCCESSFUL run
            assert stages[0] in bench.HEADLINE_FALLBACK_STAGES, (
                f"{flag}'s stage {stages[0]!r} has no headline fallback"
            )

    def test_fallback_stages_are_registered(self, bench):
        for name in bench.HEADLINE_FALLBACK_STAGES:
            assert name in bench.STAGES

    def test_compact_line_always_has_headline_and_device(self, bench):
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        # full run: the headline stage supplies value directly
        record = bench.finalize_headline({"value": 47.1, "device": device})
        compact = bench.compact_line(record)
        assert compact["headline_ms"] == 47.1
        assert compact["device"] == device
        # each single-stage mode: the stage's *_value triple promotes
        for flag, stages in bench.SINGLE_STAGE_MODES.items():
            name = stages[0]
            record = bench.finalize_headline(
                {
                    f"{name}_value": 12.5,
                    f"{name}_metric": f"{name} metric",
                    f"{name}_unit": "ms",
                    "device": device,
                }
            )
            compact = bench.compact_line(record)
            assert set(compact) >= {"headline_ms", "device"}, flag
            assert compact["headline_ms"] == 12.5, (
                f"{flag}: stage value did not promote to headline_ms"
            )
            assert compact["device"] == device
        # total failure still yields the contract keys (value None)
        compact = bench.compact_line(bench.finalize_headline({}))
        assert set(compact) >= {"headline_ms", "device"}

    def test_compact_extras_reference_known_keys(self, bench):
        # every extra source key is produced by some stage's record —
        # approximated by requiring the stage-name prefix convention
        prefixes = tuple(bench.STAGES) + ("serve",)
        for src, dst in bench.COMPACT_EXTRAS:
            assert any(src.startswith(p) for p in prefixes), src
            assert dst
        # the --serve contract keys specifically
        record = bench.finalize_headline(
            {
                "serve_value": 9.9,
                "serve_unit": "ms",
                "serve_admissions_per_s": 50.0,
                "serve_read_qps": 1000.0,
                "serve_max_lag_s": 0.1,
                "device": {"platform": "tpu", "kind": "v5e", "count": 1},
            }
        )
        compact = bench.compact_line(record)
        assert set(compact) >= {
            "headline_ms", "device", "admissions_per_s", "read_qps",
            "max_lag_s",
        }


class TestNoFallback:
    """A stage that fails fails the run: nothing is re-run elsewhere and
    no record carries a fallback label."""

    def _drive(self, bench, capsys, outcomes):
        rc = bench.driver_main(list(outcomes), run_stage=outcomes.get)
        lines = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(lines[-2]), json.loads(lines[-1])

    def test_failed_stage_exits_nonzero(self, bench, capsys):
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        rc, record, compact = self._drive(bench, capsys, {
            "policy": ({"policy_value": 3.0, "device": dict(device)}, None),
            "planner": (None, "stage timed out after 600s"),
        })
        assert rc == 1
        assert record["errors"] == {"planner": "stage timed out after 600s"}
        assert record["device"] == compact["device"] == device
        assert "backend" not in record and "backend" not in compact

    def test_every_stage_failing_names_no_device(self, bench, capsys):
        rc, record, compact = self._drive(bench, capsys, {
            "policy": (None, "bench measures the chip; JAX found 'cpu'"),
        })
        assert rc == 1
        assert compact == {"headline_ms": None, "device": None}
        assert "fallback" not in json.dumps(record)

    def test_stages_on_different_devices_fail(self, bench, capsys):
        one = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        rc, record, _ = self._drive(bench, capsys, {
            "policy": ({"policy_value": 1.0, "device": one}, None),
            "planner": ({"planner_value": 2.0,
                         "device": dict(one, count=4)}, None),
        })
        assert rc == 1 and "device" in record["errors"]

    def test_driver_never_imports_jax(self):
        """The parent stays off JAX, so only one stage child at a time
        holds the chip."""
        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('b', {_BENCH!r})\n"
            "spec.loader.exec_module(importlib.util.module_from_spec(spec))\n"
            "print('jax' in sys.modules)\n"
        )
        p = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
        )
        assert p.stdout.strip() == "False", p.stderr

    def test_bench_refuses_the_cpu(self):
        p = subprocess.run(
            [sys.executable, _BENCH, "--policy"], capture_output=True,
            text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert p.returncode != 0
        compact = json.loads(p.stdout.strip().splitlines()[-1])
        assert compact == {"headline_ms": None, "device": None}
