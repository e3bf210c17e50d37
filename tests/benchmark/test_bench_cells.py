"""Each cell's harness, end to end at a size a test can hold, on the
CPU: set-up, the window, the traced reduction's readers and the check
against the plain reference. Only the look for a chip is skipped."""

import json

import pytest

from bench_tiny import tiny_registry
from benchmark.harness import run_cell
from benchmark.registry import Registry

CELLS = [w["name"] for w in Registry().manifest()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell, tmp_path):
    reg = tiny_registry(tmp_path)
    res = run_cell(cell, 2**31 + 7, 0.5, False, registry=reg, require_tpu=False)
    json.dumps(res)  # the result line is plain JSON
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"mismatched_admissions"}
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    e2e = {m["name"] for m in reg.cell(cell)["end_to_end"]}
    assert set(res["metrics"]) == e2e
    assert res["metrics"]["admissions_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0


def _classes(payload):
    """(LocalQueue, priority, cpu) of every workload of a batch."""
    return [(w["queueName"], w["priority"], w["podSets"][0]["requests"]["cpu"])
            for w in json.loads(payload)["workloads"]]


def test_same_seed_same_inputs(tmp_path):
    reg = tiny_registry(tmp_path)
    cell = reg.cell("northstar-1k.fresh")
    loops = reg.module("loops", "passes")
    gen = reg.module("generators", cell["config"]["generator"])
    a, b, c = (loops.PassLoop(cell["config"], cell["traffic"], s, gen)
               for s in (2**33 + 5, 2**33 + 5, 2**33 + 6))
    backlog = gen.backlog(cell["config"])
    one, other = a.stream.batch(backlog), c.stream.batch(backlog)
    # the same seed gives the same backlog, however the stream is cut
    parts = [b.stream.batch(backlog[:7]), b.stream.batch(backlog[7:])]
    assert _classes(one[0]) == _classes(parts[0][0]) + _classes(parts[1][0])
    assert one[1] == parts[0][1] + parts[1][1]
    # another seed asks for the same work in another order
    assert _classes(one[0]) != _classes(other[0])
    assert sorted(_classes(one[0])) == sorted(_classes(other[0]))
    per_queue = sum(s["count"] for s in cell["config"]["workloadsSets"])
    assert len(backlog) == per_queue * gen.n_queues(cell["config"])
