"""The control, kept at a size a test can hold: the reference with the
cohort's pool ignored, in the program's place, must come out not
correct in every cell; the intact reference in the same place must
come out correct."""

import pytest

from bench_tiny import tiny_registry
from benchmark.control import ReferenceSystem, run_control
from benchmark.registry import Registry

CELLS = [w["name"] for w in Registry().manifest()["workloads"]]


@pytest.mark.parametrize("seed", [1, 2**31 + 3, 2**32 + 9])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, seed, tmp_path):
    out = run_control(cell, seed, 2, registry=tiny_registry(tmp_path))
    assert out["mismatched_admissions"] > 0, out


@pytest.mark.parametrize("cell", CELLS)
def test_intact_reference_in_the_programs_place_is_correct(cell, tmp_path):
    reg = tiny_registry(tmp_path)
    c = reg.cell(cell)
    loops = reg.module("loops", c["traffic"]["loop"])
    refs = reg.module("references", c["config"]["reference"])
    loop = loops.PassLoop(c["config"], c["traffic"], 5,
                          reg.module("generators", c["config"]["generator"]))
    system = ReferenceSystem(refs.Reference())
    loop.load(system)
    for _ in range(3):
        loop.one_pass(system)
    compared = loops.replay(refs.Reference(), loop.log)
    assert len(compared) == len(loop.log)
    assert all(x["mismatched"] == 0 for x in compared)
