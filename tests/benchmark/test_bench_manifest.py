"""BENCHMARK.json and the parts it names: every part is a file of its
own, found by name, and a part added in another directory is picked
up with no edit to an existing file."""

import json
import os
import re

import pytest

from benchmark.registry import CHECKOUT, HERE, NotFound, Registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keys_and_names(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= manifest["run_seconds"] <= 51
    for p in manifest["paths"]:
        assert os.path.isdir(os.path.join(CHECKOUT, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in manifest[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])
    for text in [w["why"] for w in manifest["workloads"]] + [
        c["why"] for c in manifest["configs"]
    ]:
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_every_named_part_is_found(manifest):
    reg = Registry()
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for c in manifest["configs"]:
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = reg.data("configs", c["name"])
        reg.module("generators", cfg["generator"])
        reg.module("references", cfg["reference"])
    for w in manifest["workloads"]:
        cell = reg.cell(w["name"])
        reg.module("loops", cell["traffic"]["loop"])
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        assert cell["per_layer"], w["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        assert callable(reg.module("metrics", m["name"]).read)
    for m in manifest["end_to_end"]:
        assert callable(reg.module("metrics", m["name"]).read)


def test_a_part_added_elsewhere_is_picked_up(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "added_metric.py").write_text(
        "def read(record):\n    return len(record['passes'])\n"
    )
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "added_mix.json").write_text(
        json.dumps({"loop": "passes", "warmup_passes": 2})
    )
    with pytest.raises(NotFound):
        Registry().module("metrics", "added_metric")
    reg = Registry(roots=[str(tmp_path), HERE])
    assert reg.module("metrics", "added_metric").read({"passes": [1, 2]}) == 2
    assert reg.data("traffic", "added_mix")["warmup_passes"] == 2
    # the benchmark's own parts are still found behind the added root
    assert reg.data("traffic", "fresh")["loop"] == "passes"


def test_names_that_leave_the_directory_are_refused():
    with pytest.raises(NotFound):
        Registry().data("configs", "../BENCHMARK")


def test_peaks_table_names_its_source_and_the_v5e():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
