"""BENCHMARK.json's names and units, and every file it names found by name."""
import json
import os

import pytest

from bench.check import NUMBERS
from bench.manifest import NAME, UNIT, Manifest

from conftest import REPO, TINY_CELL

MAN = Manifest(REPO)


def test_names_and_units():
    d = MAN.data
    names = [c["name"] for c in d["configs"]] + \
        [w["name"] for w in d["workloads"]] + \
        [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    names += [w["traffic"] for w in d["workloads"]]
    names += [k for c in d["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) >= len(d["configs"]) + len(d["workloads"])
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in d["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in MAN.data["workloads"]])
def test_each_cell_finds_its_files(cell):
    c = MAN.cell(cell)
    config, traffic, check = MAN.config(c), MAN.traffic(c), MAN.check(c)
    lay = traffic["layout"]
    assert lay["dp"] * lay["hp"] * lay["cp_outer"] * lay["cp_inner"] \
        == c["chips"]
    assert config["program_arch"]
    assert set(check["limits"]) <= set(NUMBERS)
    assert "data" in check["limits"]
    e2e = {m["name"] for m in MAN.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = MAN.per_layer(cell)
    assert per
    for m in per:
        assert m["moves"] in e2e          # what it moves is reported there
        assert callable(MAN.reader(m["name"]))


def test_config_files_are_distinct_and_under_paths():
    files = [c["file"] for c in MAN.data["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith(MAN.data["paths"][0] + "/")
        assert os.path.isfile(os.path.join(REPO, f))


def test_a_cell_config_and_metric_are_added_as_files(tiny_root):
    """A new cell, configuration, traffic and per-layer metric are new
    files and entries; no file of the harness changes."""
    with open(os.path.join(tiny_root, "bench", "metrics", "one.py"), "w") as f:
        f.write("def read(ctx):\n    return 1.0\n")
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "one", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "step",
        "moves": "tokens_per_s_per_chip", "workloads": [TINY_CELL]})
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    man = Manifest(tiny_root)
    cell = man.cell(TINY_CELL)
    assert man.config(cell)["hidden_size"] == 64
    assert man.traffic(cell)["seq_len"] == 256
    assert "one" in [m["name"] for m in man.per_layer(TINY_CELL)]
    assert man.reader("one")(None) == 1.0
