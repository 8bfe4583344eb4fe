"""Fixtures for the benchmark's own tests (run by path: ``pytest bench/tests``).

``tiny_root`` builds a checkout of its own in a temporary directory: the
repository's ``BENCHMARK.json`` and ``bench/`` plus one added configuration,
traffic and cell at a size the CPU runs in seconds, so that the tests also
show that a cell is added by adding files.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

TINY_CELL = "tiny.cell"


def make_tiny_root(tmp, arch="olmo-1b", layout=None, chips=1,
                   limits=None) -> str:
    """A checkout in ``tmp`` holding the benchmark plus a tiny cell of
    ``arch``'s family: config ``tiny``, traffic ``tiny``, cell
    ``tiny.cell``."""
    tmp = str(tmp)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "bench", "configs", arch + ".json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, vocab_size=512)
    if cfg.get("qk_norm"):            # GQA, splittable over four devices
        cfg.update(num_attention_heads=8, num_key_value_heads=4, head_dim=16)
    with open(os.path.join(REPO, "bench", "traffic", "s32k.json")) as f:
        traffic = json.load(f)
    traffic["seq_len"] = 256
    if layout:
        traffic["layout"] = layout
    check = {"check": {"steps": 3, "limits": limits or {
        "loss": 1e-3, "grad": 0.05, "change": 0.05, "data": 0}}}
    for sub, obj in (("configs/tiny.json", cfg), ("traffic/tiny.json", traffic),
                     (f"workloads/{TINY_CELL}.json", check)):
        with open(os.path.join(tmp, "bench", sub), "w") as f:
            json.dump(obj, f)
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": "tiny", "chips": chips,
                               "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append(TINY_CELL)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
