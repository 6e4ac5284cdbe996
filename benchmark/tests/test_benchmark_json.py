"""`BENCHMARK.json` names only files that exist, and says of each metric
what its reader says."""
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def exists(*parts):
    return os.path.exists(os.path.join(ROOT, *parts))


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmark"] and exists("benchmark", "run.py")
    assert SPEC["command"][-1] == "benchmark.run"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_every_configuration_has_its_file_and_a_cell():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("benchmark/") and exists(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]


def test_every_cell_has_its_traffic_its_driver_and_its_metrics():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 4)
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert exists("benchmark", "traffic", w["traffic"] + ".json")
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert exists("benchmark", "drivers", kind + ".py")
        mine = lambda m: w["name"] in m.get("workloads", [w["name"]])  # noqa
        e2e = [m["name"] for m in SPEC["end_to_end"] if mine(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in SPEC["per_layer"] if mine(m)]
        assert layer
        for m in layer:   # reported only where the metric it moves is
            assert m["moves"] in e2e, (w["name"], m["name"], m["moves"])


@pytest.mark.parametrize("m", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    if m["name"] != "setup_s":      # the harness's own clock
        mod = importlib.import_module("benchmark.end_to_end." + m["name"])
        assert mod.UNIT == m["unit"] and callable(mod.read)


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    mod = importlib.import_module(
        "benchmark.layer_metrics." + m["name"].split(".")[0])
    assert (mod.LAYER, mod.UNIT, mod.SOURCE) == (
        m["layer"], m["unit"], m["source"])
    assert callable(mod.read)
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in SPEC["workloads"]}


def test_kernels_and_peaks_are_data():
    for name in ("flash_forward", "flash_backward", "flash_sharded",
                 "paged_attention"):
        with open(os.path.join(ROOT, "benchmark", "kernels",
                               name + ".json")) as f:
            re.compile(json.load(f)["pattern"])
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["flops_bf16"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
