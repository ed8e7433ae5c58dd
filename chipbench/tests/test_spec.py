"""BENCHMARK.json is well formed, and every cell, configuration, traffic
mix and metric it names resolves to its files by name."""
import json
import os
import re

import pytest

from chipbench import spec

B = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "chipbench/run.py"]
    assert B["paths"] == ["chipbench"]
    assert 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_names_units_and_entries():
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        names = [e["name"] for e in B[group]]
        assert len(names) == len(set(names))
        for e in B[group]:
            assert set(e) == keys
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.cell(cell)
    w = next(w for w in B["workloads"] if w["name"] == cell)
    assert w["chips"] == 1 and c.chips == 1
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for key in ("slots", "max_len", "page_size", "prefill_chunk", "ramp_s",
                "check_tokens"):
        assert key in c.settings, key
    # every compared number is one the comparison computes
    assert c.settings["limits"]
    for name, limit in c.settings["limits"].items():
        assert name in ("logit_gap", "mean_gap", "off_argmax") and limit > 0
    if c.traffic["loop"] == "open":
        assert c.settings["rate"] > 0
    # the pool holds every slot at max_len, and every request fits it
    mix = c.traffic
    longest = mix["prompt"]["max"] + (mix["output"].get("max")
                                      or mix["output"]["value"])
    assert longest <= c.settings["max_len"] + 1
    assert os.path.exists(os.path.join(spec.BENCH_DIR, "references",
                                       c.config["reference"] + ".py"))
    # setup_s, another end-to-end metric, and a per-layer metric
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_a_reader(metric):
    assert callable(spec.load_reader(metric))
    m = next(m for m in METRICS if m["name"] == metric)
    for cell in m.get("workloads", []):
        assert cell in CELLS


def test_every_reader_is_a_metric_of_the_benchmark():
    files = {f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR,
                                                     "metrics"))
             if f.endswith(".py") and not f.startswith("_")}
    assert files == {m["name"].replace(".", "__") for m in METRICS}


@pytest.mark.parametrize("entry", B["configs"], ids=lambda e: e["name"])
def test_config_file_states_its_cuts(entry):
    assert entry["file"].startswith("chipbench/configs/")
    assert entry["source"].startswith("https://")
    cfg = json.load(open(os.path.join(spec.ROOT, entry["file"])))
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert set(cfg["published"]) == set(entry["reduced"])
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank", "_size")), key
        assert cfg[key] != cfg["published"][key]
    assert any(w["config"] == entry["name"] for w in B["workloads"])


def test_layers_are_named_alike():
    layers = {m["layer"] for m in B["per_layer"]}
    for name in layers:
        assert 1 <= len(name) <= 200 and "\n" not in name
