"""What a cell is made of, found by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric sits in a file of its own, so that adding a cell is adding
files and entries:

* ``chipbench/configs/<config>.json``: the model's sizes as run, with
  its source, the keys changed from it and the sizes assumed;
* ``chipbench/traffic/<traffic>.json``: the parameters the one
  generator (``generator.py``) reads;
* ``chipbench/cells/<cell>.json``: the deployment settings an operator
  pins (slots, ``max_len``, page size, prefill chunk, rate) and the
  limits of the correctness comparison;
* ``chipbench/metrics/<metric>.py``: one reader per metric, the file
  named from the metric with ``.`` written ``__``;
* ``chipbench/references/<reference>.py``: the plain reference a
  configuration names.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "chipbench")

# published (Hugging Face) key -> the program's ModelConfig field
HF_TO_PROGRAM = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "layer_norm_eps": "norm_eps",
    "hidden_act": "act",
    "torch_dtype": "param_dtype",
    "activation_dtype": "compute_dtype",
    "cache_dtype": "cache_dtype",
}


def _load_json(*parts):
    path = os.path.join(BENCH_DIR, *parts)
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name + ".json")


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", name.replace(".", "__") + ".py")


def load_reader(name: str):
    """The ``read(ctx)`` function of metric ``name``."""
    path = metric_file(name)
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "__").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name: str):
    path = os.path.join(BENCH_DIR, "references", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict          # chipbench/configs/<config>.json
    traffic: dict         # chipbench/traffic/<traffic>.json
    settings: dict        # chipbench/cells/<cell>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list

    def model_sizes(self) -> dict:
        """The config file's sizes under the program's field names."""
        return {HF_TO_PROGRAM[k]: v for k, v in self.config.items()
                if k in HF_TO_PROGRAM}


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=json.load(open(os.path.join(ROOT, cfg_entry["file"]))),
        traffic=load_traffic(w["traffic"]),
        settings=_load_json("cells", name + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])
