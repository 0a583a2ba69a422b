"""Resolve a cell of ``BENCHMARK.json`` to its configuration, traffic and readers.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``: sizes (the published ``config.json``
  keys), arithmetic, the reference's module, the correctness limits;
* ``bench/traffic/<traffic>.json``: the mix, read by ``bench/traffic.py``;
* ``bench/metrics/<metric>.py``: a ``read(ctx)`` that returns a number
  or None;
* ``bench/references/<reference>.py``: the plain reference forward.

A new cell or metric is new files plus entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

__all__ = ["Cell", "load_benchmark", "resolve_cell", "load_module",
           "model_config", "BENCH_DIR", "ROOT"]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file's contents
    traffic_name: str
    traffic: dict         # the traffic file's contents
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Sizes come from the published ``config.json`` keys the file holds;
    arithmetic and the backend pinned at every site from its
    ``arithmetic`` section.
    """
    from repro.configs.base import ApproxConfig, ModelConfig

    ar = config["arithmetic"]
    approx = ApproxConfig(
        mul_scheme=ar["mul_scheme"], div_scheme=ar["div_scheme"],
        on_mlp=ar["on_mlp"], on_attn_proj=ar["on_attn_proj"],
        on_logits=ar["on_logits"], on_softmax=ar["on_softmax"],
        on_norm=ar["on_norm"], backends=ar["backend"])
    d, h = config["hidden_size"], config["num_attention_heads"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=d // h, act=config["hidden_act"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=config["dtype"], param_dtype=config["param_dtype"],
        approx=approx)
