"""A cell, found by its name: ``BENCHMARK.json`` names its configuration and
traffic; ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``
and ``bench/workloads/<cell>.json`` hold them."""

from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: dict        # the configuration file
    traffic: dict       # the traffic file
    workload: dict      # the cell's deployment settings and limits
    chips: int
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Cell:
    m = manifest()
    entry = next((w for w in m["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in m["configs"] if c["name"] == entry["config"])
    e2e = [x for x in m["end_to_end"] if name in x.get("workloads", [name])]
    names = {x["name"] for x in e2e}
    layer = [x for x in m["per_layer"]
             if (name in x["workloads"] if "workloads" in x else x["moves"] in names)]
    return Cell(name=name, config=_json(ROOT / conf["file"]),
                traffic=_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                workload=_json(BENCH / "workloads" / f"{name}.json"),
                chips=entry["chips"], end_to_end=e2e, per_layer=layer)


def generator(cell: Cell, seed: int):
    """The traffic file's generator (``bench/traffic/<generator>.py``)."""
    kind = cell.traffic["generator"]
    mod = importlib.import_module(f"bench.traffic.{kind}")
    cls = getattr(mod, kind.capitalize())
    return cls(cell.traffic, seed, cell.config["vocab_size"])


def program_config(c: dict, options: dict | None = None):
    """The program's ``ModelConfig`` for the configuration file ``c``: the
    registry entry at ``c["num_hidden_layers"]`` layers, with the cell's
    program ``options`` (``scan_layers``, ``remat``), refused if any width
    differs from the file."""
    from repro.configs import get_config

    cfg = get_config(c["registry"], smoke=c.get("registry_smoke", False))
    opts = {"scan_layers": True, **(options or {})}
    cfg = dataclasses.replace(cfg, num_layers=c["num_hidden_layers"], **opts)
    act = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}[c["hidden_act"]]
    want = {"d_model": c["hidden_size"], "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"], "head_dim_": c["head_dim"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "padded_vocab": c["vocab_size"], "mlp_activation": act,
            "norm_type": c["norm_type"], "norm_eps": c["norm_eps"],
            "rope_theta": c["rope_theta"], "rope_type": "rope",
            "tie_embeddings": c["tie_word_embeddings"], "dtype": c["torch_dtype"]}
    bad = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if bad or any(b.mixer != "attn" or b.mlp != "dense" for b in cfg.pattern):
        raise ValueError(f"{c['registry']}: the program's config differs from "
                         f"the configuration file: {bad}")
    return cfg
