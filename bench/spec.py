"""A cell, found by its name: ``BENCHMARK.json`` names its configuration and
traffic; ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``
and ``bench/workloads/<cell>.json`` hold them. The configuration file names
its family (``bench/families/<family>.py``), the workload file its driver
(``bench/drivers/<driver>.py``); a per-layer metric ``<kernel>_roofline.*``
reads the kernel whose cost is ``bench/kernels/<kernel>.py``."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pkgutil
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
ROOFLINE = "_roofline"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict        # the configuration file
    traffic: dict       # the traffic file
    workload: dict      # the cell's deployment settings and limits
    chips: int
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list
    family: ModuleType  # bench/families/<config's family>.py
    driver: ModuleType  # bench/drivers/<workload's driver>.py


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def module(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py``, imported by name; an error that names the
    file where there is none."""
    full = f"bench.{kind}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        where = Path(importlib.import_module(f"bench.{kind}").__path__[0]) / f"{name}.py"
        raise FileNotFoundError(f"no {name!r} in bench/{kind}: {where} does not exist") from None


def kernels() -> dict[str, ModuleType]:
    """Every kernel cost file present, by kernel name."""
    pkg = importlib.import_module("bench.kernels")
    return {m.name: module("kernels", m.name)
            for m in pkgutil.iter_modules(pkg.__path__) if not m.name.startswith("_")}


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
    config = _json(ROOT / conf["file"])
    workload = _json(BENCH / "workloads" / f"{name}.json")
    for x in layer:
        kernel, found, _ = x["name"].partition(ROOFLINE)
        if found:
            module("kernels", kernel)
    return Cell(name=name, config=config,
                traffic=_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                workload=workload, chips=entry["chips"], end_to_end=e2e, per_layer=layer,
                family=module("families", config["family"]),
                driver=module("drivers", workload["driver"]))


def generator(cell: Cell, seed: int):
    """The traffic file's generator (``bench/traffic/<generator>.py``)."""
    kind = cell.traffic["generator"]
    mod = importlib.import_module(f"bench.traffic.{kind}")
    cls = getattr(mod, kind.capitalize())
    return cls(cell.traffic, seed, cell.config["vocab_size"])
