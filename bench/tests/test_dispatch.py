"""The harness finds a family, a driver and a kernel cost by name, as new
files: a toy of each, written by the test into a directory of its own that
joins the package's search path (``bench/families``, ``bench/drivers``,
``bench/kernels``), and a toy cell in a copy of the manifest, are reached by
``spec.cell``, ``run_cell``, ``reduce_trace`` and ``rehearse`` with no edit
to any file of the harness. An unknown name fails as the cell loads, naming
the file it looked for. The harness's own modules name nothing of the dense
decoder."""

from __future__ import annotations

import importlib
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

import bench.drivers
import bench.families
import bench.kernels
from bench import flops, spec

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data" / "small_trace.xplane.pb"

TOY_FAMILY = '''"""A family supplied as a new file: the dense decoder's, counting calls."""
from bench.families import dense_decoder as _dense
from bench.families.dense_decoder import *  # noqa: F401,F403

CALLS = []


def program_config(c, options=None):
    CALLS.append("program_config")
    return _dense.program_config(c, options)


def train(c, make, batches, fp8=False):
    CALLS.append("train")
    return _dense.train(c, make, batches, fp8)
'''

TOY_DRIVER = '''"""A driver supplied as a new file: the train driver's, counting calls."""
from bench.drivers import train as _train
from bench.drivers.train import *  # noqa: F401,F403

CALLS = []


def build(cell, seed, tmp):
    CALLS.append("build")
    return _train.build(cell, seed, tmp)


def check(cell, seed, rec, warmed, control=False):
    CALLS.append("check")
    return _train.check(cell, seed, rec, warmed, control)


def rehearse(cell, place, report):
    CALLS.append(("rehearse", cell.name, place(_train.np.zeros((2, 3), "float32"))))
'''

TOY_COST = '''"""A kernel cost supplied as a new file."""


def cost(operands, c, family):
    return 1e6, 1e3 * len(operands)
'''


def write(directory: Path, name: str, text: str) -> None:
    directory.mkdir(parents=True, exist_ok=True)
    (directory / f"{name}.py").write_text(text)


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A copy of the manifest and files with one more configuration
    (``family: toy``) and cell (``toy.pretrain``, ``driver: toy``), and the
    toy modules on the packages' search paths. Gives two functions: one edits
    a file of the copy, one adds a per-layer metric to its manifest."""
    root = tmp_path / "root"
    shutil.copytree(BENCH / "configs", root / "bench" / "configs")
    shutil.copytree(BENCH / "workloads", root / "bench" / "workloads")
    shutil.copytree(BENCH / "traffic", root / "bench" / "traffic")
    m = spec.manifest()
    m["configs"].append({"name": "toy", "source": "a test", "file": "bench/configs/toy.json",
                         "reduced": [], "why": "a family supplied as a new file"})
    m["workloads"].append({"name": "toy.pretrain", "config": "toy", "traffic": "pretrain",
                           "chips": 1, "why": "a driver supplied as a new file"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "minicpm-2b.pretrain" in x.get("workloads", []):
            x["workloads"].append("toy.pretrain")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    conf = json.loads((BENCH / "configs" / "minicpm-2b-stage8.json").read_text())
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps({**conf, "family": "toy"}))
    work = json.loads((BENCH / "workloads" / "minicpm-2b.pretrain.json").read_text())
    (root / "bench" / "workloads" / "toy.pretrain.json").write_text(
        json.dumps({**work, "driver": "toy"}))
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH", root / "bench")

    extra = tmp_path / "modules"
    write(extra / "families", "toy", TOY_FAMILY)
    write(extra / "drivers", "toy", TOY_DRIVER)
    write(extra / "kernels", "toy_kernel", TOY_COST)
    for pkg in (bench.families, bench.drivers, bench.kernels):
        kind = pkg.__name__.split(".")[-1]
        monkeypatch.setattr(pkg, "__path__", [*pkg.__path__, str(extra / kind)])
    importlib.invalidate_caches()

    def edit(which: str, **keys):
        path = root / "bench" / which
        path.write_text(json.dumps({**json.loads(path.read_text()), **keys}))

    def manifest(**entry):
        m = json.loads((root / "BENCHMARK.json").read_text())
        m["per_layer"].append(entry)
        (root / "BENCHMARK.json").write_text(json.dumps(m))

    yield edit, manifest
    for name in ("bench.families.toy", "bench.drivers.toy", "bench.kernels.toy_kernel"):
        sys.modules.pop(name, None)


def test_a_supplied_family_and_driver_are_found_by_name(toy):
    cell = spec.cell("toy.pretrain")
    assert cell.family.__name__ == "bench.families.toy"
    assert cell.driver.__name__ == "bench.drivers.toy"
    assert spec.cell("minicpm-2b.pretrain").family.__name__ == "bench.families.dense_decoder"
    assert "toy_kernel" in spec.kernels()


@pytest.mark.parametrize("kind", ["families", "drivers", "kernels"])
def test_an_unknown_name_fails_at_cell_load_naming_its_file(toy, kind):
    edit, manifest = toy
    if kind == "families":
        edit("configs/toy.json", family="nonesuch")
    elif kind == "drivers":
        edit("workloads/toy.pretrain.json", driver="nonesuch")
    else:
        manifest(name="nonesuch_roofline.train", unit="%", better="higher",
                 source="device_trace", layer="kernels (kernels/)", moves="train_tok_s",
                 workloads=["toy.pretrain"])
    with pytest.raises(FileNotFoundError) as e:
        spec.cell("toy.pretrain")
    assert str(BENCH / kind / "nonesuch.py") in str(e.value)


def test_a_configuration_without_a_family_is_refused(toy):
    edit, _ = toy
    path = spec.ROOT / "bench" / "configs" / "toy.json"
    conf = json.loads(path.read_text())
    del conf["family"]
    path.write_text(json.dumps(conf))
    with pytest.raises(KeyError, match="family"):
        spec.cell("toy.pretrain")


def test_run_cell_drives_a_tiny_cell_through_the_supplied_modules(toy):
    from bench.run import run_cell
    from bench.tests import tiny

    cell = tiny.cell("toy.pretrain")
    cell.family.CALLS.clear()
    cell.driver.CALLS.clear()
    out = run_cell(cell, 2**32 + 17, 1.0, False)
    assert out["correct"] and out["attempted"] > 3 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tok_s"}
    assert cell.driver.CALLS == ["build", "check"]
    assert cell.family.CALLS == ["program_config", "train"]


def test_rehearse_reaches_the_supplied_driver(toy):
    import jax
    from jax.sharding import SingleDeviceSharding

    from bench import rehearse

    one = SingleDeviceSharding(jax.devices()[0])
    rehearse.rehearse(["toy.pretrain"], one)
    (call,) = spec.cell("toy.pretrain").driver.CALLS[-1:]
    assert call[:2] == ("rehearse", "toy.pretrain")
    assert call[2].shape == (2, 3) and call[2].sharding == one


def reduced(cell: spec.Cell) -> dict:
    from bench import peaks
    from bench.run import reduce_trace

    summary, _ = reduce_trace(str(DATA), cell, {}, peaks.PEAKS["TPU v5 lite"])
    return summary["kernels"]


def test_reduce_trace_reads_every_kernel_that_has_a_cost_file(toy):
    # the recorded trace calls both kernels twice; the toy kernel has no calls
    kernels = reduced(spec.cell("minicpm-2b.pretrain"))
    assert sorted(kernels) == ["flash_attention", "streamed_matmul"]
    assert all(k["calls"] == 2 for k in kernels.values())


def test_reduce_trace_takes_a_kernel_cost_from_its_file(tmp_path, monkeypatch):
    # only a supplied cost file for the attention kernel: the matmul, with no
    # file, is not read, and the attention's least time is the file's cost
    cell = spec.cell("minicpm-2b.pretrain")
    write(tmp_path, "flash_attention", TOY_COST)
    monkeypatch.setattr(bench.kernels, "__path__", [str(tmp_path)])
    for name in ("bench.kernels.flash_attention", "bench.kernels.streamed_matmul"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    importlib.invalidate_caches()
    from bench import peaks

    kernels = reduced(cell)
    assert list(kernels) == ["flash_attention"]
    least, _ = flops.least_seconds(1e6, 3e3, peaks.PEAKS["TPU v5 lite"])
    assert kernels["flash_attention"]["least_s"] == pytest.approx(2 * least, rel=1e-12)


DENSE = re.compile(r"\b(hidden_act|intermediate_size|wq|w_gate|num_key_value_heads)\b")
BY_KIND = re.compile(r"""==\s*["'](serve|train)["']""")


@pytest.mark.parametrize("name", ["run.py", "spec.py", "rehearse.py"])
def test_the_harness_names_nothing_of_the_dense_decoder(name):
    text = (BENCH / name).read_text()
    assert DENSE.findall(text) == []
    assert BY_KIND.findall(text) == []


def test_every_configuration_names_its_family():
    for c in spec.manifest()["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert (BENCH / "families" / f"{conf['family']}.py").is_file()
    assert all(spec.cell(w["name"]).family.__name__.startswith("bench.families.")
               for w in spec.manifest()["workloads"])
