"""Compile each cell's programs at their real size for a described TPU v5e,
without the chip, and print what ``memory_analysis()`` says of each.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

A script to run by hand before a chip run, not a test. Each cell's driver
(``bench/drivers``) compiles what its window runs: for a serve cell the
engine's segment program and the prefill at every prompt length of the
ladder, for a train cell the train segment. The model code
takes its TPU branch (the Pallas kernels) because ``jax.default_backend``
is made to say ``tpu`` here, in this script only. Nothing runs, so nothing
here is a time.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import spec  # noqa: E402

GB = 1e9


def shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding), tree)


def report(what: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{what}: arguments {m.argument_size_in_bytes / GB:.3f} GB, outputs "
          f"{m.output_size_in_bytes / GB:.3f} GB, aliased {m.alias_size_in_bytes / GB:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / GB:.3f} GB; total {total / GB:.3f} GB",
          flush=True)


def rehearse(names: list[str], one) -> None:
    """Each cell's driver compiles its programs with every array placed on
    ``one``, the described device's sharding."""
    for name in names:
        cell = spec.cell(name)
        cell.driver.rehearse(cell, lambda tree: shapes(tree, one), report)


def main(argv: list[str]) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    rehearse(argv or [w["name"] for w in spec.manifest()["workloads"]], one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
