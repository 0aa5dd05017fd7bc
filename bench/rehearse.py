"""Compile each cell's programs at their real size for a described TPU v5e,
without the chip, and print what ``memory_analysis()`` says of each.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

A script to run by hand before a chip run, not a test. For a serve cell it
compiles the engine's segment program and the prefill at every prompt
length of the ladder (one chunk of the whole prompt: the largest block the
engine can choose); for a train cell, the train segment. The model code
takes its TPU branch (the Pallas kernels) because ``jax.default_backend``
is made to say ``tpu`` here, in this script only. Nothing runs, so nothing
here is a time.
"""

from __future__ import annotations

import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import serve, spec, train  # noqa: E402

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


def serve_cell(cell, one) -> None:
    from repro.launch.serve import make_prefill
    from repro.models import model as M

    c, e = cell.config, cell.workload["engine"]
    cfg = spec.program_config(c, cell.workload.get("program"))
    params = shapes(jax.eval_shape(lambda: serve.weights.to_program(
        c, serve.weights.make(c, 0, c["torch_dtype"]), cfg.scan_layers)), one)
    from repro.launch.engine import ServeEngine

    eng = ServeEngine(cfg, params, max_lanes=e["max_lanes"], pool_seq=e["pool_seq"],
                      segment_len=e["segment_len"])
    runner = eng._runner
    prog = runner._compiled_cache[eng.segment_len]
    state = (eng._logits, eng.pool.cache, eng._keys, np.asarray(eng._active))
    stacked = [[s.as_stacked() for s in ss] for ss in runner._streams]
    out_bufs = [[s.as_stacked() for s in outs] for outs in runner._out_streams]
    report(f"{cell.name} segment ({e['max_lanes']} lanes x {e['pool_seq']}, "
           f"{eng.segment_len} steps)",
           prog._call.lower(shapes(state, one), shapes(out_bufs, one),
                            shapes(stacked, one), params).compile())
    del eng
    cache = shapes(jax.eval_shape(lambda: M.init_cache(cfg, 1, e["pool_seq"])), one)
    for plen in cell.traffic["prompt_len"]["ladder"]:
        prompt = jax.ShapeDtypeStruct((1, plen), np.int32, sharding=one)
        report(f"{cell.name} prefill {plen} (block {plen})",
               make_prefill(cfg, plen).lower(params, cache, prompt).compile())


def train_cell(cell, one) -> None:
    with tempfile.TemporaryDirectory() as tmp:
        tr = train.Trainer(cell, 0, os.path.join(tmp, "tokens.u32"), abstract=True)
        runner = tr.runner
        prog = runner.compile(tr.steps)
        stacked = [[s.as_stacked() for s in ss] for ss in runner._streams]
        out_bufs = [[s.as_stacked() for s in outs] for outs in runner._out_streams]
        report(f"{cell.name} train segment ({tr.steps} step, batch "
               f"{tr.gen.batch} x {tr.gen.seq_len})",
               prog._call.lower(shapes(tr.state, one), shapes(out_bufs, one),
                                shapes(stacked, one), None).compile())


def main(argv: list[str]) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    names = argv or [w["name"] for w in spec.manifest()["workloads"]]
    for name in names:
        cell = spec.cell(name)
        (serve_cell if cell.workload["driver"] == "serve" else train_cell)(cell, one)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
