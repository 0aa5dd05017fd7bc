"""Cells of BENCHMARK.json shrunk to the program's smoke widths, for runs on
the CPU: every setting of the real cell, with the model, the pool and the
traffic cut to a size a test can hold."""

from __future__ import annotations

import copy

from bench import spec

SMOKE = {
    "minicpm-2b": dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                       head_dim=16, intermediate_size=128, vocab_size=256),
    "starcoder2-15b": dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
                           head_dim=8, intermediate_size=128, vocab_size=256),
}


def cell(name: str, layers: int = 2) -> spec.Cell:
    c = copy.deepcopy(spec.cell(name))
    c.config.update(SMOKE[c.config["registry"]], num_hidden_layers=layers,
                    registry_smoke=True)
    w = c.workload
    if w["driver"] == "serve":
        w["engine"].update(max_lanes=4, pool_seq=64, segment_len=8)
        w["check"].update(requests=3, group=2)
        c.traffic.update(prompt_len={"ladder": [8, 16], "p": [0.5, 0.5]},
                         output_len={"dist": "uniform", "min": 4, "max": 24}, pool=64)
        if "rate_per_s" in c.traffic["arrivals"]:
            c.traffic["arrivals"]["rate_per_s"] = 20.0
    else:
        c.traffic.update(batch=2, seq_len=32, batches=8)
    return c
