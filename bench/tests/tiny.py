"""Cells of BENCHMARK.json shrunk to their family's smoke widths
(``smoke(c)`` of ``bench/families/<family>.py``), for runs on the CPU: every
setting of the real cell, with the model, the pool and the traffic cut to a
size a test can hold."""

from __future__ import annotations

from bench import spec


def cell(name: str, layers: int = 2) -> spec.Cell:
    c = spec.cell(name)
    c.config.update(c.family.smoke(c.config), num_hidden_layers=layers)
    w = c.workload
    if "engine" in w:
        w["engine"].update(max_lanes=4, pool_seq=64, segment_len=8)
        w["check"].update(requests=3, group=2)
        c.traffic.update(prompt_len={"ladder": [8, 16], "p": [0.5, 0.5]},
                         output_len={"dist": "uniform", "min": 4, "max": 24}, pool=64)
        if "rate_per_s" in c.traffic["arrivals"]:
            c.traffic["arrivals"]["rate_per_s"] = 20.0
    else:
        c.traffic.update(batch=2, seq_len=32, batches=8)
    return c
