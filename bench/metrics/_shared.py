"""What the per-layer readers share. Each reader is a file of its own,
``bench/metrics/<metric>.py``, with ``read(rec) -> float | None``; ``rec`` is
the run's record (see ``bench/run.py``). A reader that finds nothing to read
returns None, and the metric is left out of the line."""

from __future__ import annotations

import numpy as np


def mean_occupancy(rec: dict):
    segs = rec.get("segments") or []
    return float(np.mean([s["occupancy"] for s in segs])) if segs else None


def median_segment_ms(rec: dict):
    segs = rec.get("segments") or []
    return 1e3 * float(np.median([s["wall"] for s in segs])) if segs else None


def idle_share(rec: dict, pending: bool = False):
    """Percent of the traced window (or, with ``pending``, of the part of it
    in which a request was queued or running) with no op on the device."""
    t = rec.get("trace")
    if not t:
        return None
    busy, span = (t["busy_pending_s"], t["pending_s"]) if pending else (t["busy_s"], t["window_s"])
    return 100.0 * (1.0 - busy / span) if span > 0 else None


def roofline(rec: dict, kernel: str):
    """Percent: the kernel's least time over its device time, summed over the
    calls in the traced window. A call's device time includes the ops that
    brought its operands into VMEM (see ``bench.trace.kernel_calls``)."""
    k = ((rec.get("trace") or {}).get("kernels") or {}).get(kernel)
    if not k or k["seconds"] <= 0:
        return None
    return 100.0 * k["least_s"] / k["seconds"]


def mfu(flops: float | None, seconds: float | None, rec: dict):
    if not flops or not seconds:
        return None
    return 100.0 * flops / seconds / rec["peak"]["flops_per_s"]
