from bench.metrics._shared import mfu


def read(rec):
    return mfu(rec.get("prefill_flops"), rec.get("prefill_s"), rec)
