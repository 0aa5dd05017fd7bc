from bench.metrics._shared import mfu


def read(rec):
    return mfu(rec.get("model_flops"), rec.get("window_s"), rec)
