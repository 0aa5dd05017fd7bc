from bench.metrics._shared import median_segment_ms


def read(rec):
    return median_segment_ms(rec)
