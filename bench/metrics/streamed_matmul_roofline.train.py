from bench.metrics._shared import roofline


def read(rec):
    return roofline(rec, "streamed_matmul")
