from bench.metrics._shared import mean_occupancy


def read(rec):
    return mean_occupancy(rec)
