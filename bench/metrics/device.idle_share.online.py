from bench.metrics._shared import idle_share


def read(rec):
    return idle_share(rec, pending=True)
