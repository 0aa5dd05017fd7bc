from bench.metrics._spans import host_ms, spans


def read(rec):
    """Median ms of a ``runtime.dispatch`` span that its ``runtime.scan``
    leaves uncovered: staging, the health check, draining and recording."""
    return host_ms(spans(rec), "runtime.dispatch", ("runtime.scan",))
