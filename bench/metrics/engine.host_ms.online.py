from bench.metrics._spans import host_ms, spans


def read(rec):
    """Median ms of an ``engine.segment`` span that its ``runtime.scan`` and
    ``engine.prefill`` spans leave uncovered: the engine's host work."""
    return host_ms(spans(rec), "engine.segment", ("runtime.scan", "engine.prefill"))
