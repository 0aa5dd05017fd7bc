import numpy as np

from bench.metrics._spans import say, spans


def read(rec):
    """90th percentile, in ms, of the queue wait (``queued_s``) of the
    requests whose ``engine.join`` began in the traced part."""
    waits = [float(s.args["queued_s"]) for s in spans(rec) or []
             if s.name == "engine.join" and "queued_s" in s.args]
    say(f"engine.queue_wait_p90_ms.online over {len(waits)} joins")
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
