"""The service tick loop: the 95th percentile of the window's
``SketchService.tick`` spans (re-admission, coalescing, ingest, queries,
subscriptions and spill). A query waits for at least one tick, so this
moves ``query_p95_ms``; None where the traffic sends no point query."""
import numpy as np


def read(run):
    w = run.window
    if not w.query_lat or not w.ticks:
        return None
    return float(np.percentile([e - s for s, e in w.ticks], 95) * 1e3)
