"""How late the open-loop driver submitted: the 95th percentile, over the
operations submitted in the window, of submit time less due time (host
clock). A late driver delays every later operation, so this is the
client side's share of ``query_p95_ms``. None for saturated traffic,
which has no due times."""
import numpy as np


def read(run):
    if not run.window.lags:
        return None
    return float(np.percentile(run.window.lags, 95) * 1e3)
