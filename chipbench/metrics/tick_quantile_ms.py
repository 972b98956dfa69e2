"""The quantile subscriptions inside the tick: the window's
``sketch.quantiles`` spans (``SketchService.stats['quantiles_ns']``:
one batched search over every due subscription and its one read) per
tick of the window, in milliseconds. None where the program records no
such span."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("quantiles_n", 0) == s0.get("quantiles_n", 0):
        return None
    return (s1["quantiles_ns"] - s0["quantiles_ns"]) / (
        s1["ticks"] - s0["ticks"]) * 1e-6
