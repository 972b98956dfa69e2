"""Answering inside the tick: the window's ``sketch.query`` spans (the
batched point query and its read) plus its ``sketch.subscriptions``
spans (the due top-k subscriptions and their reads), from
``SketchService.stats``, per tick of the window, in milliseconds. None
where neither ran, or where the program records no such span."""

SPANS = ("query", "subscriptions")


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if all(s1.get(f"{s}_n", 0) == s0.get(f"{s}_n", 0) for s in SPANS):
        return None
    ns = sum(s1[f"{s}_ns"] - s0[f"{s}_ns"] for s in SPANS)
    return ns / (s1["ticks"] - s0["ticks"]) * 1e-6
