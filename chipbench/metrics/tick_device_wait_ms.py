"""The tick's host blocked on the device: the window's ``sketch.wait``
spans (``SketchService.stats['wait_ns']``: the feeder's backpressure
and flush, the point-query and top-k reads, the spill reads) per tick
of the window, in milliseconds. Each nests in one of the stage spans.
None where the program records no such span."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("wait_n", 0) == s0.get("wait_n", 0):
        return None
    return (s1["wait_ns"] - s0["wait_ns"]) / (
        s1["ticks"] - s0["ticks"]) * 1e-6
