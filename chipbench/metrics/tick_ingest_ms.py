"""The tick's ingest stage: the window's ``sketch.ingest`` spans
(``SketchService.stats['ingest_ns']``: coalescing the pending updates
with their window expiries, cutting and padding blocks, staging and
dispatching them, and the flush that waits for the device) per tick of
the window, in milliseconds. None where the program records no such
span."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("ingest_n", 0) == s0.get("ingest_n", 0):
        return None
    return (s1["ingest_ns"] - s0["ingest_ns"]) / (
        s1["ticks"] - s0["ticks"]) * 1e-6
