"""Spill inside the tick: the window's ``sketch.spill`` spans
(``SketchService.stats['spill_ns']``: the scan for idle tenants, the
reads of each idle tenant's rows and the dispatch that clears them) per
tick of the window, in milliseconds. None where the configuration never
spills, or where the program records no such span."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("spill_n", 0) == s0.get("spill_n", 0):
        return None
    return (s1["spill_ns"] - s0["spill_ns"]) / (
        s1["ticks"] - s0["ticks"]) * 1e-6
