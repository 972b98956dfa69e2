"""Re-admission inside the tick: the window's ``sketch.admit`` spans
(``SketchService.stats['admit_ns']``: every spilled tenant a tick's
traffic or queries touch merges back before the tick's other work) per
tick of the window, in milliseconds. None where no tick re-admitted, or
where the program records no such span."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("admit_n", 0) == s0.get("admit_n", 0):
        return None
    return (s1["admit_ns"] - s0["admit_ns"]) / (
        s1["ticks"] - s0["ticks"]) * 1e-6
