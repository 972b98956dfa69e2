"""Host syncs per tick: the window's ``sketch.wait`` spans counted
(``SketchService.stats['wait_n']``), per tick of the window. None where
the program counts none."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("wait_n", 0) == s0.get("wait_n", 0):
        return None
    return (s1["wait_n"] - s0["wait_n"]) / (s1["ticks"] - s0["ticks"])
