"""The per-tenant dyadic ingest's reach: layer rows a block reaches
after its 16-fold expansion (``SketchService.stats['level_rows']``: the
layers of every tenant a block reaches) per block the window fed. None
where the program counts none."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("level_rows", 0) == s0.get("level_rows", 0):
        return None
    return (s1["level_rows"] - s0["level_rows"]) / (
        s1["blocks"] - s0["blocks"])
