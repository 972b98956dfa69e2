"""The ingest's work per block: trips of the touched-rows ingest's
chunk loop (``SketchService.stats['ingest_chunks']``: a chunk of rows a
trip, one trip on every other path) per block the window fed. None where
the program counts none."""


def read(run):
    s0, s1 = run.window.stats0, run.window.stats1
    if s1.get("ingest_chunks", 0) == s0.get("ingest_chunks", 0):
        return None
    return (s1["ingest_chunks"] - s0["ingest_chunks"]) / (
        s1["blocks"] - s0["blocks"])
