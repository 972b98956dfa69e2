"""The engine ingest's share of its roofline: the least bytes of the
window's blocks (``least_bytes`` of the configuration's kind, under
``chipbench/kinds/``: the rows each block reaches, read and written
once, and the block itself) over the
ingest's device time at the chip's HBM bandwidth, in percent. The
ingest does no floating-point work, so bandwidth is its bound."""

PROGRAMS = ("ingest",)


def read(run):
    if run.trace is None or not run.least_bytes:
        return None
    s, n = run.trace.program_s(PROGRAMS)
    if not n:
        return None
    # the least bytes of as many blocks as the trace saw executions
    least = sum(run.least_bytes[:n])
    return least / (s * run.peaks["hbm_bytes_per_s"]) * 100.0
