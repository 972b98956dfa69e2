"""The engine ingest: device time of the session's compiled ingest
(``StreamSession``'s ``jit(ingest)``, which runs ``bank._fused_touched``
on a tenant bank) per execution, one execution per block, from the
device trace's program events."""

PROGRAMS = ("ingest",)


def read(run):
    if run.trace is None:
        return None
    s, n = run.trace.program_s(PROGRAMS)
    return s / n * 1e3 if n else None
