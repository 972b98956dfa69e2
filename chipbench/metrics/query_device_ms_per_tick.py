"""The query layer: device time of the batched point query
(``tenant.query_many_tenant``, the owner-row gather) and the batched
top-k (``tenant.topk_tenants``), per tick of the window, from the
device trace. None where the traffic sends no point query."""

PROGRAMS = ("query_many_tenant", "topk_tenants")


def read(run):
    if run.trace is None or not run.window.query_lat or not run.window.ticks:
        return None
    s, n = run.trace.program_s(PROGRAMS)
    return s / len(run.window.ticks) * 1e3 if n else None
