"""Spill and re-admission: tenants spilled plus tenants re-admitted in
the window (``SketchService.stats``), per second of the window. Each is
a device-to-host read or a host-to-device write and a bank update inside
a tick. None where the configuration never spills."""


def read(run):
    w = run.window
    if run.config.spill_after is None:
        return None
    n = (w.stats1["spills"] - w.stats0["spills"]
         + w.stats1["admits"] - w.stats0["admits"])
    return n / w.seconds
