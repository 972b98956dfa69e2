"""The service front end (``SketchService.submit``: key packing and
``api.validate_block``): the summed span of the window's submit calls
over the updates they carried, in microseconds per update."""


def read(run):
    w = run.window
    if not w.submit_updates:
        return None
    return w.submit_s / w.submit_updates * 1e6
