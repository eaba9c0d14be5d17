"""Search step dispatch (``store/collection.py``, ``store/router.py``):
host milliseconds per dispatched batch in the program's ``issue.upload``
spans (the padded query matrix converted onto the device, a child of
``batch.issue``), summed over the window and divided by the batches
issued in it.  Moves ``qps`` in the closed loops, where the host's
share of each batch is time the device may wait.  A program without the
span reads nothing."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "issue.upload"]
    batches = len(ctx.batches())
    if not spans or not batches:
        return None
    return sum(s.dur for s in spans) * 1e3 / batches
