"""Admission (``store/service.py``): host milliseconds per dispatched
batch, the program's ``batch.assemble`` + ``batch.issue`` spans summed
over the window and divided by the batches issued in it.  Moves ``qps``
in the closed loops, where the host's share of each batch is time the
device may wait."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name in ("batch.assemble", "batch.issue")]
    batches = len(ctx.batches())
    if not batches:
        return None
    return sum(s.dur for s in spans) * 1e3 / batches
