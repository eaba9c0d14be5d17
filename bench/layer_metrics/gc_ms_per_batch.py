"""Host runtime (the serving process's Python collector): milliseconds
per dispatched batch in the program's ``host.gc`` spans, one per
collector pass while the tracer is enabled, summed over the window and
divided by the batches issued in it.  Moves ``qps``: a pass stops the
serving loop, and the device waits for it.

A window with no pass reads 0 where the tracer records collector passes
at all, which the ``clock.sync`` records that come with them show; a
program that records neither reads nothing."""


def read(ctx):
    batches = len(ctx.batches())
    if not batches or not any(s.name == "clock.sync" for s in ctx.spans):
        return None
    return sum(s.dur for s in ctx.spans if s.name == "host.gc") * 1e3 / batches
