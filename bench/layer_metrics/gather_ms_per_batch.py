"""Search step (``core/serve_search.py``): device milliseconds of the ops
under the ``dblsh.gather`` scope per dispatched batch, mean over the
cell's chips.  The scope holds the one-copy layout's gathers inside
``dblsh.verify``: the selected blocks' projections, ids and norms, read
slot by slot from the stored index arrays, and the candidates' rows of
the data by id.  Moves ``qps``.  A program without the scope reads
nothing."""


def read(ctx):
    batches = len(ctx.batches())
    if ctx.device is None or not batches:
        return None
    t = ctx.device.scope_s("dblsh.gather")
    return t * 1e3 / batches if t > 0 else None
