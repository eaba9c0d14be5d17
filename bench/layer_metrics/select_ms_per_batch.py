"""Search step (``core/serve_search.py``): device milliseconds of the ops
under the ``dblsh.select`` scope per dispatched batch, mean over the
cell's chips.  Select scans every block's bounding box, so it grows with
the rows a chip holds.  Moves ``qps``."""


def read(ctx):
    batches = len(ctx.batches())
    if ctx.device is None or not batches:
        return None
    t = ctx.device.scope_s("dblsh.select")
    return t * 1e3 / batches if t > 0 else None
