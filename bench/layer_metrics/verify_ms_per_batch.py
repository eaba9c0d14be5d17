"""Search step (``core/serve_search.py``): device milliseconds of the ops
under the ``dblsh.verify`` scope (the distance kernel and, on the inline
layout, the relayout of ``proj_blocks`` it is fed) per dispatched batch,
mean over the cell's chips.  Moves ``qps``."""


def read(ctx):
    batches = len(ctx.batches())
    if ctx.device is None or not batches:
        return None
    # the verify scope, and the copies of index arrays XLA lays out
    # anew for the kernel at entry (their tf_op names the argument)
    t = ctx.device.time_s(lambda o: "/dblsh.verify/" in o.tf_op
                          or o.tf_op.startswith("index."))
    return t * 1e3 / batches if t > 0 else None
