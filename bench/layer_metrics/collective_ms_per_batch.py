"""Sharded placement (``core/distributed.py``): device milliseconds of the
collective ops (all-gather, all-reduce, all-to-all, collective-permute,
reduce-scatter, by XLA's op category) per dispatched batch, mean over
the cell's chips: the global top-k merge across chips and the psum /
pmax of the per-query statistics.  Moves ``qps``."""


def read(ctx):
    batches = len(ctx.batches())
    if ctx.device is None or not batches:
        return None
    t = ctx.device.collective_s()
    return t * 1e3 / batches if t > 0 else None
