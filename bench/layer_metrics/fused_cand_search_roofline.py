"""Kernels (``kernels/ops.py``): the gathered fused search kernel's share
of the chip's roofline, in percent.  The least time the chip could take
for the window's calls (for each, the larger of the bytes the algorithm
needs over HBM bandwidth and its operations over peak FLOP/s; from
``bench/work/fused_cand_search.py`` at the dispatched batch shape) over
the kernel's device time in the trace.  Memory bounds it at these
shapes.  Moves ``qps``.  A trace without the kernel reads nothing."""

KERNEL = "fused_cand_search"


def read(ctx):
    if ctx.device is None or not ctx.peaks:
        return None
    t_kernel = ctx.device.kernel_s(KERNEL)
    if t_kernel <= 0:
        return None
    idx = ctx.config["index"]
    work = ctx.work(KERNEL)
    least = 0.0
    for span in ctx.batches():
        w = work.call(int(span.args["shape"]), L=idx["L"],
                      Ct=idx["max_blocks"] * idx["block_size"],
                      d=ctx.config["data"]["d"], K=idx["K"],
                      steps=ctx.config["service"]["steps"], k=idx["k"])
        least += max(w["bytes"] / ctx.peaks["hbm_bytes_per_s"],
                     w["flops"] / ctx.peaks["flops_per_s"])
    return 100.0 * least / t_kernel
