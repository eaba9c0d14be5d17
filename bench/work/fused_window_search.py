"""Operations and bytes of one call of ``kernels.ops.fused_window_search``
(the inline engine's fused select-DMA + distance + schedule-binning
kernel), from its shapes.

The arithmetic is that of the fused half of ``search_cell`` in
``benchmarks/roofline.py``, copied so that the yardstick stays put:

* every query streams all S = L * M selected blocks (the kernel's grid is
  (Qn, S), and an invalid slot is routed to block 0 and still read), and
  each slot needs its projection (K float32), its vector (d elements),
  its squared norm and its id;
* each query writes ``steps`` bins of ``ks`` (distance, id) pairs and
  ``steps`` counters;
* each slot costs the halfwidth (3 operations a projected dimension) and
  the norm-form distance (2 d + 3), and each block folds into one bin by
  a ``ks``-round min-select.

These are the bytes the algorithm needs, not the bytes the kernel moves:
the kernel also reads the 8-row tile that holds a block's norms and ids,
and its projection blocks come relaid out with K padded to 128 lanes.
So the share of the roofline it gives is a floor on how far the kernel
is from the chip's limit, and it cannot pass 100 % on a correct timing.
"""

from __future__ import annotations

VEC_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def per_query(S: int, B: int, d: int, K: int, steps: int, k: int, dtype: str = "fp32") -> dict:
    """Bytes and operations one query of the kernel needs."""
    vb = VEC_BYTES[dtype]
    ks = k if dtype == "fp32" else 4 * k
    slots = S * B
    block_read = slots * (K * 4 + d * vb + 4 + 4)
    if dtype == "int8":
        block_read += slots * 4  # per-slot dequant scale
    flops = slots * (3 * K + 2 * d + 3) + S * ks * 4 * (B + ks)
    return {"bytes": block_read + steps * ks * 8 + steps * 4, "flops": flops}


def call(Qn: int, *, L: int, M: int, B: int, d: int, K: int, steps: int, k: int,
         dtype: str = "fp32") -> dict:
    """Bytes and operations of one call on a batch of ``Qn`` queries."""
    q = per_query(L * M, B, d, K, steps, k, dtype)
    return {"bytes": Qn * q["bytes"], "flops": Qn * q["flops"]}
