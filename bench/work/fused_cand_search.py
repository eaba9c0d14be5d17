"""Operations and bytes of one call of ``kernels.ops.fused_cand_search``
(the ``kernel`` engine's gathered twin of ``fused_window_search``:
halfwidth + distance + schedule binning + per-bin top-ks over
pre-gathered candidates), from its shapes.

The arithmetic follows ``bench/work/fused_window_search.py``, over the
kernel's own operands:

* every query reads its L x Ct gathered candidate slots (the kernel's
  grid is (Q, L, Ct / tile), and a slot that select left empty is still
  read), and each slot needs its projection (K float32), its vector (d
  elements), its squared norm and its id;
* each query writes ``steps`` bins of ``ks`` (distance, id) pairs and
  ``steps`` counters;
* each slot costs the halfwidth (3 operations a projected dimension) and
  the norm-form distance (2 d + 3), and each bin keeps its ks best by a
  ``ks``-round min-select over its own slots and its ``ks`` carried
  entries (4 operations an entry a round): across the bins, ks x 4 x
  (L Ct + steps ks).

These are the bytes the algorithm needs, not the bytes the kernel moves:
its projection operand comes with K padded to 128 lanes, and it merges
each tile of slots into every bin the tile touches.  The gathers that
write the operands are not the kernel's (``dblsh.gather``).  So the
share of the roofline it gives is a floor on how far the kernel is from
the chip's limit, and it cannot pass 100 % on a correct timing.
"""

from __future__ import annotations

VEC_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}


def per_query(L: int, Ct: int, d: int, K: int, steps: int, k: int,
              dtype: str = "fp32") -> dict:
    """Bytes and operations one query of the kernel needs."""
    vb = VEC_BYTES[dtype]
    ks = k if dtype == "fp32" else 4 * k
    slots = L * Ct
    read = slots * (K * 4 + d * vb + 4 + 4)
    if dtype == "int8":
        read += slots * 4  # per-slot dequant scale
    flops = slots * (3 * K + 2 * d + 3) + ks * 4 * (slots + steps * ks)
    return {"bytes": read + steps * ks * 8 + steps * 4, "flops": flops}


def call(Qn: int, *, L: int, Ct: int, d: int, K: int, steps: int, k: int,
         dtype: str = "fp32") -> dict:
    """Bytes and operations of one call on a batch of ``Qn`` queries."""
    q = per_query(L, Ct, d, K, steps, k, dtype)
    return {"bytes": Qn * q["bytes"], "flops": Qn * q["flops"]}
