"""DB-LSH index construction (paper §IV-B), TPU-adapted.

The paper indexes each of the L K-dimensional projected spaces with a
bulk-loaded R*-tree.  Pointer-chasing trees are hostile to TPUs, so we
keep the *contract* (window queries at query-chosen widths over
un-quantized projections) and swap the *structure* for a dense
Sort-Tile-Recursive (STR) packed block index — the same bulk-loading
family the paper uses, with the tree levels flattened into dense arrays:

  * per table, points are STR-ordered (dim-0 slabs, dim-1 within a slab)
    and grouped into fixed blocks of ``B`` points;
  * each block stores its K-dim minimum bounding rectangle (MBR) in two
    dense ``(nb, K)`` arrays — the "leaf level" of the R*-tree;
  * a window query tests *all* MBRs with one vectorized compare (VPU,
    ``nb = n/B`` lanes), compacts the first ``M`` overlapping blocks with
    a fixed-capacity sort-compaction, and streams those blocks through
    the verifier.

See DESIGN.md §3 for the fidelity argument.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import hashing
from .params import DBLSHParams

__all__ = ["DBLSHIndex", "build", "compute_norm_blocks", "quantize_blocks"]


def compute_norm_blocks(data: jax.Array, ids_blocks: jax.Array) -> jax.Array:
    """Per-slot squared norms ||x||^2 aligned with ``ids_blocks``.

    Padded / tombstoned slots (id >= n) get +inf so the MXU distance form
    ||x||^2 - 2<q,x> + ||q||^2 masks them without an id compare."""
    n = data.shape[0]
    norms = jnp.sum(jnp.square(data), axis=-1)  # (n,)
    return jnp.take(
        norms, ids_blocks, axis=0, mode="fill", fill_value=jnp.inf
    ).astype(jnp.float32)


def quantize_blocks(
    data: jax.Array, ids_blocks: jax.Array, quant_dtype: str
) -> tuple[jax.Array, jax.Array]:
    """Quantized per-table vector blocks for the reduced-precision dot.

    Returns ``(qvec_blocks, qvec_scale)`` slot-aligned with ``ids_blocks``:

      * ``bf16``: blocks cast to bfloat16, scale all-ones (unused);
      * ``int8``: per-slot symmetric quantization ``round(x / s)`` with
        ``s = amax(|x|) / 127`` (``s = 1`` on all-zero rows), so the
        approximate dot is ``s_slot * s_q * <qx, qq>``.

    Quantization is a pure deterministic function of ``data`` — snapshots
    persist the fp32 truth and restore paths re-derive these (same pattern
    as ``compute_norm_blocks``).  Padded / tombstoned slots (id >= n)
    gather the zero fill, contributing a zero dot; admission and the final
    re-rank mask them exactly, so no sentinel is needed here."""
    if quant_dtype not in ("bf16", "int8"):
        raise ValueError(f"quant_dtype must be 'bf16' or 'int8', got {quant_dtype!r}")
    x = jnp.take(data, ids_blocks, axis=0, mode="fill", fill_value=0.0)
    if quant_dtype == "bf16":
        q = x.astype(jnp.bfloat16)
        scale = jnp.ones(ids_blocks.shape, jnp.float32)
        return q, scale
    amax = jnp.max(jnp.abs(x), axis=-1)  # (L, nb, B)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale[..., None]), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def empty_quant_blocks(dtype) -> tuple[jax.Array, jax.Array]:
    """Placeholder (empty) quantized fields for quant_dtype='none'."""
    del dtype
    return jnp.zeros((0,), jnp.int8), jnp.zeros((0,), jnp.float32)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "proj_vecs",
        "proj_blocks",
        "ids_blocks",
        "mbr_lo",
        "mbr_hi",
        "data",
        "vec_blocks",
        "norm_blocks",
        "qvec_blocks",
        "qvec_scale",
    ],
    meta_fields=["params"],
)
@dataclasses.dataclass
class DBLSHIndex:
    """The (K, L)-index with dynamic bucketing support.

    Shapes (B = params.block_size, nb = ceil(n / B)):
      proj_vecs:   (L, K, d)      the LSH functions a_ij (Eq. 3)
      proj_blocks: (L, nb, B, K)  STR-ordered projections, +inf padded
      ids_blocks:  (L, nb, B)     original point ids, n-padded
      mbr_lo/hi:   (L, nb, K)     per-block K-dim bounding boxes
      data:        (n, d)         the dataset ('gather' verify layout)
      vec_blocks:  (L, nb, B, d)  optional per-table reordered vectors
                                  ('inline' streaming layout), else ()
      norm_blocks: (L, nb, B)     per-slot squared L2 norms ||x||^2,
                                  slot-aligned with ids_blocks (+inf on
                                  padded / tombstoned slots) — the MXU
                                  verify form ||x||^2 - 2<q,x> + ||q||^2
                                  reads these instead of re-reducing d
                                  diff lanes per candidate per radius
      qvec_blocks: (L, nb, B, d)  quantized per-table vectors (bf16/int8)
                                  for the reduced-precision distance path,
                                  else () when params.quant_dtype='none'
      qvec_scale:  (L, nb, B)     per-slot dequantization scales (f32),
                                  all-ones for bf16, else ()
    """

    proj_vecs: jax.Array
    proj_blocks: jax.Array
    ids_blocks: jax.Array
    mbr_lo: jax.Array
    mbr_hi: jax.Array
    data: jax.Array
    vec_blocks: jax.Array
    norm_blocks: jax.Array
    qvec_blocks: jax.Array
    qvec_scale: jax.Array
    params: DBLSHParams

    @property
    def n(self) -> int:
        return self.data.shape[0]

    @property
    def nb(self) -> int:
        return self.proj_blocks.shape[1]

    def memory_bytes(self) -> int:
        tot = 0
        for f in (
            self.proj_vecs,
            self.proj_blocks,
            self.ids_blocks,
            self.mbr_lo,
            self.mbr_hi,
            self.vec_blocks,
            self.norm_blocks,
            self.qvec_blocks,
            self.qvec_scale,
        ):
            tot += f.size * f.dtype.itemsize
        return tot


def _str_order(proj_t: jax.Array, block_size: int) -> jax.Array:
    """STR ordering for one table: sort by dim-0 into slabs, then by dim-1
    within each slab. Returns the permutation (n,) of original point ids."""
    n, K = proj_t.shape
    nb = -(-n // block_size)
    n_slabs = max(1, int(math.ceil(math.sqrt(nb))))
    slab_pts = -(-n // n_slabs)
    rank0 = jnp.argsort(jnp.argsort(proj_t[:, 0]))
    slab = rank0 // slab_pts
    key2 = proj_t[:, 1] if K > 1 else proj_t[:, 0]
    # lexsort: last key is primary.
    return jnp.lexsort((key2, slab))


@partial(jax.jit, static_argnames="params")
def _hash_and_pack(key: jax.Array, data: jax.Array, params: DBLSHParams):
    """The hash functions, and each table's STR-ordered blocks: (proj_vecs,
    orders, proj_blocks, ids_blocks, mbr_lo, mbr_hi).

    One program, the key an argument so that one compile serves every
    key.  Op by op, each intermediate (the (L, n, K) projections, their
    sorted copy, its padded concatenation) is a buffer of its own in the
    device's layout, and several are live at once beside the data: at
    n = 10M that does not fit one 16 GB chip.  Every value here is a
    projection (one dot per point and table, as op by op) or a copy of
    one, so the arrays are the same bit for bit."""
    n, d = data.shape
    B, K, L = params.block_size, params.K, params.L
    nb = -(-n // B)
    n_pad = nb * B

    proj_vecs = hashing.sample_projections(key, d, K, L)
    proj = hashing.project(data, proj_vecs)  # (L, n, K)

    def _pack(proj_t):
        order = _str_order(proj_t, B)
        p_sorted = jnp.take(proj_t, order, axis=0)
        pad = jnp.full((n_pad - n, K), jnp.inf, p_sorted.dtype)
        p_sorted = jnp.concatenate([p_sorted, pad], axis=0).reshape(nb, B, K)
        ids = jnp.concatenate(
            [order.astype(jnp.int32), jnp.full((n_pad - n,), n, jnp.int32)]
        ).reshape(nb, B)
        # MBRs over real points only: padded rows are +inf so they never
        # lower `lo`; mask them out of `hi` with -inf.
        finite = jnp.isfinite(p_sorted[..., :1])
        lo = jnp.min(p_sorted, axis=1)
        hi = jnp.max(jnp.where(finite, p_sorted, -jnp.inf), axis=1)
        return order, p_sorted, ids, lo, hi

    # one table at a time: a table's sort and gather temporaries are a
    # fifth of all tables' at once
    return (proj_vecs,) + jax.lax.map(_pack, proj)


def build(key: jax.Array, data: jax.Array, params: DBLSHParams) -> DBLSHIndex:
    """Indexing phase (paper §IV-B): project into L K-dim spaces (Eq. 7),
    then bulk-load one dense STR index per space.

    The hashing and packing run as one program (``_hash_and_pack``); the
    squared norms and the quantized blocks stay op by op, since inside a
    larger program XLA may fuse their arithmetic into its neighbours and
    round float32 differently."""
    params = params.resolve()
    n, d = data.shape
    assert n == params.n and d == params.d, (data.shape, params)
    B = params.block_size
    nb = -(-n // B)
    n_pad = nb * B

    proj_vecs, orders, proj_blocks, ids_blocks, mbr_lo, mbr_hi = (
        _hash_and_pack(key, data, params)
    )

    if params.inline_vectors:
        def _pack_vecs(order):
            v = jnp.take(data, order, axis=0)
            pad = jnp.zeros((n_pad - n, d), v.dtype)
            return jnp.concatenate([v, pad], axis=0).reshape(nb, B, d)

        vec_blocks = jax.vmap(_pack_vecs)(orders)
    else:
        vec_blocks = jnp.zeros((0,), dtype=data.dtype)

    if params.quant_dtype != "none":
        qvec_blocks, qvec_scale = quantize_blocks(
            data, ids_blocks, params.quant_dtype
        )
    else:
        qvec_blocks, qvec_scale = empty_quant_blocks(data.dtype)

    return DBLSHIndex(
        proj_vecs=proj_vecs,
        proj_blocks=proj_blocks,
        ids_blocks=ids_blocks,
        mbr_lo=mbr_lo,
        mbr_hi=mbr_hi,
        data=data,
        vec_blocks=vec_blocks,
        norm_blocks=compute_norm_blocks(data, ids_blocks),
        qvec_blocks=qvec_blocks,
        qvec_scale=qvec_scale,
        params=params,
    )
