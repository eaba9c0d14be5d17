"""jit'd wrappers around the Pallas kernels (padding, BlockSpecs, tiling).

``interpret=None`` auto-selects: compiled Mosaic on TPU, interpret mode
elsewhere (the kernel body runs as pure Python/XLA on CPU — this is how
the kernels are validated in this container; TPU is the target).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairwise_l2 import pairwise_l2_kernel
from .window_verify import (
    _ROWS,
    candidate_dist_kernel,
    candidate_verify_kernel,
    fused_cand_kernel,
    fused_window_kernel,
    window_dist_kernel,
    window_verify_kernel,
)

_IMAX = jnp.iinfo(jnp.int32).max


def _interp(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x, mult, axis, value):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("n", "k", "tile_c", "interpret"))
def candidate_verify(cand_proj, cand_vecs, cand_ids, g, q, w, *, n, k,
                     tile_c: int = 256, interpret=None):
    """Fused box-mask + L2 + top-k over pre-gathered candidates.

    Args:
      cand_proj: (Q, C, K); cand_vecs: (Q, C, d); cand_ids: (Q, C) int32.
      g: (Q, K); q: (Q, d); w: scalar window width.
      n: sentinel id; k: top-k.

    Returns: (Q, k) squared distances ascending, (Q, k) ids (n when empty).
    """
    Qn, C, K = cand_proj.shape
    d = cand_vecs.shape[-1]
    tile_c = min(tile_c, max(8, C))
    cand_proj = _pad_to(cand_proj, tile_c, 1, jnp.inf)
    cand_vecs = _pad_to(cand_vecs, tile_c, 1, 0.0)
    cand_ids = _pad_to(cand_ids, tile_c, 1, n)
    Cp = cand_proj.shape[1]
    w_arr = jnp.asarray(w, jnp.float32).reshape(1, 1)

    grid = (Qn, Cp // tile_c)
    kern = functools.partial(candidate_verify_kernel, k=k, n=n)
    out_d, out_i = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda qi, t: (0, 0)),  # w
            pl.BlockSpec((1, K), lambda qi, t: (qi, 0)),  # g
            pl.BlockSpec((1, d), lambda qi, t: (qi, 0)),  # q
            pl.BlockSpec((1, tile_c, K), lambda qi, t: (qi, t, 0)),
            pl.BlockSpec((1, tile_c, d), lambda qi, t: (qi, t, 0)),
            pl.BlockSpec((1, tile_c), lambda qi, t: (qi, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda qi, t: (qi, 0)),
            pl.BlockSpec((1, k), lambda qi, t: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qn, k), jnp.float32),
            jax.ShapeDtypeStruct((Qn, k), jnp.int32),
        ],
        interpret=_interp(interpret),
    )(w_arr, g, q, cand_proj, cand_vecs, cand_ids)
    out_i = jnp.where(out_i == _IMAX, n, out_i)
    return out_d, out_i


@functools.partial(jax.jit, static_argnames=("n", "k", "interpret"))
def window_verify(blk_idx, proj_blocks, vec_blocks, ids_blocks, g, q, w, *,
                  n, k, interpret=None):
    """Scalar-prefetch fused window verify over an 'inline' layout table.

    Args:
      blk_idx: (Q, M) int32 STR block ids (nb = invalid slot).
      proj_blocks: (nb, B, K); vec_blocks: (nb, B, d); ids_blocks: (nb, B).
      g: (Q, K); q: (Q, d); w scalar.

    The BlockSpec index_map reads blk_idx — each grid step DMAs exactly
    the selected block HBM->VMEM (zero-copy gather).
    """
    from jax.experimental.pallas import tpu as pltpu

    Qn, M = blk_idx.shape
    nb, B, K = proj_blocks.shape
    d = vec_blocks.shape[-1]
    w_arr = jnp.asarray(w, jnp.float32).reshape(1, 1)
    safe_blk = jnp.minimum(blk_idx, nb - 1).astype(jnp.int32)

    kern = functools.partial(window_verify_kernel, k=k, n=n, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Qn, M),
        in_specs=[
            pl.BlockSpec((1, 1), lambda qi, m, blk: (0, 0)),  # w
            pl.BlockSpec((1, K), lambda qi, m, blk: (qi, 0)),  # g
            pl.BlockSpec((1, d), lambda qi, m, blk: (qi, 0)),  # q
            # invalid slots route to the fixed block 0 (not a clamped
            # *real* block): consecutive invalid slots keep the same
            # block index, so Pallas skips the re-DMA entirely, and the
            # kernel pl.when-skips their compute
            pl.BlockSpec((1, B, K), lambda qi, m, blk: (jnp.where(blk[qi, m] < nb, blk[qi, m], 0), 0, 0)),
            pl.BlockSpec((1, B, d), lambda qi, m, blk: (jnp.where(blk[qi, m] < nb, blk[qi, m], 0), 0, 0)),
            pl.BlockSpec((1, B), lambda qi, m, blk: (jnp.where(blk[qi, m] < nb, blk[qi, m], 0), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda qi, m, blk: (qi, 0)),
            pl.BlockSpec((1, k), lambda qi, m, blk: (qi, 0)),
        ],
    )
    out_d, out_i = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Qn, k), jnp.float32),
            jax.ShapeDtypeStruct((Qn, k), jnp.int32),
        ],
        interpret=_interp(interpret),
    )(blk_idx, w_arr, g, q, proj_blocks, vec_blocks, ids_blocks)
    out_i = jnp.where(out_i == _IMAX, n, out_i)
    return out_d, out_i


@functools.partial(jax.jit, static_argnames=("exact", "tile_c", "interpret"))
def candidate_dist(cand_proj, cand_vecs, cand_norms, g, q, *, exact: bool = False,
                   tile_c: int = 256, interpret=None):
    """One-pass fused distance + window-halfwidth over pre-gathered
    candidates, tiled per (query, table).

    Args:
      cand_proj: (Q, L, Ct, K); cand_vecs: (Q, L, Ct, d);
      cand_norms: (Q, L, Ct) squared norms (+inf = padded/invalid slot).
      g: (Q, L, K) per-table query projections; q: (Q, d).
      exact: diff-form distances (escape hatch for the ``||x||^2 -
        2<q,x> + ||q||^2`` fp32 rounding change).

    Returns: d2 (Q, L*Ct) exact squared distances (+inf on invalid
    slots in norm form), hw (Q, L*Ct) per-slot window halfwidths
    ``max_k |p_k - g_k|`` (+inf = never admitted) — flattened
    table-major to match the caller's candidate axis.
    """
    Qn, L, Ct, K = cand_proj.shape
    d = cand_vecs.shape[-1]
    tile_c = min(tile_c, max(8, Ct))
    cand_proj = _pad_to(cand_proj, tile_c, 2, jnp.inf)
    cand_vecs = _pad_to(cand_vecs, tile_c, 2, 0.0)
    cand_norms = _pad_to(cand_norms, tile_c, 2, jnp.inf)
    Cp = cand_proj.shape[2]
    q2 = jnp.sum(jnp.square(q), axis=-1, keepdims=True)  # (Q, 1)

    grid = (Qn, L, Cp // tile_c)
    kern = functools.partial(candidate_dist_kernel, exact=exact)
    d2, hw = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, K), lambda qi, l, t: (qi, l, 0)),  # g
            pl.BlockSpec((1, d), lambda qi, l, t: (qi, 0)),  # q
            pl.BlockSpec((1, 1), lambda qi, l, t: (qi, 0)),  # q2
            pl.BlockSpec((1, 1, tile_c, K), lambda qi, l, t: (qi, l, t, 0)),
            pl.BlockSpec((1, 1, tile_c, d), lambda qi, l, t: (qi, l, t, 0)),
            pl.BlockSpec((1, 1, tile_c), lambda qi, l, t: (qi, l, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tile_c), lambda qi, l, t: (qi, l, t)),
            pl.BlockSpec((1, 1, tile_c), lambda qi, l, t: (qi, l, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qn, L, Cp), jnp.float32),
            jax.ShapeDtypeStruct((Qn, L, Cp), jnp.float32),
        ],
        interpret=_interp(interpret),
    )(g, q, q2, cand_proj, cand_vecs, cand_norms)
    return (
        d2[:, :, :Ct].reshape(Qn, L * Ct),
        hw[:, :, :Ct].reshape(Qn, L * Ct),
    )


@functools.partial(jax.jit, static_argnames=("M", "exact", "interpret"))
def window_dist(blk_idx, proj_blocks, vec_blocks, norm_blocks, g, q, *,
                M: int, exact: bool = False, interpret=None):
    """Scalar-prefetch one-pass distance + halfwidth over an 'inline'
    layout index with all L tables flattened onto one block axis.

    Args:
      blk_idx: (Q, S) int32 flattened block ids, S = L*M, table l's
        block b stored as ``l*nb + b`` (``L*nb`` = invalid slot).
      proj_blocks: (L*nb, B, K); vec_blocks: (L*nb, B, d);
      norm_blocks: (L*nb, B) squared norms (+inf padded).
      g: (Q, L, K); q: (Q, d); M: blocks per table (maps slot -> table).

    Returns: d2 (Q, S*B), hw (Q, S*B) — same contract as
    :func:`candidate_dist`, but the block gather happens inside the
    kernel (one DMA per selected block for the whole schedule).
    """
    from jax.experimental.pallas import tpu as pltpu

    Qn, S = blk_idx.shape
    lnb, B, K = proj_blocks.shape
    d = vec_blocks.shape[-1]
    q2 = jnp.sum(jnp.square(q), axis=-1, keepdims=True)  # (Q, 1)

    kern = functools.partial(window_dist_kernel, lnb=lnb, exact=exact)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Qn, S),
        in_specs=[
            pl.BlockSpec((1, 1, K), lambda qi, s, blk: (qi, s // M, 0)),  # g
            pl.BlockSpec((1, d), lambda qi, s, blk: (qi, 0)),  # q
            pl.BlockSpec((1, 1), lambda qi, s, blk: (qi, 0)),  # q2
            # route invalid slots to fixed block 0 (see window_verify:
            # unchanged index -> no re-DMA; compute is pl.when-skipped)
            pl.BlockSpec((1, B, K),
                         lambda qi, s, blk: (jnp.where(blk[qi, s] < lnb, blk[qi, s], 0), 0, 0)),
            pl.BlockSpec((1, B, d),
                         lambda qi, s, blk: (jnp.where(blk[qi, s] < lnb, blk[qi, s], 0), 0, 0)),
            pl.BlockSpec((1, B),
                         lambda qi, s, blk: (jnp.where(blk[qi, s] < lnb, blk[qi, s], 0), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, B), lambda qi, s, blk: (qi, s, 0)),
            pl.BlockSpec((1, 1, B), lambda qi, s, blk: (qi, s, 0)),
        ],
    )
    d2, hw = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Qn, S, B), jnp.float32),
            jax.ShapeDtypeStruct((Qn, S, B), jnp.float32),
        ],
        interpret=_interp(interpret),
    )(blk_idx, g, q, q2, proj_blocks, vec_blocks, norm_blocks)
    return d2.reshape(Qn, S * B), hw.reshape(Qn, S * B)


def _quantize_query(q, mode: str):
    """Query-side arithmetic prep for a distance mode.

    Returns (qv, qs): the query operand in the mode's dtype and the
    (Q, 1) per-query dequant scale (all-ones when the mode has none)."""
    Qn = q.shape[0]
    if mode == "bf16":
        return q.astype(jnp.bfloat16), jnp.ones((Qn, 1), jnp.float32)
    if mode == "int8":
        amax = jnp.max(jnp.abs(q), axis=-1, keepdims=True)
        qs = jnp.where(amax > 0.0, amax / 127.0, 1.0).astype(jnp.float32)
        qv = jnp.clip(jnp.round(q / qs), -127.0, 127.0).astype(jnp.int8)
        return qv, qs
    return q, None


@functools.partial(
    jax.jit, static_argnames=("M", "ks", "n", "mode", "interpret")
)
def fused_window_search(blk_idx, halves, proj_blocks, x_blocks, norm_blocks,
                        ids_blocks, g, q, *, M, ks, n, mode: str = "norm",
                        interpret=None, x_scale=None):
    """Fully fused one-pass search over an 'inline' layout index: block
    select DMA + halfwidth + distance + schedule binning + per-bin
    top-ks, one scalar-prefetch kernel — candidates never reach HBM.

    Args:
      blk_idx: (Q, S) int32 flattened block ids, S = L*M (L*nb invalid).
      halves: (steps,) f32 schedule half window widths w_j/2, ascending.
      proj_blocks: (L*nb, B, K); x_blocks: (L*nb, B, d) fp32 vectors
        (mode 'norm'/'exact') or quantized blocks (mode 'bf16'/'int8');
      norm_blocks: (L*nb, B) fp32 squared norms (+inf padded);
      ids_blocks: (L*nb, B) int32; g: (Q, L, K); q: (Q, d) fp32.
      ks: bin accumulator width (k, or 4k for the quantized shortlist).
      x_scale: (L*nb, B) per-slot dequant scales (quant modes only).

    Returns:
      bins_d (Q, steps, ks) f32  per-bin ascending top-ks distances,
      bins_i (Q, steps, ks) i32  matching ids (n = unfilled),
      cnt    (Q, steps)     i32  admitted candidate slots per bin
                                 (cumsum = the C1 admission count).
    """
    from jax.experimental.pallas import tpu as pltpu

    Qn, S = blk_idx.shape
    lnb, B, K = proj_blocks.shape
    L = g.shape[1]
    d = x_blocks.shape[-1]
    steps = halves.shape[0]
    halves2 = halves.reshape(1, steps).astype(jnp.float32)
    q2 = jnp.sum(jnp.square(q), axis=-1, keepdims=True)  # (Q, 1) fp32
    qv, qs = _quantize_query(q, mode)
    quant = mode in ("bf16", "int8")

    def _route(blk, qi, s):
        return jnp.where(blk[qi, s] < lnb, blk[qi, s], 0)

    # TPU blocks need their last two dims (8, 128)-aligned or equal to the
    # array's: per-query operands get a unit sublane axis, and the (L*nb,
    # B) per-slot rows are read as the aligned 8-row tile holding the
    # selected block (the kernel picks row blk % 8) — no relayout of the
    # index arrays.
    row_tile = pl.BlockSpec(
        (_ROWS, B), lambda qi, s, blk: (_route(blk, qi, s) // _ROWS, 0)
    )
    in_specs = [
        pl.BlockSpec((1, steps), lambda qi, s, blk: (0, 0)),  # halves
        pl.BlockSpec((1, 1, 1, K), lambda qi, s, blk: (qi, s // M, 0, 0)),  # g
        pl.BlockSpec((1, 1, d), lambda qi, s, blk: (qi, 0, 0)),  # q
        pl.BlockSpec((1, 1, 1), lambda qi, s, blk: (qi, 0, 0)),  # q2
    ]
    operands = [halves2, g.reshape(Qn, L, 1, K), qv.reshape(Qn, 1, d),
                q2.reshape(Qn, 1, 1)]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, 1), lambda qi, s, blk: (qi, 0, 0)))
        operands.append(qs.reshape(Qn, 1, 1))
    in_specs += [
        pl.BlockSpec((1, B, K), lambda qi, s, blk: (_route(blk, qi, s), 0, 0)),
        pl.BlockSpec((1, B, d), lambda qi, s, blk: (_route(blk, qi, s), 0, 0)),
        row_tile,  # norms
        row_tile,  # ids
    ]
    operands += [proj_blocks, x_blocks, norm_blocks, ids_blocks]
    if quant:
        in_specs.append(row_tile)
        operands.append(x_scale)

    kern = functools.partial(
        fused_window_kernel, lnb=lnb, steps=steps, ks=ks, mode=mode
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Qn, S),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, steps, ks), lambda qi, s, blk: (qi, 0, 0)),
            pl.BlockSpec((1, steps, ks), lambda qi, s, blk: (qi, 0, 0)),
            pl.BlockSpec((1, 1, steps), lambda qi, s, blk: (qi, 0, 0)),
        ],
        # the query's slot rows (d², ids, bins): selected once per bin
        # at its last slot step
        scratch_shapes=[
            pltpu.VMEM((S, B), jnp.float32),
            pltpu.VMEM((S, B), jnp.int32),
            pltpu.VMEM((S, B), jnp.int32),
        ],
    )
    bd, bi, cnt = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Qn, steps, ks), jnp.float32),
            jax.ShapeDtypeStruct((Qn, steps, ks), jnp.int32),
            jax.ShapeDtypeStruct((Qn, 1, steps), jnp.int32),
        ],
        interpret=_interp(interpret),
    )(blk_idx, *operands)
    return bd, jnp.where(bi == _IMAX, n, bi), cnt.reshape(Qn, steps)


@functools.partial(
    jax.jit, static_argnames=("ks", "n", "mode", "tile_c", "interpret")
)
def fused_cand_search(cand_proj, cand_x, cand_norms, cand_ids, halves, g, q,
                      *, ks, n, mode: str = "norm", tile_c: int = 256,
                      interpret=None, cand_scale=None):
    """Gathered twin of :func:`fused_window_search` ('kernel' engine):
    pre-gathered candidates, same bin-accumulator outputs.

    Args:
      cand_proj: (Q, L, Ct, K) (+inf on invalid slots — that alone keeps
        them out of every bin); cand_x: (Q, L, Ct, d) fp32 or quantized;
      cand_norms: (Q, L, Ct) fp32 (+inf padded); cand_ids: (Q, L, Ct);
      halves: (steps,); g: (Q, L, K); q: (Q, d) fp32;
      cand_scale: (Q, L, Ct) dequant scales (quant modes only).

    Returns: (bins_d, bins_i, cnt) as :func:`fused_window_search`.
    """
    Qn, L, Ct, K = cand_proj.shape
    d = cand_x.shape[-1]
    steps = halves.shape[0]
    tile_c = min(tile_c, max(8, Ct))
    cand_proj = _pad_to(cand_proj, tile_c, 2, jnp.inf)
    cand_x = _pad_to(cand_x, tile_c, 2, 0)
    cand_norms = _pad_to(cand_norms, tile_c, 2, jnp.inf)
    cand_ids = _pad_to(cand_ids, tile_c, 2, n)
    Cp = cand_proj.shape[2]
    halves2 = halves.reshape(1, steps).astype(jnp.float32)
    q2 = jnp.sum(jnp.square(q), axis=-1, keepdims=True)  # (Q, 1)
    qv, qs = _quantize_query(q, mode)
    quant = mode in ("bf16", "int8")

    # TPU-legal blocks: every per-query / per-slot operand gets a unit
    # sublane axis so its block's last two dims equal the array's
    slot = pl.BlockSpec((1, 1, 1, tile_c), lambda qi, l, t: (qi, l, 0, t))
    in_specs = [
        pl.BlockSpec((1, steps), lambda qi, l, t: (0, 0)),  # halves
        pl.BlockSpec((1, 1, 1, K), lambda qi, l, t: (qi, l, 0, 0)),  # g
        pl.BlockSpec((1, 1, d), lambda qi, l, t: (qi, 0, 0)),  # q
        pl.BlockSpec((1, 1, 1), lambda qi, l, t: (qi, 0, 0)),  # q2
    ]
    operands = [halves2, g.reshape(Qn, L, 1, K), qv.reshape(Qn, 1, d),
                q2.reshape(Qn, 1, 1)]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, 1), lambda qi, l, t: (qi, 0, 0)))
        operands.append(qs.reshape(Qn, 1, 1))
    in_specs += [
        pl.BlockSpec((1, 1, tile_c, K), lambda qi, l, t: (qi, l, t, 0)),
        pl.BlockSpec((1, 1, tile_c, d), lambda qi, l, t: (qi, l, t, 0)),
        slot,  # norms
        slot,  # ids
    ]
    operands += [cand_proj, cand_x, cand_norms.reshape(Qn, L, 1, Cp),
                 cand_ids.reshape(Qn, L, 1, Cp)]
    if quant:
        cand_scale = _pad_to(cand_scale, tile_c, 2, 1.0)
        in_specs.append(slot)
        operands.append(cand_scale.reshape(Qn, L, 1, Cp))

    kern = functools.partial(fused_cand_kernel, steps=steps, ks=ks, mode=mode)
    bd, bi, cnt = pl.pallas_call(
        kern,
        grid=(Qn, L, Cp // tile_c),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, steps, ks), lambda qi, l, t: (qi, 0, 0)),
            pl.BlockSpec((1, steps, ks), lambda qi, l, t: (qi, 0, 0)),
            pl.BlockSpec((1, 1, steps), lambda qi, l, t: (qi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Qn, steps, ks), jnp.float32),
            jax.ShapeDtypeStruct((Qn, steps, ks), jnp.int32),
            jax.ShapeDtypeStruct((Qn, 1, steps), jnp.int32),
        ],
        interpret=_interp(interpret),
    )(*operands)
    return bd, jnp.where(bi == _IMAX, n, bi), cnt.reshape(Qn, steps)


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_n", "tile_d", "interpret"))
def pairwise_l2(Q, X, *, tile_q: int = 256, tile_n: int = 256, tile_d: int = 128,
                interpret=None):
    """Blocked squared-distance matrix (Q_n, X_n) -> (Q_n, X_n)."""
    nq, d = Q.shape
    nn = X.shape[0]
    tile_q = min(tile_q, nq)
    tile_n = min(tile_n, nn)
    tile_d = min(tile_d, d)
    Qp = _pad_to(_pad_to(Q, tile_q, 0, 0.0), tile_d, 1, 0.0)
    Xp = _pad_to(_pad_to(X, tile_n, 0, 0.0), tile_d, 1, 0.0)
    gq, gn, gd = Qp.shape[0] // tile_q, Xp.shape[0] // tile_n, Qp.shape[1] // tile_d

    out = pl.pallas_call(
        pairwise_l2_kernel,
        grid=(gq, gn, gd),
        in_specs=[
            pl.BlockSpec((tile_q, tile_d), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((tile_n, tile_d), lambda i, j, kd: (j, kd)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_n), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp.shape[0], Xp.shape[0]), jnp.float32),
        interpret=_interp(interpret),
    )(Qp, Xp)
    return out[:nq, :nn]
