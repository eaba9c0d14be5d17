"""Pallas TPU kernels: window-query verification for DB-LSH.

The query-phase hot spot of Algorithm 1 is verification: for each query,
stream the candidate blocks selected by the MBR pass, test K-dim box
containment against the query-centric bucket W(G_i(q), w), compute exact
squared L2 distances for in-box points, and maintain a running top-k —
all without materializing per-candidate distances in HBM.

Per-radius fused variants (the multi-pass reference path):

* ``candidate_verify_kernel`` — operates on pre-gathered candidates
  (``gather`` index layout). Grid: (Q, C/TILE_C); the top-k accumulator
  lives in the output block, revisited across the C tiles.

* ``window_verify_kernel`` — operates directly on the table via
  **scalar-prefetch block indices**: the BlockSpec index_map reads the
  per-(query, slot) STR block id and DMAs exactly that block HBM->VMEM.
  This is the zero-copy gather: the XLA-level ``jnp.take`` of blocks
  disappears entirely (``inline`` layout required). Same in-kernel fused
  verify + top-k.

One-pass schedule variants (the serving path): the fixed-schedule
search verifies each selected block **once** for the whole radius
schedule, so these kernels drop the in-kernel window mask and top-k and
instead emit, per candidate slot, the exact squared distance plus the
slot's **window halfwidth** ``hw = max_k |p_k - g_k|`` — the smallest
half window width that admits the slot.  The per-step box test then
collapses to ``hw <= w_j / 2``, evaluated host-of-kernel against the
whole schedule without touching the d-dim vectors again:

* ``candidate_dist_kernel`` — pre-gathered candidates, grid
  (Q, L, Ct/TILE_C) so each tile reads its own table's query projection.
* ``window_dist_kernel`` — scalar-prefetch block DMA over the L tables
  flattened to one (L*nb) block axis (``inline`` layout required).

Both compute distances in the MXU form ``||x||^2 - 2<q,x> + ||q||^2``
(one dot against the query instead of d diff+square lanes per slot)
using squared norms precomputed at build time, with a static
``exact=True`` escape hatch that restores the materialized-diff form
(the norm trick changes fp32 rounding).

The in-kernel top-k is a k-step vectorized selection (min + one-hot
write + mask), free of data-dependent scatters so it lowers to pure VPU
ops. Because cross-table duplicates carry identical (dist, id) pairs,
the "remove all entries equal to the selected (dist, id)" step performs
exact dedup for free.

Fully fused one-pass variants (the serving path's fast lane): the
schedule masking and the per-step delta merges move *into* the kernel,
so candidates never touch HBM between block select and the final
result.  Each candidate is assigned its schedule **bin** — the first
step whose window admits it, ``binid = #{j: hw > w_j/2}`` — and folded
into a per-(query, step) top-ks accumulator plus an admitted-slot
counter (the ``with_stats``/C1 feed).  The caller recovers exact
per-step merge semantics by prefix-merging the bins (windows nest, so
bin j IS the step-j delta):

* ``fused_cand_kernel``   — pre-gathered candidates, grid (Q, L, Ct/TC);
  merges each candidate tile into the bins as it streams (``merge_topk``
  per bin and tile).
* ``fused_window_kernel`` — scalar-prefetch block DMA, grid (Q, S); each
  slot step only writes its (d², id, bin) row into the query's (S, B)
  VMEM scratch, and the last slot step selects every bin's top-ks once
  over the whole tile (``_select_bins``): one ks-round selection a
  query, not one a bin and slot.

Both take a ``mode`` in {'exact', 'norm', 'bf16', 'int8'}: the
quantized modes compute the dot against quantized blocks (per-slot
symmetric int8 scales / plain bf16 casts) while norms, halfwidths and
admission stay fp32-exact — the caller re-ranks the shortlist in fp32.

VMEM budget (per grid step, fp32): TILE_C*(K + d + 1) + 2k floats.
With TILE_C = 256, K = 12, d = 128, k = 50: ~145 KiB — comfortably
inside the ~16 MiB v5e VMEM; TILE_C is raised by ops.py when d is small.
The fused accumulators add steps*(2*ks + 1) words — 2.6 KiB at
steps = 8, ks = 40 — and the window variant's scratch 3*S*B words,
three (32, 128)-padded tiles of 16 KiB at S = 25, B = 64 (see
DESIGN.md §13 for the full table).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = jnp.inf
_IMAX = jnp.iinfo(jnp.int32).max
_ROWS = 8  # sublane tile: the row granularity a TPU block may start at


def merge_topk(cd, ci, out_d, out_i, k: int):
    """k-step vectorized selection merging candidates into (out_d, out_i).

    cd/ci: (C,) candidate squared distances / ids (masked slots = +inf).
    out_d/out_i: (k,) current top-k (ascending, +inf padded).
    Pure VPU ops: min-reduce, compare, select. No dynamic scatter.
    """
    cd = jnp.concatenate([out_d, cd])
    ci = jnp.concatenate([out_i, ci])
    idxk = jax.lax.iota(jnp.int32, k)

    def body(j, carry):
        cd, nd, ni = carry
        m = jnp.min(cd)
        finite = jnp.isfinite(m)
        eq = cd == m
        sel = jnp.min(jnp.where(eq, ci, _IMAX))
        oh = idxk == j
        nd = jnp.where(oh, m, nd)
        ni = jnp.where(oh & finite, sel, ni)
        # drop every entry with the selected (dist, id) — exact dedup of
        # cross-table duplicates, which carry identical pairs.
        cd = jnp.where(eq & (ci == sel), _INF, cd)
        return cd, nd, ni

    init = (cd, jnp.full((k,), _INF, cd.dtype), jnp.full((k,), _IMAX, jnp.int32))
    _, nd, ni = jax.lax.fori_loop(0, k, body, init)
    return nd, ni


def candidate_verify_kernel(
    w_ref, g_ref, q_ref, proj_ref, vec_ref, ids_ref, topd_ref, topi_ref, *, k: int, n: int
):
    """Grid (Q, C_tiles). Blocks: proj (1,TC,K), vec (1,TC,d), ids (1,TC);
    g (1,K), q (1,d), w (1,1) replicated; outputs (1,k) revisited over
    tiles."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        topd_ref[...] = jnp.full_like(topd_ref, _INF)
        topi_ref[...] = jnp.full_like(topi_ref, _IMAX)

    half = 0.5 * w_ref[0, 0]
    p = proj_ref[0]  # (TC, K)
    x = vec_ref[0]  # (TC, d)
    ids = ids_ref[0]  # (TC,)
    g = g_ref[0]  # (K,)
    q = q_ref[0]  # (d,)

    inbox = jnp.all(jnp.abs(p - g[None, :]) <= half, axis=-1)  # (TC,)
    diff = x - q[None, :]
    d2 = jnp.sum(diff * diff, axis=-1)  # (TC,)
    d2 = jnp.where(inbox & (ids < n), d2, _INF)

    nd, ni = merge_topk(d2, ids, topd_ref[0], topi_ref[0], k)
    topd_ref[0] = nd
    topi_ref[0] = ni


def window_verify_kernel(
    blk_ref,  # scalar prefetch: (Q, M) int32 block ids
    w_ref,
    g_ref,
    q_ref,
    proj_ref,  # (1, B, K) block DMA'd via blk_ref
    vec_ref,  # (1, B, d)
    ids_ref,  # (1, B)
    topd_ref,
    topi_ref,
    *,
    k: int,
    n: int,
    nb: int,
):
    """Grid (Q, M). The index_map for proj/vec/ids reads blk_ref — Pallas
    DMAs exactly the selected STR block; no gathered copy ever exists."""
    qi = pl.program_id(0)
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _init():
        topd_ref[...] = jnp.full_like(topd_ref, _INF)
        topi_ref[...] = jnp.full_like(topi_ref, _IMAX)

    # invalid slots are routed to block 0 by the index_map; skip their
    # compute entirely — the accumulator simply isn't touched
    @pl.when(blk_ref[qi, m] < nb)
    def _compute():
        half = 0.5 * w_ref[0, 0]
        p = proj_ref[0]
        x = vec_ref[0]
        ids = ids_ref[0]
        g = g_ref[0]
        q = q_ref[0]

        inbox = jnp.all(jnp.abs(p - g[None, :]) <= half, axis=-1)
        diff = x - q[None, :]
        d2 = jnp.sum(diff * diff, axis=-1)
        d2 = jnp.where(inbox & (ids < n), d2, _INF)

        nd, ni = merge_topk(d2, ids, topd_ref[0], topi_ref[0], k)
        topd_ref[0] = nd
        topi_ref[0] = ni


def candidate_dist_kernel(
    g_ref, q_ref, q2_ref, proj_ref, vec_ref, nrm_ref, d2_ref, hw_ref, *, exact: bool
):
    """One-pass distance + halfwidth over pre-gathered candidates.

    Grid (Q, L, Ct_tiles). Blocks: proj (1,1,TC,K), vec (1,1,TC,d), nrm
    (1,1,TC); g (1,1,K) per (query, table), q (1,d) / q2 (1,1) per
    query; outputs d2 / hw (1,1,TC). No window mask, no top-k: the
    radius schedule is applied outside against ``hw``."""
    p = proj_ref[0, 0]  # (TC, K)
    x = vec_ref[0, 0]  # (TC, d)
    g = g_ref[0, 0]  # (K,)
    q = q_ref[0]  # (d,)

    hw = jnp.max(jnp.abs(p - g[None, :]), axis=-1)  # (TC,)
    if exact:
        diff = x - q[None, :]
        d2 = jnp.sum(diff * diff, axis=-1)
    else:
        # MXU form: one dot against the query; +inf norms (padding,
        # tombstones) poison d2 so no id compare is needed here.
        d2 = jnp.maximum(
            nrm_ref[0, 0] - 2.0 * jnp.dot(x, q) + q2_ref[0, 0], 0.0
        )
    d2_ref[0, 0] = d2
    hw_ref[0, 0] = hw


def window_dist_kernel(
    blk_ref,  # scalar prefetch: (Q, S) int32 flattened block ids (S = L*M)
    g_ref,  # (1, 1, K): the owning table's query projection
    q_ref,  # (1, d)
    q2_ref,  # (1, 1)
    proj_ref,  # (1, B, K) block DMA'd via blk_ref
    vec_ref,  # (1, B, d)
    nrm_ref,  # (1, B)
    d2_ref,  # (1, 1, B)
    hw_ref,  # (1, 1, B)
    *,
    lnb: int,
    exact: bool,
):
    """Grid (Q, S). Scalar-prefetch twin of ``candidate_dist_kernel``:
    the index_map DMAs exactly the selected STR block of the flattened
    (L*nb) table axis — the serving path's only touch of the d-dim
    vectors for the entire radius schedule.

    Invalid slots (blk >= lnb) are routed to block 0 by the index_map
    (consecutive invalid slots therefore re-DMA nothing — Pallas skips
    the copy when the block index is unchanged) and the compute is
    ``pl.when``-skipped entirely: the slot's outputs are written as +inf
    so the schedule mask can never admit it."""
    qi = pl.program_id(0)
    s = pl.program_id(1)

    blk_valid = blk_ref[qi, s] < lnb

    @pl.when(blk_valid)
    def _compute():
        p = proj_ref[0]  # (B, K)
        x = vec_ref[0]  # (B, d)
        g = g_ref[0, 0]  # (K,)
        q = q_ref[0]  # (d,)

        hw = jnp.max(jnp.abs(p - g[None, :]), axis=-1)  # (B,)
        if exact:
            diff = x - q[None, :]
            d2 = jnp.sum(diff * diff, axis=-1)
        else:
            d2 = jnp.maximum(
                nrm_ref[0] - 2.0 * jnp.dot(x, q) + q2_ref[0, 0], 0.0
            )
        d2_ref[0, 0] = d2
        hw_ref[0, 0] = hw

    @pl.when(~blk_valid)
    def _invalid():
        d2_ref[...] = jnp.full_like(d2_ref, _INF)
        hw_ref[...] = jnp.full_like(hw_ref, _INF)


def _slot_d2(x, q, nrm, q2, *, mode: str, xscale=None, qscale=None):
    """Per-slot squared distances in the requested arithmetic mode.

    x: (C, d) candidate vectors (fp32, bf16 or int8 depending on mode);
    q: (d,) query in the matching dtype; nrm/q2: fp32 exact squared
    norms.  ``bf16``/``int8`` compute only the *dot* reduced-precision —
    norms stay fp32-exact, so the error model is confined to the cross
    term (DESIGN.md §13)."""
    if mode == "exact":
        diff = x - q[None, :]
        return jnp.sum(diff * diff, axis=-1)
    if mode == "norm":
        # HIGHEST: a DEFAULT-precision f32 dot runs as one bf16 pass on the
        # MXU, which the norm form's cancellation turns into a visible
        # distance error
        dot = jnp.dot(x, q, precision=jax.lax.Precision.HIGHEST)
        return jnp.maximum(nrm - 2.0 * dot + q2, 0.0)
    if mode not in ("int8", "bf16"):  # pragma: no cover - wrapper-guarded
        raise ValueError(f"unknown distance mode {mode!r}")
    # (1, d) x (C, d)^T: the matmul form Mosaic lowers for narrow dtypes
    dot = jax.lax.dot_general(
        q[None, :], x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32 if mode == "int8" else jnp.float32,
    )[0].astype(jnp.float32)
    return jnp.maximum(nrm - 2.0 * (xscale * qscale * dot) + q2, 0.0)


def _bin_and_count(hw, halves, cnt_ref, *, steps: int):
    """Each candidate's schedule *bin*, with the admitted slots counted.

    A candidate belongs to exactly one bin: the first step whose window
    admits it, ``binid = #{j : hw > w_j/2}`` (``steps`` = never admitted;
    hw = +inf slots land there).  Windows nest, so the step-j delta slice
    of the radius schedule is exactly bin j — the epilogue recovers the
    per-step merge semantics by prefix-merging the bins.  ``cnt`` (1, 1,
    steps), revisited across the grid's slot axis, accumulates admitted
    slots per bin; its cumulative sum equals the C1 admission count
    ``#{hw <= w_j/2}``.
    """
    c = hw.shape[0]
    binid = jnp.sum((hw[None, :] > halves[:, None]).astype(jnp.int32), axis=0)
    # 2D iota (broadcasted_iota): 1D iota does not lower on TPU
    stepv = jax.lax.broadcasted_iota(jnp.int32, (steps, c), 0)
    hits = binid[None, :] == stepv  # (steps, C)
    cnt_ref[0, 0] = cnt_ref[0, 0] + jnp.sum(hits.astype(jnp.int32), axis=1)
    return binid


def _fused_slot_update(hw, d2, ids, halves, bd_ref, bi_ref, cnt_ref, *,
                       steps: int, ks: int):
    """Fold one tile's candidates into the per-step bin accumulators.

    ``bd/bi`` are (1, steps, ks) accumulators revisited across the
    tiles of the grid; ``merge_topk``'s drop-equal-(dist, id) step dedups
    cross-table duplicates within a bin exactly as the flat merge does.
    """
    binid = _bin_and_count(hw, halves, cnt_ref, steps=steps)
    for j in range(steps):
        m = binid == j

        @pl.when(jnp.any(m))
        def _merge(j=j, m=m):
            nd, ni = merge_topk(
                jnp.where(m, d2, _INF), ids, bd_ref[0, j], bi_ref[0, j], ks
            )
            bd_ref[0, j] = nd
            bi_ref[0, j] = ni


def _select_bins(d2, ids, binid, *, steps: int, ks: int):
    """Per-bin top-ks of one query's whole (S, B) candidate tile.

    ``d2`` (S, B) f32 squared distances, ``ids`` (S, B) i32 and ``binid``
    (S, B) i32 first admitting steps (``steps`` = never admitted).  The
    bins are reduced side by side as a (steps, S, B) array: one ks-round
    min-select with ``merge_topk``'s rule — smallest d², ties to the
    smallest id, every entry equal to the chosen (d², id) dropped (the
    cross-table duplicates) — so the result is the ks lexicographically
    smallest distinct pairs of each bin, ``fused_search_ref``'s contract.
    Returns (steps, 1, ks) distances (+inf padded) and ids (IMAX padded).
    """
    shape = (steps,) + d2.shape
    stepv = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cd = jnp.where(binid[None] == stepv, d2[None], _INF)
    ci = jnp.broadcast_to(ids[None], shape)
    rank = jax.lax.broadcasted_iota(jnp.int32, (steps, 1, ks), 2)

    def body(r, carry):
        cd, nd, ni = carry
        m = jnp.min(jnp.min(cd, axis=2, keepdims=True), axis=1, keepdims=True)
        eq = cd == m
        sel = jnp.min(
            jnp.min(jnp.where(eq, ci, _IMAX), axis=2, keepdims=True),
            axis=1, keepdims=True,
        )
        oh = rank == r
        nd = jnp.where(oh, m, nd)
        ni = jnp.where(oh & (m < _INF), sel, ni)
        cd = jnp.where(eq & (ci == sel), _INF, cd)
        return cd, nd, ni

    init = (cd, jnp.full((steps, 1, ks), _INF, jnp.float32),
            jnp.full((steps, 1, ks), _IMAX, jnp.int32))
    _, nd, ni = jax.lax.fori_loop(0, ks, body, init)
    return nd, ni


def fused_window_kernel(*refs, lnb: int, steps: int, ks: int, mode: str):
    """One-pass fused search over an 'inline' layout: select-slot DMA +
    halfwidth + distance + schedule binning + per-bin top-ks, one kernel.

    Grid (Q, S).  Scalar-prefetch block DMA exactly as
    ``window_dist_kernel``; candidates never reach HBM.  Each slot step
    writes its (d², id, bin) row into the query's (S, B) VMEM scratch
    tiles and adds its admitted slots to ``cnt``; the last slot step
    selects every bin's top-ks once over the whole tile
    (``_select_bins``) and writes the (1, steps, ks) bins.

    Blocks: g (1,1,1,K) of the slot's table, q (1,1,d), q2 (1,1,1); proj
    (1,B,K) and vec (1,B,d) of the selected block; norms and ids arrive
    as the aligned (8,B) row tile holding the block, of which row
    ``blk % 8`` is the block's.  Quantized modes take two extra refs: the
    per-query quant scale (qs, (1,1,1)) after q2 and the per-slot dequant
    scales (scl, an (8,B) row tile) after ids.  Scratch: d², ids and bin
    ids, (S, B) each, persisting across grid steps."""
    quant = mode in ("bf16", "int8")
    if quant:
        (blk_ref, halves_ref, g_ref, q_ref, q2_ref, qs_ref,
         proj_ref, vec_ref, nrm_ref, ids_ref, scl_ref,
         bd_ref, bi_ref, cnt_ref, d2_scr, id_scr, bin_scr) = refs
    else:
        (blk_ref, halves_ref, g_ref, q_ref, q2_ref,
         proj_ref, vec_ref, nrm_ref, ids_ref,
         bd_ref, bi_ref, cnt_ref, d2_scr, id_scr, bin_scr) = refs
    qi = pl.program_id(0)
    s = pl.program_id(1)
    S, B = d2_scr.shape

    # the scratch carries the previous query's rows: an invalid slot
    # skips its compute, so its row must read "never admitted"
    @pl.when(s == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        bin_scr[...] = jnp.full_like(bin_scr, steps)

    blk = blk_ref[qi, s]

    @pl.when(blk < lnb)
    def _compute():
        row = pl.ds(blk % _ROWS, 1)
        p = proj_ref[0]  # (B, K)
        g = g_ref[0, 0, 0]  # (K,)
        hw = jnp.max(jnp.abs(p - g[None, :]), axis=-1)  # (B,)
        d2 = _slot_d2(
            vec_ref[0], q_ref[0, 0], nrm_ref[row, :][0], q2_ref[0, 0, 0],
            mode=mode,
            xscale=scl_ref[row, :][0] if quant else None,
            qscale=qs_ref[0, 0, 0] if quant else None,
        )
        binid = _bin_and_count(hw, halves_ref[0], cnt_ref, steps=steps)
        slot = pl.ds(s, 1)
        d2_scr[slot, :] = d2.reshape(1, B)
        id_scr[slot, :] = ids_ref[row, :]
        bin_scr[slot, :] = binid.reshape(1, B)

    @pl.when(s == S - 1)
    def _select():
        nd, ni = _select_bins(
            d2_scr[...], id_scr[...], bin_scr[...], steps=steps, ks=ks
        )
        for j in range(steps):
            bd_ref[0, pl.ds(j, 1), :] = nd[j]
            bi_ref[0, pl.ds(j, 1), :] = ni[j]


def fused_cand_kernel(*refs, steps: int, ks: int, mode: str):
    """Gathered twin of ``fused_window_kernel``: grid (Q, L, Ct_tiles)
    over pre-gathered candidates (``kernel`` engine / 'gather' layout).
    Invalid slots carry +inf projections from the gather fill, so their
    hw = +inf keeps them out of every bin — no validity scalar needed."""
    quant = mode in ("bf16", "int8")
    if quant:
        (halves_ref, g_ref, q_ref, q2_ref, qs_ref,
         proj_ref, vec_ref, nrm_ref, ids_ref, scl_ref,
         bd_ref, bi_ref, cnt_ref) = refs
    else:
        (halves_ref, g_ref, q_ref, q2_ref,
         proj_ref, vec_ref, nrm_ref, ids_ref,
         bd_ref, bi_ref, cnt_ref) = refs
    li = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when((li == 0) & (t == 0))
    def _init():
        bd_ref[...] = jnp.full_like(bd_ref, _INF)
        bi_ref[...] = jnp.full_like(bi_ref, _IMAX)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    p = proj_ref[0, 0]  # (TC, K)
    g = g_ref[0, 0, 0]  # (K,)
    hw = jnp.max(jnp.abs(p - g[None, :]), axis=-1)  # (TC,)
    d2 = _slot_d2(
        vec_ref[0, 0], q_ref[0, 0], nrm_ref[0, 0, 0], q2_ref[0, 0, 0],
        mode=mode,
        xscale=scl_ref[0, 0, 0] if quant else None,
        qscale=qs_ref[0, 0, 0] if quant else None,
    )
    _fused_slot_update(
        hw, d2, ids_ref[0, 0, 0], halves_ref[0], bd_ref, bi_ref, cnt_ref,
        steps=steps, ks=ks,
    )
