"""Batched fixed-schedule DB-LSH search — the TPU serving path.

`query.search_batch` (vmapped `lax.while_loop`) is the paper-faithful
adaptive path: each query stops at its own radius. On a TPU serving a
batch of requests, data-dependent per-query schedules waste the lockstep
vector units, so production serving uses a *fixed* radius schedule: every
query runs ``steps`` probes r0, c·r0, …, c^{steps-1}·r0 with masked
updates after a query's termination condition fires (identical results
to the adaptive path whenever the adaptive path would have terminated
within ``steps``; the fixed path can only find *more*).

**One-pass incremental probing** (DESIGN.md §7).  The paper's query
cost argument (§IV-C) rests on windows *nesting* across the schedule:
W(G(q), w0·r) ⊆ W(G(q), w0·c·r).  The serving pipeline exploits this so
each unit of work happens exactly once for the whole schedule instead
of once per radius:

  1. **Select once.**  ``_select_blocks`` runs a single MINDIST-ordered
     MBR pass at the *final* radius.  Every earlier radius' block set is
     a subset of this one, recoverable by masking on the per-block
     window-overlap halfwidth — ``steps-1`` full MBR scans + top_k
     compactions disappear.
  2. **Verify once.**  The selected blocks of all L tables are gathered
     and verified in one batched pass over a flat (Qn, L·M·B) candidate
     axis, producing per-slot exact squared distances plus the slot's
     window halfwidth ``hw = max_k |p_k - g_k|`` (the smallest half
     window that admits it).  Total verify work collapses from
     Σ_j L·M·B to L·M·B.
  3. **Merge deltas.**  Per step only the newly-admitted slice
     (w_{j-1}/2 < hw ≤ w_j/2) is merged into the running top-k — a
     streaming top-k is exact because added candidates only push ranks
     down.  The merge is the sort-free k-step vectorized selection
     (`query.merge_dedup_topk`), one call per step for all tables.
  4. **MXU distances.**  ``||x||² - 2<q,x> + ||q||²`` with per-point
     squared norms precomputed at build time (``index.norm_blocks``)
     turns verification into one dot per candidate.  ``exact=True``
     restores materialized-diff distances (the norm trick changes fp32
     rounding); results are id-set/recall equivalent either way.

Three verify engines:
  * ``jnp``    — pure-XLA gather + verify (works everywhere; CPU default)
  * ``kernel`` — Pallas ``fused_cand_search`` on pre-gathered candidates
                 (either layout: the one-copy layout's Pallas engine)
  * ``inline`` — Pallas ``fused_window_search`` with scalar-prefetch
                 block DMA (zero-copy gather; requires
                 params.inline_vectors)

**The fused gathered step copies no (L, nb, ...) index array**
(DESIGN.md §13).  The device keeps ``proj_blocks``, ``ids_blocks`` and
``norm_blocks`` with nb as their minor dimension, so merging (L, nb)
into one gather axis relays out the whole array on every call: 6.06
GiB of step temporaries at n = 10M.  The ``kernel`` engine reads the
selected blocks' slots out of the stored arrays instead
(``_slot_gather``); the ``jnp`` pool path keeps the merge, which is
twice as fast at a shard's size.  The gathers run under the
``dblsh.gather`` scope inside ``dblsh.verify``, with the gather of the
candidates' data rows by id.  The inline engine's operands are the
kernel's own layout, and keep their relayout.

``search_batch_fixed_ref`` preserves the multi-pass (per-radius
re-selection) algorithm verbatim: it is the equivalence oracle for the
one-pass pipeline and the baseline of ``benchmarks/search_hotpath.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .index import DBLSHIndex
from .query import merge_dedup_topk
from .. import kernels

__all__ = [
    "Termination",
    "search_batch_fixed",
    "search_batch_fixed_ref",
    "search_batch_fixed_dispatch",
    "PendingSearch",
    "validate_engine",
    "validate_dtype",
    "ENGINES",
    "DTYPES",
    "TERM_EXHAUSTED",
    "TERM_C1",
    "TERM_C2",
]

_INF = jnp.inf

ENGINES = ("jnp", "kernel", "inline")
DTYPES = ("fp32", "bf16", "int8")


def validate_engine(engine: str) -> str:
    """The engine-name check shared by the search path and the store
    layer (collection defaults, service overrides)."""
    if engine not in ENGINES:
        raise ValueError(
            f"unknown engine {engine!r}: use " + " | ".join(ENGINES)
        )
    return engine


def validate_dtype(dtype: str, params=None, exact: bool = False) -> str:
    """Distance-dtype check for the serving path.

    ``fp32`` is the default exact-arithmetic path.  ``bf16``/``int8``
    route the in-kernel dots through the quantized blocks (top-4k
    shortlist + exact fp32 re-rank) and therefore need an index built
    with the matching ``params.quant_dtype``; ``exact=True`` asserts
    bit-fidelity to the multi-pass seed, which no quantized path can
    promise, so the combination is rejected outright."""
    if dtype not in DTYPES:
        raise ValueError(
            f"unknown dtype {dtype!r}: use " + " | ".join(DTYPES)
        )
    if dtype != "fp32":
        if exact:
            raise ValueError(
                f"exact=True requires dtype='fp32' (got {dtype!r}): the "
                "quantized path is a shortlist + re-rank, not bit-exact"
            )
        if params is not None and params.quant_dtype != dtype:
            raise ValueError(
                f"dtype={dtype!r} needs an index built with "
                f"quant_dtype={dtype!r} (index has "
                f"{params.quant_dtype!r}) — rebuild or derive params "
                "with quant_dtype set"
            )
    return dtype


@dataclasses.dataclass(frozen=True)
class Termination:
    """Paper terminate conditions (§IV-B/§IV-C) as a static schedule policy.

    ``termination=None`` (the default everywhere) keeps the plain fixed
    schedule: all ``steps`` radii run unrolled, with the C2 rule freezing
    finished queries' *results* exactly as before.  Passing a
    ``Termination`` turns the schedule into a ``lax.while_loop`` whose
    per-query ``done`` masks gate every delta merge, so terminated
    queries stop gathering/verifying work:

    * **C1** (``use_c1``): a query is done once its windows have admitted
      at least ``c1_budget`` verified candidate slots — the paper's
      candidate-count termination (``βn + k``, concretely ``2tL + k``;
      ``c1_budget=0`` derives it from the index params).  The count is
      over verified candidate *slots* (cross-table duplicates included):
      that is the unit of verification work the device actually performs,
      and it is computable from the per-slot admission halfwidths the
      verify engines already emit — no extra gather.
    * **C2** (``use_c2``): a query is done once its k-th best verified
      distance is ≤ c·r — a point within ``c·r`` at radius ``r``
      certifies a c²-approximate answer (the returned top-1 is within
      ``c²·r`` of the true NN).
    * **early exit** (``early_exit``): the while_loop stops as soon as
      every query in the batch is done.  Terminated queries' state is
      frozen by the masks, so the exit is bit-invisible in the results —
      it only skips device work.

    Frozen/hashable: a Termination is a static jit argument, one compiled
    program per distinct policy.
    """

    use_c1: bool = True
    c1_budget: int = 0  # 0 -> derive the paper budget 2tL + k from params
    use_c2: bool = True
    early_exit: bool = True


def _select_blocks(index: DBLSHIndex, G: jax.Array, w):
    """MINDIST-ordered fixed-capacity block selection for a query batch.

    G: (Q, L, K) query projections. Returns (blk, bhw): blk (L, Q, M)
    int32 (nb = invalid); bhw (L, Q, M) per-block window halfwidths —
    the L∞ box distance from the query projection to the block MBR, i.e.
    the smallest half window width whose window overlaps the block
    (+inf on invalid slots)."""
    p = index.params
    nb = index.nb

    def per_table(mbr_lo, mbr_hi, g):
        # g: (Q, K); mbr: (nb, K)
        lo = g[:, None, :] - 0.5 * w
        hi = g[:, None, :] + 0.5 * w
        overlap = jnp.all((mbr_lo[None] <= hi) & (mbr_hi[None] >= lo), axis=-1)
        # per-dim box distance (at most one term is positive for a valid
        # MBR, so the sum equals the clamped max)
        pd = jnp.maximum(mbr_lo[None] - g[:, None, :], 0.0) + jnp.maximum(
            g[:, None, :] - mbr_hi[None], 0.0
        )  # (Q, nb, K)
        mindist = jnp.sum(jnp.square(pd), axis=-1)  # (Q, nb)
        score = jnp.where(overlap, mindist, _INF)
        _, blk = jax.lax.top_k(-score, p.max_blocks)  # (Q, M)
        sel_ok = jnp.take_along_axis(overlap, blk, 1)
        bhw = jnp.take_along_axis(jnp.max(pd, axis=-1), blk, 1)
        return (
            jnp.where(sel_ok, blk, nb).astype(jnp.int32),
            jnp.where(sel_ok, bhw, _INF),
        )

    return jax.vmap(per_table)(index.mbr_lo, index.mbr_hi, jnp.swapaxes(G, 0, 1))


def _slot_gather(a: jax.Array, blk_q: jax.Array, M: int, fill):
    """The slots of the selected blocks, read from a stored per-table
    block array without copying it.

    a: (L, nb, B) or (L, nb, B, K); blk_q: (Qn, S) flattened cross-table
    block ids (S = L·M, table-major; sentinel L·nb).  Returns (Qn, L, M,
    B) or (Qn, L, M, B, K), ``fill`` on invalid slots.

    The device keeps these arrays with nb as the minor dimension (B = 64
    and K = 10 would pad to 128 lanes), so the trailing (B[, K]) slab of
    a block is not contiguous there: merging (L, nb) into one gather axis
    is a relayout of the whole array on every call.  The view with nb
    last, (L, B, nb) or (L·K·B, nb), is the stored order, and each slot
    is gathered as one element of it.  The proj gather runs one K column
    at a time: as one 2-D element gather of Qn·S·B·K indices it compiles
    for minutes."""
    L, nb, B = a.shape[:3]
    Qn = blk_q.shape[0]
    bq = blk_q.reshape(Qn, L, M, 1)
    t = jnp.arange(L, dtype=jnp.int32)[None, :, None, None]
    ok = bq < L * nb
    b = jnp.where(ok, bq - t * nb, 0)  # local block id, (Qn, L, M, 1)
    j = jnp.arange(B, dtype=jnp.int32)
    if a.ndim == 3:
        x = jnp.swapaxes(a, 1, 2)[t, j, b]  # (Qn, L, M, B)
        return jnp.where(ok, x, fill)
    K = a.shape[3]
    view = jnp.transpose(a, (0, 3, 2, 1)).reshape(L * K * B, nb)
    cols = jax.lax.map(lambda c: view[(t * K + c) * B + j, b],
                       jnp.arange(K, dtype=jnp.int32))  # (K, Qn, L, M, B)
    return jnp.where(ok[..., None], jnp.moveaxis(cols, 0, -1), fill)


def _gather_slots(index: DBLSHIndex, blk_q: jax.Array, dtype: str):
    """What the gathered engines read of the selected slots, under the
    ``dblsh.gather`` scope: projections (Qn, S, B, K) (+inf on invalid
    slots), ids (Qn, S, B) (n), squared norms (Qn, S, B) (+inf), the
    vectors (Qn, S, B, d) (0; the quantized blocks for a quantized
    ``dtype``) and their dequant scales (Qn, S, B) (1; ``None`` for
    fp32).  blk_q: (Qn, S) flattened cross-table block ids."""
    p = index.params
    L, M, B = p.L, p.max_blocks, p.block_size
    Qn, S = blk_q.shape
    lnb = L * index.nb

    def blocks(a, fill):  # the inline layout's (L, nb, B, d) copies
        return jnp.take(a.reshape(lnb, B, -1), blk_q, axis=0, mode="fill",
                        fill_value=fill)

    with jax.named_scope("dblsh.gather"):
        pb = _slot_gather(index.proj_blocks, blk_q, M, _INF).reshape(
            Qn, S, B, p.K)
        ib = _slot_gather(index.ids_blocks, blk_q, M, index.n).reshape(
            Qn, S, B)
        nrm = _slot_gather(index.norm_blocks, blk_q, M, _INF).reshape(
            Qn, S, B)
        sc = None
        if dtype != "fp32":
            vb = blocks(index.qvec_blocks, 0)
            sc = _slot_gather(index.qvec_scale, blk_q, M, 1.0).reshape(
                Qn, S, B)
        elif p.inline_vectors:
            vb = blocks(index.vec_blocks, 0.0)
        else:
            vb = jnp.take(
                index.data, ib.reshape(Qn, -1), axis=0, mode="fill",
                fill_value=0.0,
            ).reshape(Qn, S, B, -1)
    return pb, ib, nrm, vb, sc


def _gather_pool(index: DBLSHIndex, blk_q: jax.Array, G: jax.Array,
                 Q: jax.Array, exact: bool):
    """The 'jnp' engine's verify-once stage.

    blk_q: (Qn, S) flattened cross-table block ids (S = L·M, sentinel
    L·nb). Returns (d2, hw, ci): (Qn, C) exact squared distances, window
    halfwidths and ids over the C = S·B candidate slots, table-major.
    Slots are *not* window-masked — the schedule applies per-step masks
    on hw."""
    p = index.params
    L, M, B = p.L, p.max_blocks, p.block_size
    Qn, S = blk_q.shape
    lnb = L * index.nb

    # the whole-array (L·nb) merge, a relayout on every call: at a
    # 2.5M-row shard it costs 1.5 GiB of temporaries but half the time of
    # _slot_gather's element gathers (5.1 against 10.2 ms a batch of 32
    # on a v5e), and the sharded placement runs this engine
    def flat(a, fill):
        return jnp.take(a.reshape((lnb,) + a.shape[2:]), blk_q, axis=0,
                        mode="fill", fill_value=fill)

    with jax.named_scope("dblsh.gather"):
        pb = flat(index.proj_blocks, _INF)
        ib = flat(index.ids_blocks, index.n)
        nrm = flat(index.norm_blocks, _INF)
        if p.inline_vectors:
            vb = flat(index.vec_blocks, 0.0)
        else:
            vb = jnp.take(
                index.data, ib.reshape(Qn, -1), axis=0, mode="fill",
                fill_value=0.0,
            ).reshape(Qn, S, B, -1)

    g_rep = jnp.repeat(G, M, axis=1)  # (Qn, S, K)
    hw = jnp.max(jnp.abs(pb - g_rep[:, :, None, :]), axis=-1)  # (Qn, S, B)
    C = S * B
    if exact:
        d2 = jnp.sum(jnp.square(vb - Q[:, None, None, :]), axis=-1)
    else:
        q2 = jnp.sum(jnp.square(Q), axis=-1)  # (Qn,)
        # per-slot multiply + last-axis reduce (not a batched-matmul
        # einsum): the reduction order is then independent of the batch
        # shape, so the store layer's padded dispatch stays bit-identical
        # to an unpadded call.  The true MXU raising lives in the Pallas
        # engines, whose tile shapes never depend on Qn.
        dots = jnp.sum(vb * Q[:, None, None, :], axis=-1)  # (Qn, S, B)
        d2 = jnp.maximum(
            nrm - 2.0 * dots + q2[:, None, None], 0.0
        )
    return d2.reshape(Qn, C), hw.reshape(Qn, C), ib.reshape(Qn, C)


def _fused_bins(index: DBLSHIndex, blk_q: jax.Array, G: jax.Array,
                Q: jax.Array, halves: jax.Array, engine: str, exact: bool,
                dtype: str, ks: int, interpret):
    """Fused verify+bin stage: one pass over the selected slots emitting
    per-(query, step) top-ks *bin* accumulators instead of the (Qn, C)
    distance pool.

    Bin j holds the ks best distinct (d2, id) pairs among candidates
    whose window halfwidth first admits them at step j — exactly the
    step-j delta slice of the schedule (windows nest), so the epilogue's
    prefix merge reproduces the flat per-step merge bit-for-bit.  ``cnt``
    (Qn, steps) counts admitted candidate slots per bin; its cumsum is
    the C1 admission count.

    Engine routing: 'inline' streams blocks via scalar-prefetch DMA
    (candidates never reach HBM); 'kernel' runs the gathered twin; 'jnp'
    lands here only for quantized dtypes and computes the same bins in
    pure XLA (the CPU-parity twin of the quantized kernels)."""
    p = index.params
    nb = index.nb
    L, M, B = p.L, p.max_blocks, p.block_size
    Qn = Q.shape[0]
    n = index.n
    S = L * M
    mode = ("exact" if exact else "norm") if dtype == "fp32" else dtype

    if engine == "inline":
        if dtype == "fp32":
            xb, xs = index.vec_blocks.reshape(L * nb, B, -1), None
        else:
            xb = index.qvec_blocks.reshape(L * nb, B, -1)
            xs = index.qvec_scale.reshape(L * nb, B)
        return kernels.fused_window_search(
            blk_q, halves, index.proj_blocks.reshape(L * nb, B, p.K), xb,
            index.norm_blocks.reshape(L * nb, B),
            index.ids_blocks.reshape(L * nb, B), G, Q,
            M=M, ks=ks, n=n, mode=mode, interpret=interpret, x_scale=xs,
        )

    pb, ib, nrm, vb, sc = _gather_slots(index, blk_q, dtype)

    if engine == "kernel":
        return kernels.fused_cand_search(
            pb.reshape(Qn, L, M * B, p.K),
            vb.reshape(Qn, L, M * B, -1),
            nrm.reshape(Qn, L, M * B),
            ib.reshape(Qn, L, M * B),
            halves, G, Q, ks=ks, n=n, mode=mode, interpret=interpret,
            cand_scale=None if sc is None else sc.reshape(Qn, L, M * B),
        )

    # 'jnp' + quantized: pure-XLA twin of the quantized kernels
    C = S * B
    steps = halves.shape[0]
    g_rep = jnp.repeat(G, M, axis=1)  # (Qn, S, K)
    hw = jnp.max(jnp.abs(pb - g_rep[:, :, None, :]), axis=-1).reshape(Qn, C)
    q2 = jnp.sum(jnp.square(Q), axis=-1)
    if dtype == "bf16":
        qv = Q.astype(jnp.bfloat16)
        dots = jnp.sum(
            vb.astype(jnp.float32) * qv.astype(jnp.float32)[:, None, None, :],
            axis=-1,
        )
        df = dots
    else:  # int8
        amax = jnp.max(jnp.abs(Q), axis=-1, keepdims=True)
        qs = jnp.where(amax > 0.0, amax / 127.0, 1.0)
        qq = jnp.clip(jnp.round(Q / qs), -127.0, 127.0).astype(jnp.int32)
        idot = jnp.sum(vb.astype(jnp.int32) * qq[:, None, None, :], axis=-1)
        df = sc * qs[:, :, None] * idot.astype(jnp.float32)
    d2q = jnp.maximum(
        nrm - 2.0 * df + q2[:, None, None], 0.0
    ).reshape(Qn, C)
    ci = ib.reshape(Qn, C)
    binid = jnp.sum(
        (hw[:, :, None] > halves[None, None, :]).astype(jnp.int32), axis=-1
    )  # (Qn, C)
    cnt = jnp.sum(
        binid[:, :, None] == jnp.arange(steps)[None, None, :], axis=1,
        dtype=jnp.int32,
    )  # (Qn, steps)
    bd0 = jnp.full((Qn, ks), _INF)
    bi0 = jnp.full((Qn, ks), n, jnp.int32)
    bds, bis = [], []
    for j in range(steps):
        dj = jnp.where(binid == j, d2q, _INF)
        bd_j, bi_j = merge_dedup_topk(bd0, bi0, dj, ci, n, ks)
        bds.append(bd_j)
        bis.append(bi_j)
    return jnp.stack(bds, axis=1), jnp.stack(bis, axis=1), cnt


def _rerank_bins(index: DBLSHIndex, Q: jax.Array, bins_d, bins_i):
    """Exact fp32 re-rank of the quantized shortlist bins.

    Gathers the shortlisted data rows and recomputes norm-form distances
    in fp32, so the epilogue's merges — and with them the C2
    certification ``kth <= c*r`` — run on exact distances.  The only
    quantization-induced loss left is a true neighbor falling outside
    its bin's top-4k shortlist (the documented recall band)."""
    n = index.n
    Qn, steps, ks = bins_d.shape
    ids = bins_i.reshape(Qn, steps * ks)
    with jax.named_scope("dblsh.gather"):
        x = jnp.take(
            index.data, ids, axis=0, mode="fill", fill_value=0.0
        ).reshape(Qn, steps, ks, -1)
    nrm = jnp.sum(jnp.square(x), axis=-1)
    dots = jnp.sum(x * Q[:, None, None, :], axis=-1)
    q2 = jnp.sum(jnp.square(Q), axis=-1)
    d2 = jnp.maximum(nrm - 2.0 * dots + q2[:, None, None], 0.0)
    valid = (bins_i < n) & jnp.isfinite(bins_d)
    return jnp.where(valid, d2, _INF)


def _masked_delta_merge(best_d, best_i, delta, d2, ci, done, n, k):
    """One schedule-step merge: fold the newly-admitted delta slice into
    the running top-k with finished queries frozen — skipping the whole
    merge (``lax.cond``) when the delta is empty batch-wide.  Merging an
    all-masked delta is the identity, so the skip is bit-safe; it saves
    the O(k·C) selection on every step whose windows admit nothing
    anywhere in the batch (common late in an adaptive schedule and on
    sparse regions of a fixed one)."""

    def run(bd, bi):
        nd, ni = merge_dedup_topk(bd, bi, jnp.where(delta, d2, _INF), ci, n, k)
        return (
            jnp.where(done[:, None], bd, nd),
            jnp.where(done[:, None], bi, ni),
        )

    return jax.lax.cond(
        jnp.any(delta), run, lambda bd, bi: (bd, bi), best_d, best_i
    )


#: ``explain["term_cause"]`` codes: why a query's schedule stopped
#: advancing — C2 wins ties with C1 on the same step, mirroring the
#: mask-update order of the dispatch itself.  ``repro.obs.explain``
#: renders these into the human-readable record.
TERM_EXHAUSTED, TERM_C1, TERM_C2 = 0, 1, 2


@partial(
    jax.jit,
    static_argnames=(
        "k", "steps", "engine", "interpret", "with_stats", "exact",
        "termination", "with_explain", "dtype",
    ),
)
def search_batch_fixed(
    index: DBLSHIndex,
    Q: jax.Array,
    k: int = 0,
    r0: float = 1.0,
    steps: int = 8,
    engine: str = "jnp",
    interpret=None,
    with_stats: bool = False,
    exact: bool = False,
    termination: Termination | None = None,
    with_explain: bool = False,
    dtype: str = "fp32",
):
    """Fixed-schedule batched (c,k)-ANN — one-pass incremental probing.

    Args:
      index: built DBLSHIndex (engine='inline' needs inline_vectors=True).
      Q: (Qn, d) query batch.
      k, r0, steps: top-k, initial radius, schedule length.
      engine: 'jnp' | 'kernel' | 'inline'.  The Pallas engines run the
        *fully fused* one-pass kernel: select-slot DMA, halfwidths,
        distances, schedule admission and the per-step top-k merges all
        happen in-kernel via per-step bin accumulators — candidates
        never round-trip through HBM between select and the final (k,)
        result.  Results are identical to the 'jnp' pool path (bit-equal
        under ``exact=True``).
      with_stats: also return per-query probe statistics.
      exact: use materialized-diff distances instead of the MXU norm
        form (bit-compatible with :func:`search_batch_fixed_ref`).
        Requires ``dtype='fp32'``.
      termination: ``None`` runs the plain fixed schedule; a
        :class:`Termination` enables per-query adaptive termination
        (paper C1/C2 done masks + batch-wide while_loop early exit —
        the ``repro.tune`` subsystem's serving hook).
      with_explain: additionally return the per-query *per-step* arrays
        the stats reduce away (implies ``with_stats``): the EXPLAIN
        ANALYZE feed for ``repro.obs.explain``.  The result arrays and
        the done-mask updates are computed identically — explain only
        *observes* — so distances/ids are bit-equal to the
        ``with_explain=False`` program.
      dtype: 'fp32' (default) | 'bf16' | 'int8'.  The quantized dtypes
        compute candidate dots against the index's quantized blocks
        (``params.quant_dtype`` must match), shortlist the top-4k per
        schedule bin, and re-rank the shortlist in exact fp32 before
        the merges — so the C2 certificate stays sound and the only
        loss is a neighbor falling off its bin's shortlist (recall@10
        within 0.005 of fp32 on the benchmark workload; see
        DESIGN.md §13 for the error model).

    Returns: (Qn, k) distances ascending, (Qn, k) ids; with ``with_stats``
    a third element ``{"radius_steps": (Qn,) int32, "candidates": (Qn,)
    int32}`` — schedule steps run before the termination rule fired, and
    *distinct* candidate slots fetched while active: each selected block
    (all tables) counts its B slots once, at the step its window first
    overlaps it, and never while the query is already done.  Padded
    selection slots (blk == nb) are not work and are not counted.

    With ``with_explain`` a fourth element::

        {"step_half":    (steps,)     f32  per-step window halfwidths,
         "step_slots":   (Qn, steps)  i32  admitted-delta slots per step
                                           (rows sum to ``candidates``),
         "term_cause":   (Qn,)        i32  TERM_EXHAUSTED | TERM_C1 |
                                           TERM_C2 (first rule to fire),
         "final_radius": (Qn,)        f32  radius at termination (the
                                           certified radius under C2)}
    """
    validate_engine(engine)
    p = index.params
    validate_dtype(dtype, p, exact)
    if with_explain:
        with_stats = True
    k = k or p.k
    n = index.n
    Qn = Q.shape[0]
    nb = index.nb
    B = p.block_size
    L, M = p.L, p.max_blocks
    quant = dtype != "fp32"
    # Pallas engines (and every quantized dtype) run the fused bin path;
    # 'jnp' + fp32 keeps the seed's pool path verbatim
    use_bins = engine in ("kernel", "inline") or quant
    ks = 4 * k if quant else k  # quantized: top-4k shortlist per bin

    # named_scope labels are HLO metadata only (numerics-invariant): they
    # let a jax.profiler device trace line up with the host-side
    # store.dispatch spans by stage name (repro.obs, DESIGN.md §10)
    with jax.named_scope("dblsh.project"):
        G = jnp.einsum("lkd,qd->qlk", index.proj_vecs, Q)  # (Qn, L, K)

    # -------- select once, at the final radius (windows nest: every
    # earlier step's block set is this set masked on bhw)
    r_last = jnp.asarray(r0, jnp.float32)
    for _ in range(steps - 1):
        r_last = r_last * p.c
    with jax.named_scope("dblsh.select"):
        blk, bhw = _select_blocks(index, G, p.w0 * r_last)  # (L, Qn, M) each

        # flatten the table axis: one cross-table candidate pool
        offs = (jnp.arange(L, dtype=jnp.int32) * nb)[:, None, None]
        blk_flat = jnp.where(blk < nb, blk + offs, L * nb)  # (L, Qn, M)
        blk_q = jnp.swapaxes(blk_flat, 0, 1).reshape(Qn, L * M)

    # schedule half window widths, built by the same f32 multiply chain
    # the step loop runs — the in-kernel admission compares against the
    # bit-identical values the host masks would use
    halves_list, rr = [], jnp.asarray(r0, jnp.float32)
    for _ in range(steps):
        halves_list.append(0.5 * (p.w0 * rr))
        rr = rr * p.c
    halves_sched = jnp.stack(halves_list)  # (steps,)

    # -------- verify once: either the fused bin accumulators (Pallas
    # engines / quantized dtypes — per-step deltas and counters computed
    # in-kernel) or the (Qn, C) distance pool (the 'jnp' fp32 path)
    bins_d = bins_i = cum_adm = d2 = hw = ci = None
    with jax.named_scope("dblsh.verify"):
        if use_bins:
            bins_d, bins_i, bin_cnt = _fused_bins(
                index, blk_q, G, Q, halves_sched, engine, exact, dtype,
                ks, interpret,
            )
            if quant:
                bins_d = _rerank_bins(index, Q, bins_d, bins_i)
            # C1 admission count at step j == slots in bins 0..j
            cum_adm = jnp.cumsum(bin_cnt, axis=1)
        else:
            d2, hw, ci = _gather_pool(index, blk_q, G, Q, exact)

    bhw_q = jnp.swapaxes(bhw, 0, 1).reshape(Qn, L * M)  # (Qn, S)

    best_d = jnp.full((Qn, k), _INF)
    best_i = jnp.full((Qn, k), n, jnp.int32)
    done = jnp.zeros((Qn,), bool)
    radius_steps = jnp.zeros((Qn,), jnp.int32)
    candidates = jnp.zeros((Qn,), jnp.int32)
    # explain accumulators: fixed (Qn, steps)/(Qn,) shapes so the same
    # dict threads through the unrolled loop and the while_loop carry
    # (per-step writes land via a one-hot on the step index)
    ex = None
    if with_explain:
        ex = {
            "step_slots": jnp.zeros((Qn, steps), jnp.int32),
            "term_cause": jnp.full((Qn,), TERM_EXHAUSTED, jnp.int32),
            "final_radius": jnp.zeros((Qn,), jnp.float32),
        }

    c1_thr = None
    if termination is not None and termination.use_c1:
        c1_thr = (
            termination.c1_budget if termination.c1_budget > 0 else p.budget
        )
    use_c2 = True if termination is None else termination.use_c2

    def schedule_step(j, r, prev_half, best_d, best_i, done, radius_steps,
                      candidates, ex):
        half = 0.5 * (p.w0 * r)
        if with_stats:
            active = ~done
            radius_steps = radius_steps + active.astype(jnp.int32)
            newly = (bhw_q <= half) & (bhw_q > prev_half)  # (Qn, S)
            n_slots = jnp.sum(newly.astype(jnp.int32), axis=1) * B
            candidates = candidates + jnp.where(active, n_slots, 0)
            if with_explain:
                onehot = (jnp.arange(steps) == j).astype(jnp.int32)
                ex = dict(ex, step_slots=ex["step_slots"]
                          + jnp.where(active, n_slots, 0)[:, None] * onehot)

        # newly-admitted delta slice: slots whose window first reaches
        # them at this radius (hw = +inf slots never admit); finished
        # queries keep their result through the masked merge.  On the
        # fused path the delta IS bin j (the kernel binned candidates by
        # first-admitting step), so the merge folds ks pre-reduced
        # entries instead of the whole C-slot pool.
        with jax.named_scope("dblsh.merge"):
            if use_bins:
                cd = jnp.take(bins_d, j, axis=1)  # (Qn, ks)
                cids = jnp.take(bins_i, j, axis=1)
                best_d, best_i = _masked_delta_merge(
                    best_d, best_i, jnp.isfinite(cd), cd, cids, done, n, k
                )
            else:
                delta = (hw <= half) & (hw > prev_half)
                best_d, best_i = _masked_delta_merge(
                    best_d, best_i, delta, d2, ci, done, n, k
                )
        if use_c2:
            fired = best_d[:, k - 1] <= jnp.square(p.c * r)
            if with_explain:
                newly_done = fired & ~done
                ex = dict(
                    ex,
                    term_cause=jnp.where(newly_done, TERM_C2,
                                         ex["term_cause"]),
                    final_radius=jnp.where(newly_done, r,
                                           ex["final_radius"]),
                )
            done = done | fired
        if c1_thr is not None:
            # C1 from the halfwidths the verify engines already emitted:
            # slots the current window admits whose distance is finite
            # (verified work) — no extra gather/DMA to evaluate.  The
            # fused path's per-bin counters carry the same quantity:
            # cumsum(cnt)[j] == #{hw <= w_j/2} (admitted slots are live
            # slots, whose distances are always finite).
            if use_bins:
                n_adm = jnp.take(cum_adm, j, axis=1)  # (Qn,)
            else:
                n_adm = jnp.sum(
                    ((hw <= half) & jnp.isfinite(d2)).astype(jnp.int32),
                    axis=1,
                )
            fired = n_adm >= c1_thr
            if with_explain:
                newly_done = fired & ~done
                ex = dict(
                    ex,
                    term_cause=jnp.where(newly_done, TERM_C1,
                                         ex["term_cause"]),
                    final_radius=jnp.where(newly_done, r,
                                           ex["final_radius"]),
                )
            done = done | fired
        return half, best_d, best_i, done, radius_steps, candidates, ex

    if termination is None:
        r = jnp.asarray(r0, jnp.float32)
        prev_half = -_INF
        for j in range(steps):
            prev_half, best_d, best_i, done, radius_steps, candidates, ex = (
                schedule_step(j, r, prev_half, best_d, best_i, done,
                              radius_steps, candidates, ex)
            )
            r = r * p.c
    else:
        # adaptive schedule: same per-step body in a while_loop whose
        # carry threads (r, prev_half) through the identical multiply
        # chain (bit-equal radii), exiting as soon as every query's done
        # mask fired — frozen state makes the exit result-invisible
        def cond_fn(carry):
            j, _, _, _, _, done = carry[:6]
            more = j < steps
            if termination.early_exit:
                more = more & ~jnp.all(done)
            return more

        def body_fn(carry):
            j, r, prev_half, best_d, best_i, done, radius_steps, cands, ex = (
                carry
            )
            prev_half, best_d, best_i, done, radius_steps, cands, ex = (
                schedule_step(j, r, prev_half, best_d, best_i, done,
                              radius_steps, cands, ex)
            )
            return (j + 1, r * p.c, prev_half, best_d, best_i, done,
                    radius_steps, cands, ex)

        carry = (
            jnp.asarray(0, jnp.int32),
            jnp.asarray(r0, jnp.float32),
            jnp.asarray(-_INF, jnp.float32),
            best_d, best_i, done, radius_steps, candidates, ex,
        )
        (_, _, _, best_d, best_i, done, radius_steps, candidates, ex) = (
            jax.lax.while_loop(cond_fn, body_fn, carry)
        )

    if with_explain:
        # exhausted queries (cause 0) terminated at the schedule's final
        # radius; halves_sched replayed the same multiply chain the loop
        # ran, so it matches the admission masks bit-for-bit
        ex = dict(
            ex,
            step_half=halves_sched,
            final_radius=jnp.where(
                ex["term_cause"] == TERM_EXHAUSTED, r_last,
                ex["final_radius"],
            ),
        )
        stats = {"radius_steps": radius_steps, "candidates": candidates}
        return jnp.sqrt(best_d), best_i, stats, ex
    if with_stats:
        stats = {"radius_steps": radius_steps, "candidates": candidates}
        return jnp.sqrt(best_d), best_i, stats
    return jnp.sqrt(best_d), best_i


def _merge_dedup_topk_lexsort(run_d, run_i, new_d, new_i, n, k):
    """(Q, a) + (Q, b) -> (Q, k) dedup'd ascending merge (the multi-pass
    reference's lexsort merge, kept verbatim for bit-fidelity)."""
    d = jnp.concatenate([run_d, new_d], axis=1)
    i = jnp.concatenate([run_i, new_i], axis=1)

    def one(dq, iq):
        order = jnp.lexsort((dq, iq))
        ids_s = jnp.take(iq, order)
        d_s = jnp.take(dq, order)
        first = jnp.concatenate([jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]])
        d_s = jnp.where(first & (ids_s < n), d_s, _INF)
        neg, idx = jax.lax.top_k(-d_s, k)
        ids = jnp.take(ids_s, idx)
        return -neg, jnp.where(jnp.isfinite(-neg), ids, n)

    return jax.vmap(one)(d, i)


@partial(jax.jit, static_argnames=("k", "steps", "engine", "interpret", "with_stats"))
def search_batch_fixed_ref(
    index: DBLSHIndex,
    Q: jax.Array,
    k: int = 0,
    r0: float = 1.0,
    steps: int = 8,
    engine: str = "jnp",
    interpret=None,
    with_stats: bool = False,
):
    """Multi-pass reference: re-select, re-gather, and re-verify at every
    radius (the pre-one-pass serving algorithm, preserved verbatim).

    Used by the equivalence tests as the oracle for
    :func:`search_batch_fixed` (``exact=True`` pins bit-equal distances)
    and by ``benchmarks/search_hotpath.py`` as the speedup baseline.
    ``with_stats`` keeps the old accounting: every selected block slot
    recounts at every step it remains selected.
    """
    validate_engine(engine)
    p = index.params
    k = k or p.k
    n = index.n
    Qn = Q.shape[0]
    nb = index.nb
    B = p.block_size

    G = jnp.einsum("lkd,qd->qlk", index.proj_vecs, Q)  # (Qn, L, K)

    best_d = jnp.full((Qn, k), _INF)
    best_i = jnp.full((Qn, k), n, jnp.int32)
    done = jnp.zeros((Qn,), bool)
    radius_steps = jnp.zeros((Qn,), jnp.int32)
    candidates = jnp.zeros((Qn,), jnp.int32)

    r = jnp.asarray(r0, jnp.float32)
    for _ in range(steps):
        w = p.w0 * r
        blk, _ = _select_blocks(index, G, w)  # (L, Qn, M)
        if with_stats:
            active = ~done
            radius_steps = radius_steps + active.astype(jnp.int32)
            n_slots = jnp.sum((blk < nb).astype(jnp.int32), axis=(0, 2)) * B
            candidates = candidates + jnp.where(active, n_slots, 0)

        step_d = jnp.full((Qn, k), _INF)
        step_i = jnp.full((Qn, k), n, jnp.int32)
        for li in range(p.L):
            g_l = G[:, li, :]
            if engine == "inline":
                d_l, i_l = kernels.window_verify(
                    blk[li],
                    index.proj_blocks[li],
                    index.vec_blocks[li],
                    index.ids_blocks[li],
                    g_l,
                    Q,
                    w,
                    n=n,
                    k=k,
                    interpret=interpret,
                )
            else:
                pb = jnp.take(index.proj_blocks[li], blk[li], axis=0,
                              mode="fill", fill_value=_INF)  # (Qn,M,B,K)
                ib = jnp.take(index.ids_blocks[li], blk[li], axis=0,
                              mode="fill", fill_value=n)
                if p.inline_vectors:
                    vb = jnp.take(index.vec_blocks[li], blk[li], axis=0,
                                  mode="fill", fill_value=0.0)
                else:
                    vb = jnp.take(index.data, ib.reshape(Qn, -1), axis=0,
                                  mode="fill", fill_value=0.0)
                M = blk.shape[-1]
                cp = pb.reshape(Qn, M * B, p.K)
                cv = vb.reshape(Qn, M * B, -1)
                ci = ib.reshape(Qn, M * B)
                if engine == "kernel":
                    d_l, i_l = kernels.candidate_verify(
                        cp, cv, ci, g_l, Q, w, n=n, k=k, interpret=interpret
                    )
                else:  # 'jnp'
                    inbox = jnp.all(
                        jnp.abs(cp - g_l[:, None, :]) <= 0.5 * w, axis=-1
                    ) & (ci < n)
                    d2 = jnp.sum(jnp.square(cv - Q[:, None, :]), axis=-1)
                    d2 = jnp.where(inbox, d2, _INF)
                    d_l, i_l = jax.lax.top_k(-d2, k)
                    d_l = -d_l
                    i_l = jnp.where(jnp.isfinite(d_l),
                                    jnp.take_along_axis(ci, i_l, 1), n)
            step_d, step_i = _merge_dedup_topk_lexsort(
                step_d, step_i, d_l, i_l, n, k
            )

        # masked merge: finished queries keep their result
        nd, ni = _merge_dedup_topk_lexsort(best_d, best_i, step_d, step_i, n, k)
        best_d = jnp.where(done[:, None], best_d, nd)
        best_i = jnp.where(done[:, None], best_i, ni)
        done = done | (best_d[:, k - 1] <= jnp.square(p.c * r))
        r = r * p.c

    if with_stats:
        stats = {"radius_steps": radius_steps, "candidates": candidates}
        return jnp.sqrt(best_d), best_i, stats
    return jnp.sqrt(best_d), best_i


class PendingSearch:
    """Handle for an issued-but-not-awaited ``search_batch_fixed`` call.

    JAX dispatch is asynchronous: the jitted search returns device
    futures immediately, and the host only stalls when it *reads* them.
    This handle makes the two stages explicit so a serving loop can
    issue batch i+1 (host-side padding, slicing, queue work) while the
    device still executes batch i:

        pending = search_batch_fixed_dispatch(index, Q, k=10)
        ...host work for the next batch...
        dists, ids = pending.result()        # first host sync

    ``ready()`` is a non-blocking readiness probe (used by the store
    scheduler to opportunistically retire in-flight batches).
    """

    __slots__ = ("dists", "ids", "stats", "explain")

    def __init__(self, dists, ids, stats=None, explain=None):
        self.dists = dists
        self.ids = ids
        self.stats = stats
        self.explain = explain  # device-side per-step arrays, or None

    def _leaves(self):
        leaves = [self.dists, self.ids]
        if self.stats is not None:
            leaves.extend(jax.tree_util.tree_leaves(self.stats))
        if self.explain is not None:
            leaves.extend(jax.tree_util.tree_leaves(self.explain))
        return leaves

    def ready(self) -> bool:
        """True once every output buffer has materialized (never blocks)."""
        return all(
            x.is_ready() for x in self._leaves() if hasattr(x, "is_ready")
        )

    def result(self):
        """Block until complete; returns (dists, ids[, stats])."""
        jax.block_until_ready(self._leaves())
        if self.stats is not None:
            return self.dists, self.ids, self.stats
        return self.dists, self.ids


def search_batch_fixed_dispatch(
    index: DBLSHIndex,
    Q: jax.Array,
    k: int = 0,
    r0: float = 1.0,
    steps: int = 8,
    engine: str = "jnp",
    interpret=None,
    with_stats: bool = False,
    exact: bool = False,
    termination: Termination | None = None,
    with_explain: bool = False,
    dtype: str = "fp32",
) -> PendingSearch:
    """Issue a fixed-schedule search without blocking on the device.

    Same arguments and numerics as :func:`search_batch_fixed` (it *is*
    the same compiled program — bit-equality between the overlapped and
    synchronous paths is by construction), but the return value is a
    :class:`PendingSearch` whose ``result()`` performs the only host
    sync.  This is the dispatch half of the store scheduler's two-stage
    pipeline.
    """
    out = search_batch_fixed(
        index, Q, k=k, r0=r0, steps=steps, engine=engine,
        interpret=interpret, with_stats=with_stats, exact=exact,
        termination=termination, with_explain=with_explain, dtype=dtype,
    )
    if with_explain:
        return PendingSearch(out[0], out[1], out[2], out[3])
    if with_stats:
        return PendingSearch(out[0], out[1], out[2])
    return PendingSearch(out[0], out[1])
