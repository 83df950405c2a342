"""Pallas IVF list-DMA kernels: stream ONLY probed buckets through VMEM.

The XLA IVF path (`ivf_flat.ivf_scan_scores`) gathers each probed bucket
into a fresh [b, cap_list, d] HBM array per probe rank and then reads it
again for the distance einsum — 3x the necessary HBM traffic, plus it
cannot skip padded ranks. These kernels use scalar-prefetched bucket ids
as the BlockSpec index_map, so the Pallas pipeline DMAs exactly one probed
bucket [cap_list, d] from HBM to VMEM per grid step (double-buffered), and
the distance + running top-k merge happen in VMEM with nothing written
back but the final [b, k].

Replaces the hot loop the reference runs through faiss's IVF scanners over
src/simd/hook.cc kernels (vector_index_ivf_flat.cc search path).

Two loop orders over the same (query, probed bucket) pairs, same answers:

  query-major  `ivf_list_topk` / `ivf_pruned_topk`: grid (b, budget[, nblk]),
               the output block of query q resident across its own probes.
               One step multiplies ONE query row with a bucket tile, so the
               dimension-blocked variant can stop a candidate early (PDX);
               a bucket is read once per query that probes it.
  batch-major  `ivf_batch_topk`: grid (touched buckets,), the whole [b, k]
               top-k resident. One step reads a bucket ONCE and multiplies
               it with all b queries on the MXU; pairs whose query did not
               probe the bucket are masked. No pruning.

`ivf_probe_scan` picks one from the request's own shape at trace time
(`scan_arm`): with fewer than ROW_BLOCK queries a bucket is hardly ever
shared and an M = 1 step that prunes wins; from ROW_BLOCK queries on, the
query-major grid pays b x budget x nblk steps of ~1 us each for mat-vecs
(19 ms at b = 64 on a v5e) where the batch-major grid pays one ~2-us step
a touched bucket (PERF.md, PR 28).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dingo_tpu.ops.pallas_topk import _select_topk
from dingo_tpu.obs.sentinel import sentinel_jit

NEG_INF = float("-inf")
#: output lane padding (TPU lane width; k slots live in the first k lanes)
OUT_PAD = 128
#: sublane-aligned row blocking for per-query arrays (batch padded to this)
ROW_BLOCK = 8


def _ivf_kernel(vp_ref, q_ref, qsq_ref, x_ref, xsq_ref, val_ref, slot_ref,
                outv_ref, outi_ref, *, k, ascending):
    # Mosaic's tiling rule rejects blocks with a size-1 sublane dim on a
    # larger array (observed on-chip round 3), so queries/qsq/outputs
    # arrive as 8-row sublane-aligned blocks (index q // 8) and the kernel
    # addresses its query's row within the block with a dynamic slice —
    # VMEM stays O(1) in the batch, unlike full-batch blocks. The grid is
    # query-major, so all 8 rows of an output block are initialized and
    # filled by their own queries before the block index advances.
    qi = pl.program_id(0)
    r = pl.program_id(1)
    row = pl.ds(jax.lax.rem(qi, ROW_BLOCK), 1)

    @pl.when(r == 0)
    def _init():
        outv_ref[row, :] = jnp.full(
            (1, outv_ref.shape[1]), NEG_INF, jnp.float32
        )
        outi_ref[row, :] = jnp.full(
            (1, outi_ref.shape[1]), -1, jnp.int32
        )

    @pl.when(vp_ref[qi, r] >= 0)
    def _scan_bucket():
        q = q_ref[row, :]                                # [1, d]
        x = x_ref[0].astype(jnp.float32)                 # [cap, d]
        dots = jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )                                                # [1, cap]
        if ascending:   # L2 score = -(||q||^2 - 2qx + ||x||^2)
            scores = -(qsq_ref[row, :] - 2.0 * dots + xsq_ref[0])
        else:           # IP
            scores = dots
        scores = jnp.where(val_ref[0] > 0.5, scores, NEG_INF)
        slot = slot_ref[0].astype(jnp.int32)             # [1, cap]
        blk_v, blk_i = _select_topk(scores, slot, k)
        cur_v = outv_ref[row, :]
        cur_i = outi_ref[row, :]
        cat_v = jnp.concatenate([cur_v[:, :k], blk_v], axis=1)
        cat_i = jnp.concatenate([cur_i[:, :k], blk_i], axis=1)
        new_v, new_i = _select_topk(cat_v, cat_i, k)
        pad = outv_ref.shape[1] - k
        outv_ref[row, :] = jnp.concatenate(
            [new_v, jnp.full((1, pad), NEG_INF, jnp.float32)], axis=1
        )
        outi_ref[row, :] = jnp.concatenate(
            [new_i, jnp.full((1, pad), -1, jnp.int32)], axis=1
        )

    @pl.when(r == pl.num_programs(1) - 1)
    def _finish():
        fv = outv_ref[row, :]
        # -inf picks carry arbitrary slots; normalize to -1 like the XLA path
        outi_ref[row, :] = jnp.where(jnp.isneginf(fv), -1, outi_ref[row, :])


@sentinel_jit("ops.pallas.ivf_list_topk",
              static_argnames=("k", "ascending", "interpret", "nq"))
def ivf_list_topk(
    vprobes: jax.Array,        # [b, budget] int32 virtual bucket ids (-1 pad)
    queries: jax.Array,        # [b, d] f32
    buckets: jax.Array,        # [B, cap, d]
    bucket_sqnorm: jax.Array,  # [B, cap] f32
    bucket_valid: jax.Array,   # [B, cap] bool/float
    bucket_slot: jax.Array,    # [B, cap] int32
    k: int,
    ascending: bool = True,
    interpret: bool = False,
    nq: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Fused probed-bucket scan -> (scores[b, k], slots[b, k]).

    Scores follow the 'larger is better' convention (negated L2 when
    ascending); slots are -1 where fewer than k valid rows were probed.
    `nq` clamps the query grid to the REAL batch: arrays stay padded to
    ROW_BLOCK rows (Mosaic tiling), but padded rows get no grid steps —
    without the clamp a b=1 batch paid 8x the grid (and each dead step
    still DMA'd bucket 0's [cap, d] tile through VMEM).
    """
    b, d = queries.shape
    nb, cap, _ = buckets.shape
    budget = vprobes.shape[1]
    nq = nq or b
    q32 = queries.astype(jnp.float32)
    qsq = jnp.einsum(
        "bd,bd->b", q32, q32, precision=jax.lax.Precision.HIGHEST
    )[:, None]
    # index_map reads the prefetched probes; clamp padded (-1) ranks to
    # bucket 0 — the kernel body skips them via pl.when
    def bucket_map(q, r, vp):
        return (jnp.maximum(vp[q, r], 0), 0, 0)

    # row metadata rides as [B, 1, cap] so each block is (1, 1, cap): the
    # last two dims equal the array's — Mosaic rejects (1, cap) blocks on
    # [B, cap] (size-1 sublane on a larger array). Per-query arrays ride
    # as ROW_BLOCK-row blocks so VMEM stays O(1) in the batch.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, budget),
        in_specs=[
            pl.BlockSpec(
                (ROW_BLOCK, d), lambda q, r, vp: (q // ROW_BLOCK, 0)
            ),                                                    # queries
            pl.BlockSpec(
                (ROW_BLOCK, 1), lambda q, r, vp: (q // ROW_BLOCK, 0)
            ),                                                    # qsq
            pl.BlockSpec((1, cap, d), bucket_map),                # bucket data
            pl.BlockSpec((1, 1, cap), bucket_map),                # sqnorm
            pl.BlockSpec((1, 1, cap), bucket_map),                # valid
            pl.BlockSpec((1, 1, cap), bucket_map),                # slots
        ],
        out_specs=[
            pl.BlockSpec(
                (ROW_BLOCK, OUT_PAD), lambda q, r, vp: (q // ROW_BLOCK, 0)
            ),
            pl.BlockSpec(
                (ROW_BLOCK, OUT_PAD), lambda q, r, vp: (q // ROW_BLOCK, 0)
            ),
        ],
    )
    out_v, out_i = pl.pallas_call(
        functools.partial(_ivf_kernel, k=k, ascending=ascending),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.float32),
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.int32),
        ],
        interpret=interpret,
    )(
        vprobes,
        q32,
        qsq,
        buckets,
        bucket_sqnorm[:, None, :],
        bucket_valid.astype(jnp.float32)[:, None, :],
        bucket_slot[:, None, :],
    )
    return out_v[:, :k], out_i[:, :k]


def _pad_rows(queries, vprobes):
    """Pad the per-query arrays to the ROW_BLOCK sublane multiple (padded
    queries probe nothing: vprobes -1)."""
    pad = (-queries.shape[0]) % ROW_BLOCK
    if pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((pad, queries.shape[1]), queries.dtype)]
        )
        vprobes = jnp.concatenate(
            [vprobes, jnp.full((pad, vprobes.shape[1]), -1, vprobes.dtype)]
        )
    return queries, vprobes


def touched_buckets(vprobes: jax.Array, nbuckets: int):
    """The batch's probe SCHEDULE: every bucket some query probes, once.

    vprobes [b, budget] (-1 pad) -> (sched [S] int32, count int32) with
    S = min(nbuckets, b * budget) static; sched[:count] holds the touched
    bucket ids in ascending order, entries past count repeat the last id
    (the grid's block index then does not change, so the pipeline fetches
    nothing for a padded step)."""
    b, budget = vprobes.shape
    steps = min(nbuckets, b * budget)
    flat = vprobes.reshape(-1)
    hit = jnp.zeros((nbuckets + 1,), jnp.bool_).at[
        jnp.where(flat >= 0, flat, nbuckets)
    ].set(True)[:nbuckets]
    count = jnp.sum(hit, dtype=jnp.int32)
    ids = jnp.nonzero(hit, size=steps, fill_value=0)[0].astype(jnp.int32)
    last = ids[jnp.maximum(count - 1, 0)]
    sched = jnp.where(jnp.arange(steps, dtype=jnp.int32) < count, ids, last)
    return sched, count


def _ivf_batch_kernel(sched_ref, cnt_ref, vp_ref, q_ref, qsq_ref, x_ref,
                      xsq_ref, val_ref, slot_ref, *rest, k, ascending, sq):
    """Batch-major list scan: grid step s streams bucket sched[s] through
    VMEM ONCE and scores the whole batch against it with one MXU matmul
    [b, d] x [d, cap]. A (query, candidate) pair counts only if the
    query's own probes hold this bucket (vector compare of the resident
    [b, budget] probes with the step's bucket id) and the row is valid, so
    each query sees exactly the rows the query-major kernels show it. The
    [b, k] running top-k stays resident in the output block for the whole
    grid. The k rounds of select run per ROW_BLOCK of queries and only
    where some candidate beats a row's running k-th best: of ~1,100 steps
    a batch of 64 takes, a query's shortlist changes in a handful."""
    if sq:
        vmin_ref, scale_ref, outv_ref, outi_ref, sc_ref = rest
    else:
        outv_ref, outi_ref, sc_ref = rest
    s = pl.program_id(0)
    b = q_ref.shape[0]
    pad = outv_ref.shape[1] - k

    @pl.when(s == 0)
    def _init():
        outv_ref[:] = jnp.full(outv_ref.shape, NEG_INF, jnp.float32)
        outi_ref[:] = jnp.full(outi_ref.shape, -1, jnp.int32)

    @pl.when(s < cnt_ref[0])
    def _scan_bucket():
        q = q_ref[:]                                     # [b, d]
        x = x_ref[0]                                     # [cap, d]
        if sq:
            # the sq8 tier's compute contract (ops/sq.py), as the pruned
            # kernel: decode in f32, multiply in bf16, accumulate in f32
            x = (
                x.astype(jnp.int32).astype(jnp.float32) * scale_ref[:]
                + vmin_ref[:]
            ).astype(jnp.bfloat16)
            q = q.astype(jnp.bfloat16)
        else:
            x = x.astype(jnp.float32)
        dots = jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(None if sq else jax.lax.Precision.HIGHEST),
        )                                                # [b, cap]
        if ascending:   # L2 score = -(||q||^2 - 2qx + ||x||^2)
            scores = -(qsq_ref[:] - 2.0 * dots + xsq_ref[0])
        else:           # IP
            scores = dots
        member = jnp.max(
            jnp.where(vp_ref[:] == sched_ref[s], 1.0, 0.0),
            axis=1, keepdims=True,
        )                                                # [b, 1]
        live = (member > 0.5) & (val_ref[0] > 0.5)       # [b, cap]
        scores = jnp.where(live, scores, NEG_INF)
        beats = jnp.where(scores > outv_ref[:, k - 1:k], 1.0, 0.0)

        @pl.when(jnp.sum(beats) > 0.5)
        def _merge():
            sc_ref[:] = scores
            slot = slot_ref[0].astype(jnp.int32)         # [1, cap]

            def group(g, carry):
                rows = pl.ds(pl.multiple_of(g * ROW_BLOCK, ROW_BLOCK),
                             ROW_BLOCK)
                sc = sc_ref[rows, :]                     # [8, cap]
                cur_v = outv_ref[rows, :]
                better = jnp.where(sc > cur_v[:, k - 1:k], 1.0, 0.0)

                @pl.when(jnp.sum(better) > 0.5)
                def _select():
                    blk_v, blk_i = _select_topk(
                        sc, jnp.broadcast_to(slot, sc.shape), k)
                    cur_i = outi_ref[rows, :]
                    cat_v = jnp.concatenate([cur_v[:, :k], blk_v], axis=1)
                    cat_i = jnp.concatenate([cur_i[:, :k], blk_i], axis=1)
                    new_v, new_i = _select_topk(cat_v, cat_i, k)
                    outv_ref[rows, :] = jnp.concatenate(
                        [new_v,
                         jnp.full((ROW_BLOCK, pad), NEG_INF, jnp.float32)],
                        axis=1,
                    )
                    outi_ref[rows, :] = jnp.concatenate(
                        [new_i, jnp.full((ROW_BLOCK, pad), -1, jnp.int32)],
                        axis=1,
                    )

                return carry

            jax.lax.fori_loop(0, b // ROW_BLOCK, group, 0)

    @pl.when(s == pl.num_programs(0) - 1)
    def _finish():
        # -inf picks carry arbitrary slots; normalize to -1 like the XLA path
        outi_ref[:] = jnp.where(jnp.isneginf(outv_ref[:]), -1, outi_ref[:])


@sentinel_jit("ops.pallas.ivf_batch_topk",
              static_argnames=("k", "ascending", "interpret"))
def ivf_batch_topk(
    vprobes: jax.Array,        # [b, budget] int32 virtual bucket ids (-1 pad)
    queries: jax.Array,        # [b, d] f32, b a multiple of ROW_BLOCK
    buckets: jax.Array,        # [B, cap, d] rows (f32/bf16) or codes (uint8)
    bucket_sqnorm: jax.Array,  # [B, cap] f32 (decoded) norms
    bucket_valid: jax.Array,   # [B, cap] bool/float
    bucket_slot: jax.Array,    # [B, cap] int32
    sq_vmin,                   # [d] f32 codec params (None for float rows)
    sq_scale,
    k: int,
    ascending: bool = True,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batch-major probed-bucket scan -> (scores[b, k], slots[b, k],
    touched-bucket count). Same answers as ivf_list_topk (no dimension
    pruning: on the MXU a dead candidate's lanes cost nothing to carry),
    but each bucket the batch probes is read from HBM once and multiplied
    with all b queries, where the query-major grids read it once per
    probing query for an M = 1 mat-vec."""
    b, d = queries.shape
    nb, cap, _ = buckets.shape
    assert b % ROW_BLOCK == 0, f"batch {b} not a multiple of {ROW_BLOCK}"
    sq = sq_vmin is not None
    sched, count = touched_buckets(vprobes, nb)
    q32 = queries.astype(jnp.float32)
    qsq = jnp.einsum(
        "bd,bd->b", q32, q32, precision=jax.lax.Precision.HIGHEST
    )[:, None]
    # membership compares ride full lane tiles (-1 never equals a bucket)
    lanes = (-vprobes.shape[1]) % OUT_PAD
    vp = jnp.pad(vprobes, ((0, 0), (0, lanes)), constant_values=-1)

    def whole(s, sched, cnt):
        return (0, 0)

    def bucket_map(s, sched, cnt):
        return (sched[s], 0, 0)

    in_specs = [
        pl.BlockSpec(vp.shape, whole),                        # probes
        pl.BlockSpec((b, d), whole),                          # queries
        pl.BlockSpec((b, 1), whole),                          # qsq
        pl.BlockSpec((1, cap, d), bucket_map),                # bucket rows
        pl.BlockSpec((1, 1, cap), bucket_map),                # sqnorm
        pl.BlockSpec((1, 1, cap), bucket_map),                # valid
        pl.BlockSpec((1, 1, cap), bucket_map),                # slots
    ]
    args = [
        vp, q32, qsq, buckets,
        bucket_sqnorm[:, None, :],
        bucket_valid.astype(jnp.float32)[:, None, :],
        bucket_slot[:, None, :],
    ]
    if sq:
        in_specs += [pl.BlockSpec((1, d), whole)] * 2
        args += [sq_vmin[None, :], sq_scale[None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(sched.shape[0],),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((b, OUT_PAD), whole)] * 2,
        scratch_shapes=[pltpu.VMEM((b, cap), jnp.float32)],   # step scores
    )
    out_v, out_i = pl.pallas_call(
        functools.partial(_ivf_batch_kernel, k=k, ascending=ascending, sq=sq),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.float32),
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.int32),
        ],
        interpret=interpret,
    )(sched, count[None], *args)
    return out_v[:, :k], out_i[:, :k], count


def _ivf_pruned_kernel(vp_ref, q_ref, qsq_ref, qpsq_ref, x_ref, bsq_ref,
                       xsq_ref, val_ref, slot_ref, *rest,
                       k, ascending, nblk, check_every, sq, inbucket):
    """Dimension-blocked early-pruning list scan (PDX on TPU).

    Grid (q, r, jb) with the dimension block jb INNERMOST: for each probed
    bucket the kernel streams one [cap, dblk] tile per step, accumulates
    the partial dot in VMEM scratch, and after each block masks out
    candidates whose partial-distance bound already cannot beat the
    running k-th best (read from the resident output block). A bucket
    whose candidates are ALL dead skips the remaining blocks' compute
    entirely. Bounds:

      L2: partial dist through block j = qpsq[j] - 2*cum + xpsq[j] is a
          LOWER bound of the final distance (remaining blocks add >= 0),
          so -partial is an upper bound of the final score.
      IP: cum + sqrt(qtail[j] * xtail[j]) (Cauchy-Schwarz on the unseen
          dimension suffix) is an upper bound of the final dot.

    A candidate is pruned only when its upper bound is STRICTLY below the
    running k-th best, so results match the non-pruning kernels exactly
    (up to f32 partial-sum rounding on the reported distances).

    With `inbucket` (FLAGS.ivf_prune_inbucket_bound) the threshold also
    REFRESHES between dimension blocks inside a bucket: every alive
    candidate carries a suffix-norm LOWER bound of its final score
    (L2: dist <= partial + (|q_tail| + |x_tail|)^2 by the triangle
    inequality; IP: dot >= cum - |q_tail||x_tail| by Cauchy-Schwarz), and
    the k-th largest lower bound among them is a valid prune threshold
    even though none of these candidates has reached the shortlist merge
    yet. Early buckets — where the output block still reads -inf — start
    pruning from block 1 instead of scanning fully.

    Stats output lanes (accumulated per query): 0 = candidate-block pairs
    actually scanned, 1 = candidate-block pairs total, 2 = candidates
    scanned to the last block, 3 = candidates considered.
    """
    if sq:
        (vmin_ref, scale_ref, outv_ref, outi_ref, outs_ref,
         cum, alive, xpsq) = rest
    else:
        outv_ref, outi_ref, outs_ref, cum, alive, xpsq = rest
    qi = pl.program_id(0)
    r = pl.program_id(1)
    jb = pl.program_id(2)
    row = pl.ds(jax.lax.rem(qi, ROW_BLOCK), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, outs_ref.shape[1]), 1)

    @pl.when((r == 0) & (jb == 0))
    def _init_out():
        outv_ref[row, :] = jnp.full(
            (1, outv_ref.shape[1]), NEG_INF, jnp.float32
        )
        outi_ref[row, :] = jnp.full((1, outi_ref.shape[1]), -1, jnp.int32)
        outs_ref[row, :] = jnp.zeros((1, outs_ref.shape[1]), jnp.float32)

    @pl.when(vp_ref[qi, r] >= 0)
    def _scan_bucket():
        @pl.when(jb == 0)
        def _init_bucket():
            cum[:] = jnp.zeros_like(cum)
            xpsq[:] = jnp.zeros_like(xpsq)
            alive[:] = val_ref[0]
            nvalid = jnp.sum(val_ref[0])
            outs_ref[row, :] += jnp.where(
                lanes == 1, nvalid * nblk,
                jnp.where(lanes == 3, nvalid, 0.0),
            )

        nalive = jnp.sum(alive[:])
        outs_ref[row, :] += jnp.where(lanes == 0, nalive, 0.0)

        @pl.when((jb == nblk - 1))
        def _count_full():
            outs_ref[row, :] += jnp.where(lanes == 2, nalive, 0.0)

        @pl.when(nalive > 0.5)
        def _compute():
            q = q_ref[row, :]                          # [1, dblk]
            x = x_ref[0]                               # [cap, dblk]
            if sq:
                # decode in f32, multiply in bf16 with f32 accumulation —
                # the sq8 tier's compute contract (ops/sq.py): native
                # bf16 MXU matmul fed by 1-byte HBM reads. The codes
                # widen via int32: Mosaic has no direct uint8 -> f32 cast
                x = (
                    x.astype(jnp.int32).astype(jnp.float32) * scale_ref[:]
                    + vmin_ref[:]
                ).astype(jnp.bfloat16)
                q = q.astype(jnp.bfloat16)
            else:
                x = x.astype(jnp.float32)
            dots = jax.lax.dot_general(
                q, x, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=(None if sq else jax.lax.Precision.HIGHEST),
            )                                          # [1, cap]
            cum[:] += dots
            xpsq[:] += bsq_ref[0, pl.ds(jb, 1), :]     # [1, cap]
            bound = outv_ref[row, :][:, k - 1:k]       # running k-th best
            qpsq_j = qpsq_ref[0, row, :]               # [1, 1] prefix
            qtail = jnp.maximum(qsq_ref[row, :] - qpsq_j, 0.0)
            xtail = jnp.maximum(xsq_ref[0] - xpsq[:], 0.0)
            if ascending:
                partial = qpsq_j - 2.0 * cum[:] + xpsq[:]
                ub = -partial
                final = ub
            else:
                ub = cum[:] + jnp.sqrt(qtail * xtail)
                final = cum[:]

            @pl.when((jb < nblk - 1)
                     & (jax.lax.rem(jb + 1, check_every) == 0))
            def _prune():
                bnd = bound
                if inbucket:
                    # within-bucket refresh (PDX finer threshold): each
                    # alive candidate's final score is >= its suffix-norm
                    # LOWER bound, so the k-th largest lower bound among
                    # this bucket's alive candidates is itself a valid
                    # prune threshold — usable blocks before any of them
                    # reaches the shortlist merge. A candidate can never
                    # prune itself: ub >= lb always, so ub < kth-lb
                    # implies its own lb is below the top-k lb set.
                    if ascending:
                        tail = jnp.sqrt(qtail) + jnp.sqrt(xtail)
                        lb = -(partial + tail * tail)
                    else:
                        lb = cum[:] - jnp.sqrt(qtail * xtail)
                    # f32 safety shave: the bound math is exact in real
                    # arithmetic; keep rounding on the conservative side
                    lb = lb - 1e-5 * jnp.abs(lb) - 1e-6
                    lb = jnp.where(alive[:] > 0.5, lb, NEG_INF)
                    lb_k, _ = _select_topk(
                        lb, slot_ref[0].astype(jnp.int32), k
                    )
                    bnd = jnp.maximum(bnd, lb_k[:, k - 1:k])
                alive[:] = jnp.where(ub < bnd, 0.0, alive[:])

            @pl.when(jb == nblk - 1)
            def _merge():
                scores = jnp.where(alive[:] > 0.5, final, NEG_INF)
                slot = slot_ref[0].astype(jnp.int32)
                blk_v, blk_i = _select_topk(scores, slot, k)
                cur_v = outv_ref[row, :]
                cur_i = outi_ref[row, :]
                cat_v = jnp.concatenate([cur_v[:, :k], blk_v], axis=1)
                cat_i = jnp.concatenate([cur_i[:, :k], blk_i], axis=1)
                new_v, new_i = _select_topk(cat_v, cat_i, k)
                pad = outv_ref.shape[1] - k
                outv_ref[row, :] = jnp.concatenate(
                    [new_v, jnp.full((1, pad), NEG_INF, jnp.float32)],
                    axis=1,
                )
                outi_ref[row, :] = jnp.concatenate(
                    [new_i, jnp.full((1, pad), -1, jnp.int32)], axis=1
                )

    @pl.when((r == pl.num_programs(1) - 1) & (jb == nblk - 1))
    def _finish():
        fv = outv_ref[row, :]
        outi_ref[row, :] = jnp.where(jnp.isneginf(fv), -1, outi_ref[row, :])


@sentinel_jit("ops.pallas.ivf_pruned_topk",
              static_argnames=("k", "ascending", "dim_block", "check_every",
                               "interpret", "nq", "sq", "inbucket"))
def ivf_pruned_topk(
    vprobes: jax.Array,        # [b, budget] int32 virtual bucket ids (-1 pad)
    queries: jax.Array,        # [b, d] f32
    qpsq: jax.Array,           # [b, nblk] f32 inclusive per-block prefixes
    buckets: jax.Array,        # [B, cap, d] rows (f32/bf16) or codes (uint8)
    bucket_bsq: jax.Array,     # [B, nblk, cap] f32 per-block (decoded) norms
    bucket_sqnorm: jax.Array,  # [B, cap] f32 total (decoded) norms
    bucket_valid: jax.Array,   # [B, cap] bool/float
    bucket_slot: jax.Array,    # [B, cap] int32
    sq_vmin,                   # [d] f32 codec params (None for float rows)
    sq_scale,
    k: int,
    dim_block: int,
    ascending: bool = True,
    check_every: int = 1,
    interpret: bool = False,
    nq: int = 0,
    sq: bool = False,
    inbucket: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Early-pruning probed-bucket scan -> (scores, slots, stats).

    Same contract as ivf_list_topk plus a [b, OUT_PAD] stats output (see
    _ivf_pruned_kernel lanes) the caller turns into pruned-fraction
    metrics. The [B, cap, d] bucket array is NOT physically re-laid-out:
    the (1, cap, dim_block) BlockSpec tile IS the PDX vertical access
    pattern (one dimension block of every candidate per DMA)."""
    b, d = queries.shape
    nb, cap, _ = buckets.shape
    budget = vprobes.shape[1]
    nblk = d // dim_block
    nq = nq or b
    q32 = queries.astype(jnp.float32)
    qsq = jnp.einsum(
        "bd,bd->b", q32, q32, precision=jax.lax.Precision.HIGHEST
    )[:, None]

    def bucket_map(q, r, jb, vp):
        return (jnp.maximum(vp[q, r], 0), 0, 0)

    in_specs = [
        pl.BlockSpec(
            (ROW_BLOCK, dim_block),
            lambda q, r, jb, vp: (q // ROW_BLOCK, jb),
        ),                                                    # queries
        pl.BlockSpec(
            (ROW_BLOCK, 1), lambda q, r, jb, vp: (q // ROW_BLOCK, 0)
        ),                                                    # qsq
        # prefix norms ride as [nblk, b, 1] (tile (1, ROW_BLOCK, 1)): a
        # (ROW_BLOCK, 1) column block over [b, nblk] is refused by the
        # Pallas TPU lowering
        pl.BlockSpec(
            (1, ROW_BLOCK, 1),
            lambda q, r, jb, vp: (jb, q // ROW_BLOCK, 0),
        ),                                                    # qpsq
        pl.BlockSpec(
            (1, cap, dim_block),
            lambda q, r, jb, vp: (jnp.maximum(vp[q, r], 0), 0, jb),
        ),                                                    # bucket tile
        # all nblk rows of the bucket's block norms in one tile (the
        # kernel picks row jb): a (1, 1, cap) tile indexed by jb is a
        # size-1 sublane on a larger dim, which the lowering refuses —
        # and this way the tile is fetched once per bucket, not per jb
        pl.BlockSpec((1, nblk, cap), bucket_map),             # per-block norms
        pl.BlockSpec((1, 1, cap), bucket_map),                # total norms
        pl.BlockSpec((1, 1, cap), bucket_map),                # valid
        pl.BlockSpec((1, 1, cap), bucket_map),                # slots
    ]
    args = [
        q32,
        qsq,
        qpsq.T[:, :, None],
        buckets,
        bucket_bsq,
        bucket_sqnorm[:, None, :],
        bucket_valid.astype(jnp.float32)[:, None, :],
        bucket_slot[:, None, :],
    ]
    if sq:
        in_specs += [
            pl.BlockSpec((1, dim_block), lambda q, r, jb, vp: (0, jb)),
            pl.BlockSpec((1, dim_block), lambda q, r, jb, vp: (0, jb)),
        ]
        args += [sq_vmin[None, :], sq_scale[None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, budget, nblk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (ROW_BLOCK, OUT_PAD),
                lambda q, r, jb, vp: (q // ROW_BLOCK, 0),
            ),
        ] * 3,
        scratch_shapes=[
            pltpu.VMEM((1, cap), jnp.float32),    # cum dot
            pltpu.VMEM((1, cap), jnp.float32),    # alive mask
            pltpu.VMEM((1, cap), jnp.float32),    # x per-block prefix norms
        ],
    )
    out_v, out_i, out_s = pl.pallas_call(
        functools.partial(
            _ivf_pruned_kernel, k=k, ascending=ascending, nblk=nblk,
            check_every=check_every, sq=sq, inbucket=inbucket,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.float32),
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.int32),
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.float32),
        ],
        interpret=interpret,
    )(vprobes, *args)
    return out_v[:, :k], out_i[:, :k], out_s[:, :4]


#: VMEM the batch-major kernel may plan for (v5e's scoped default is
#: 16 MiB; Mosaic's own temporaries take the rest)
BATCH_VMEM_BUDGET = 12 << 20


def scan_arm(b: int, cap: int, d: int, itemsize: int) -> str:
    """Loop order for a scan of b (padded) queries over [cap, d] buckets:
    "batch" from ROW_BLOCK queries on, where its blocks fit VMEM (bucket
    tile double-buffered and widened once if the rows are not f32, the
    resident queries, the step's [b, cap] scores); else "query"."""
    if b < ROW_BLOCK or b % ROW_BLOCK:
        return "query"
    tile = cap * d
    need = (2 * tile * itemsize + (0 if itemsize == 4 else 2 * tile * 4)
            + 2 * b * d * 4 + 4 * b * cap * 4 + 6 * b * OUT_PAD * 4)
    return "batch" if need <= BATCH_VMEM_BUDGET else "query"


def ivf_probe_scan(
    vprobes, queries, buckets, bucket_bsq, bucket_sqnorm, bucket_valid,
    bucket_slot, sq_vmin, sq_scale, *, k: int, ascending: bool,
    interpret: bool, check_every: int = 1, inbucket: bool = True,
):
    """The scan stage of one request, traced inside its program: virtual
    probes + queries -> (scores[b, k], slots[b, k], aux). The loop order
    comes from the shapes (`scan_arm`); aux is the touched-bucket count
    (batch-major), the [b, 4] pruning stats (query-major with the blocked
    norms `bucket_bsq`) or None (query-major without). The query-major
    arrays are padded to ROW_BLOCK rows with the grid clamped to the real
    batch, so a b < 8 request neither runs nor DMAs for dead steps."""
    b, d = queries.shape
    _, cap, _ = buckets.shape
    if scan_arm(b, cap, d, buckets.dtype.itemsize) == "batch":
        return ivf_batch_topk(
            vprobes, queries, buckets, bucket_sqnorm, bucket_valid,
            bucket_slot, sq_vmin, sq_scale,
            k=k, ascending=ascending, interpret=interpret,
        )
    queries, vprobes = _pad_rows(queries, vprobes)
    if bucket_bsq is None:
        vals, slots = ivf_list_topk(
            vprobes, queries, buckets, bucket_sqnorm, bucket_valid,
            bucket_slot, k=k, ascending=ascending, interpret=interpret, nq=b,
        )
        return vals[:b], slots[:b], None
    from dingo_tpu.ops.blocked import query_prefix_sqnorms

    dim_block = d // bucket_bsq.shape[1]
    vals, slots, stats = ivf_pruned_topk(
        vprobes, queries, query_prefix_sqnorms(queries, dim_block), buckets,
        bucket_bsq, bucket_sqnorm, bucket_valid, bucket_slot, sq_vmin,
        sq_scale, k=k, dim_block=dim_block, ascending=ascending,
        check_every=check_every, interpret=interpret, nq=b,
        sq=sq_vmin is not None, inbucket=inbucket,
    )
    return vals[:b], slots[:b], stats[:b]
