"""Fused distance + running top-k Pallas kernel.

SURVEY.md §7 kernel layer: "fused distance+top-k Pallas kernel with running
k-selection to avoid materializing [b, n]". The XLA path (ops/distance.py +
lax.top_k) materializes the full [b, n] score matrix in HBM; this kernel
streams the database through VMEM in blocks, keeps a [b, k] running best in
VMEM scratch, and never writes the score matrix out — at 10M x 768 that is
~2.5 GB of HBM traffic saved per query batch (k=10, b=64).

Selection strategy: per block, k rounds of (max, argmax, mask) over the
[b, C] block scores — k/d ≈ 1-2% overhead relative to the distance matmul —
then a merge of the 2k running+block candidates by another k rounds.
Runs under interpret=True on CPU for tests; compiled on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from dingo_tpu.obs.sentinel import sentinel_jit

NEG_INF = float("-inf")


def _select_topk(scores, idx, k):
    """k rounds of max/argmax/mask over [b, C] -> ([b, k], [b, k]).

    The winner's id is extracted with a masked max reduction rather than
    take_along_axis: Mosaic's gather lowering only accepts indices shaped
    operand+(1,), so a [b,1] gather on [b,C] fails to lower (observed
    on-chip round 3) — and a where+max over the one matching lane is
    vector-unit work anyway, no gather needed.
    """
    vals, ids = [], []
    b, c = scores.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    for _ in range(k):
        m = jnp.max(scores, axis=1)                      # [b]
        am = jnp.argmax(scores, axis=1)                  # [b]
        hit = cols == am[:, None]
        ids.append(jnp.max(
            jnp.where(hit, idx, jnp.int32(np.iinfo(np.int32).min)), axis=1
        ))
        vals.append(m)
        # mask the winner out
        scores = jnp.where(hit, NEG_INF, scores)
    return jnp.stack(vals, axis=1), jnp.stack(ids, axis=1)


def _fused_kernel(q_ref, qsq_ref, x_ref, xsq_ref, valid_ref,
                  out_v_ref, out_i_ref, best_v, best_i, *, k, block, ascending):
    j = pl.program_id(0)
    nblocks = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        best_v[:] = jnp.full_like(best_v, NEG_INF)
        best_i[:] = jnp.full_like(best_i, -1)

    q = q_ref[:]                                          # [b, d]
    x = x_ref[:].astype(jnp.float32)   # bf16 stores promote in VMEM
    # HIGHEST precision: the default bf16-pass matmul measurably costs
    # recall (distance.py pins the same; flat recall@10 0.9875 -> 1.0).
    dots = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                                     # [b, C]
    if ascending:  # L2: score = -(||q||^2 - 2qx + ||x||^2)
        scores = -(qsq_ref[:] - 2.0 * dots + xsq_ref[:])  # [b,1] + [1,C]
    else:          # IP
        scores = dots
    valid = valid_ref[:]                                  # [1, C] float (1/0)
    scores = jnp.where(valid > 0.5, scores, NEG_INF)

    b = scores.shape[0]
    gidx = (
        jax.lax.broadcasted_iota(jnp.int32, (b, block), 1) + j * block
    )
    blk_v, blk_i = _select_topk(scores, gidx, k)

    cat_v = jnp.concatenate([best_v[:], blk_v], axis=1)   # [b, 2k]
    cat_i = jnp.concatenate([best_i[:], blk_i], axis=1)
    new_v, new_i = _select_topk(cat_v, cat_i, k)
    best_v[:] = new_v
    best_i[:] = new_i

    @pl.when(j == nblocks - 1)
    def _finish():
        fv = best_v[:]
        out_v_ref[:] = fv
        # -inf picks are argmax-of-all-masked artifacts: they carry real
        # (and duplicated) slot ids. Map them to -1 like the XLA path
        # (topk.py maps -inf picks to -1) so filter-excluded ids never leak.
        out_i_ref[:] = jnp.where(jnp.isneginf(fv), -1, best_i[:])


@sentinel_jit("ops.pallas.fused_topk",
              static_argnames=("k", "block", "ascending", "interpret"))
def fused_topk(
    q: jax.Array,
    x: jax.Array,
    x_sqnorm: jax.Array,
    valid: jax.Array,
    k: int,
    block: int = 2048,
    ascending: bool = True,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Streaming fused search: q[b,d] vs x[n,d] -> (scores[b,k], slots[b,k]).

    Returns 'larger is better' scores (negated L2 when ascending) and global
    slot indices (-1 for masked). n must be a multiple of `block` (pad with
    valid=0 rows).
    """
    b, d = q.shape
    n = x.shape[0]
    assert n % block == 0, f"n={n} not a multiple of block={block}"
    qsq = jnp.einsum("bd,bd->b", q.astype(jnp.float32), q.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)[:, None]   # [b, 1]
    grid = (n // block,)
    out_v, out_i = pl.pallas_call(
        functools.partial(_fused_kernel, k=k, block=block,
                          ascending=ascending),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, d), lambda j: (0, 0)),         # q (all blocks)
            pl.BlockSpec((b, 1), lambda j: (0, 0)),         # qsq [b,1]
            pl.BlockSpec((block, d), lambda j: (j, 0)),     # x block
            pl.BlockSpec((1, block), lambda j: (0, j)),     # xsq [1, n]
            pl.BlockSpec((1, block), lambda j: (0, j)),     # valid [1, n]
        ],
        out_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((b, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),
            pltpu.VMEM((b, k), jnp.int32),
        ],
        interpret=interpret,
    )(q.astype(jnp.float32), qsq, x, x_sqnorm[None, :],
      valid.astype(jnp.float32)[None, :])
    return out_v, out_i


def fused_search(
    q: np.ndarray,
    x: jax.Array,
    x_sqnorm: jax.Array,
    valid: jax.Array,
    k: int,
    block: int = 2048,
    ascending: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Host-friendly wrapper: pads n to the block multiple; compiled on
    the TPU, interpreted on the CPU (config.pallas_interpret)."""
    from dingo_tpu.common.config import pallas_interpret

    n = x.shape[0]
    pad = (-n) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])
        x_sqnorm = jnp.concatenate([x_sqnorm, jnp.zeros((pad,), x_sqnorm.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)])
    return fused_topk(jnp.asarray(q), x, x_sqnorm, valid, k=k, block=block,
                      ascending=ascending, interpret=pallas_interpret())


#: stats output lane width (TPU lane tile; only the first 4 lanes carry)
STATS_PAD = 128


def _pruned_fused_kernel(q_ref, qsq_ref, qpsq_ref, x_ref, bsq_ref, xsq_ref,
                         valid_ref, *rest, k, block, nblk, check_every,
                         ascending, sq, inbucket):
    """Dimension-blocked early-pruning whole-index scan (the FLAT arm of
    the PDX scheme — see ops/pallas_ivf._ivf_pruned_kernel for the bound
    math). Grid (row_block j, dim_block jb) with jb INNERMOST: partial
    dots accumulate in VMEM scratch per row block; candidates whose bound
    cannot beat the running k-th best stop contributing, and a row block
    whose candidates are ALL dead (for every query) skips the remaining
    dimension blocks' matmuls.

    Stats output lanes (per query, accumulated): 0 = candidate-block
    pairs scanned, 1 = pairs total, 2 = candidates scanned to the last
    block, 3 = candidates considered."""
    if sq:
        (vmin_ref, scale_ref, out_v_ref, out_i_ref, outs_ref,
         best_v, best_i, cum, alive, xpsq) = rest
    else:
        (out_v_ref, out_i_ref, outs_ref,
         best_v, best_i, cum, alive, xpsq) = rest
    j = pl.program_id(0)
    jb = pl.program_id(1)
    b = cum.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (b, STATS_PAD), 1)

    @pl.when((j == 0) & (jb == 0))
    def _init():
        best_v[:] = jnp.full_like(best_v, NEG_INF)
        best_i[:] = jnp.full_like(best_i, -1)
        outs_ref[:] = jnp.zeros_like(outs_ref)

    @pl.when(jb == 0)
    def _init_block():
        cum[:] = jnp.zeros_like(cum)
        xpsq[:] = jnp.zeros_like(xpsq)
        alive[:] = jnp.broadcast_to(valid_ref[:], (b, block))
        nvalid = jnp.sum(valid_ref[:])
        outs_ref[:] += jnp.where(
            lanes == 1, nvalid * nblk, jnp.where(lanes == 3, nvalid, 0.0)
        )

    per_q = jnp.sum(alive[:], axis=1, keepdims=True)       # [b, 1]
    outs_ref[:] += jnp.where(lanes == 0, per_q, 0.0)

    @pl.when(jb == nblk - 1)
    def _count_full():
        outs_ref[:] += jnp.where(lanes == 2, per_q, 0.0)

    @pl.when(jnp.sum(alive[:]) > 0.5)
    def _compute():
        q = q_ref[:]                                       # [b, dblk]
        x = x_ref[0]                                       # [block, dblk]
        if sq:
            # decode f32 -> bf16 multiplies, f32 accumulate (the sq8
            # tier's compute contract, ops/sq.py); the codes widen via
            # int32 — Mosaic has no direct uint8 -> f32 cast
            x = (
                x.astype(jnp.int32).astype(jnp.float32) * scale_ref[:]
                + vmin_ref[:]
            ).astype(jnp.bfloat16)
            q = q.astype(jnp.bfloat16)
            bf16_mul = True
        else:
            # bf16 stores keep bf16 multiplies with f32 accumulation —
            # the same pairing distance._dot applies on the XLA arm, so
            # the pruned scan ranks identically to the flat kernel
            bf16_mul = x.dtype == jnp.bfloat16
            if bf16_mul:
                q = q.astype(jnp.bfloat16)
            else:
                x = x.astype(jnp.float32)
        dots = jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(None if bf16_mul else jax.lax.Precision.HIGHEST),
        )                                                  # [b, block]
        cum[:] += dots
        xpsq[:] += bsq_ref[0]                              # [1, block]
        bound = best_v[:, k - 1:k]                         # [b, 1]
        qpsq_j = qpsq_ref[0]                               # [b, 1] prefix
        qtail = jnp.maximum(qsq_ref[:] - qpsq_j, 0.0)      # [b, 1]
        xtail = jnp.maximum(xsq_ref[:] - xpsq[:], 0.0)      # [1, block]
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
                # within-row-block threshold refresh: the k-th largest
                # suffix-norm LOWER bound among alive candidates prunes
                # blocks before any of them reaches a shortlist merge
                # (see ops/pallas_ivf._ivf_pruned_kernel for the math
                # and the self-prune impossibility argument)
                if ascending:
                    tail = jnp.sqrt(qtail) + jnp.sqrt(xtail)
                    lb = -(partial + tail * tail)
                else:
                    lb = cum[:] - jnp.sqrt(qtail * xtail)
                lb = lb - 1e-5 * jnp.abs(lb) - 1e-6   # f32 safety shave
                lb = jnp.where(alive[:] > 0.5, lb, NEG_INF)
                gidx = jax.lax.broadcasted_iota(
                    jnp.int32, lb.shape, 1
                )
                lb_k, _ = _select_topk(lb, gidx, k)
                bnd = jnp.maximum(bnd, lb_k[:, k - 1:k])
            alive[:] = jnp.where(ub < bnd, 0.0, alive[:])

        @pl.when(jb == nblk - 1)
        def _merge():
            scores = jnp.where(alive[:] > 0.5, final, NEG_INF)
            gidx = (
                jax.lax.broadcasted_iota(jnp.int32, (b, block), 1)
                + j * block
            )
            blk_v, blk_i = _select_topk(scores, gidx, k)
            cat_v = jnp.concatenate([best_v[:], blk_v], axis=1)
            cat_i = jnp.concatenate([best_i[:], blk_i], axis=1)
            new_v, new_i = _select_topk(cat_v, cat_i, k)
            best_v[:] = new_v
            best_i[:] = new_i

    @pl.when((j == pl.num_programs(0) - 1) & (jb == nblk - 1))
    def _finish():
        fv = best_v[:]
        out_v_ref[:] = fv
        out_i_ref[:] = jnp.where(jnp.isneginf(fv), -1, best_i[:])


@sentinel_jit("ops.pallas.pruned_fused_topk",
              static_argnames=("k", "block", "dim_block", "check_every",
                               "ascending", "interpret", "sq", "inbucket"))
def pruned_fused_topk(
    q: jax.Array,              # [b, d] f32
    x_blk: jax.Array,          # [nblk, n, dblk] rows (f32/bf16) or codes
    bsq_blk: jax.Array,        # [nblk, n] f32 per-block (decoded) norms
    x_sqnorm: jax.Array,       # [n] f32 total (decoded) norms
    valid: jax.Array,          # [n] bool/float
    sq_vmin,                   # [d] f32 codec params (None for float rows)
    sq_scale,
    k: int,
    block: int = 2048,
    dim_block: int = 128,
    check_every: int = 1,
    ascending: bool = True,
    interpret: bool = False,
    sq: bool = False,
    inbucket: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Early-pruning streaming search over the dimension-blocked store
    mirror (slot_store.vecs_blk/bsq_blk) -> (scores[b,k], slots[b,k],
    stats[b,4]). Same contract as fused_topk plus the pruning stats."""
    b, d = q.shape
    nblk, n, dblk = x_blk.shape
    assert n % block == 0, f"n={n} not a multiple of block={block}"
    assert dblk * nblk == d, f"blocked dim {nblk}x{dblk} != {d}"
    q32 = q.astype(jnp.float32)
    qsq = jnp.einsum("bd,bd->b", q32, q32,
                     precision=jax.lax.Precision.HIGHEST)[:, None]
    from dingo_tpu.ops.blocked import query_prefix_sqnorms

    # prefix norms ride as [nblk, b, 1] so the per-dim-block tile is
    # (1, b, 1): its last two dims equal the array's. A (b, 1) column
    # block over [b, nblk] is refused by the Pallas TPU lowering (lane
    # dim neither 128-divisible nor the whole array).
    qpsq = query_prefix_sqnorms(q32, dblk).T[:, :, None]   # [nblk, b, 1]
    grid = (n // block, nblk)
    in_specs = [
        pl.BlockSpec((b, dblk), lambda j, jb: (0, jb)),     # q (dim block)
        pl.BlockSpec((b, 1), lambda j, jb: (0, 0)),         # qsq
        pl.BlockSpec((1, b, 1), lambda j, jb: (jb, 0, 0)),  # qpsq prefix
        pl.BlockSpec((1, block, dblk), lambda j, jb: (jb, j, 0)),   # x tile
        pl.BlockSpec((1, 1, block), lambda j, jb: (jb, 0, j)),      # bsq
        pl.BlockSpec((1, block), lambda j, jb: (0, j)),     # xsq total
        pl.BlockSpec((1, block), lambda j, jb: (0, j)),     # valid
    ]
    args = [
        q32, qsq, qpsq, x_blk, bsq_blk[:, None, :],
        x_sqnorm[None, :], valid.astype(jnp.float32)[None, :],
    ]
    if sq:
        in_specs += [
            pl.BlockSpec((1, dblk), lambda j, jb: (0, jb)),
            pl.BlockSpec((1, dblk), lambda j, jb: (0, jb)),
        ]
        args += [sq_vmin[None, :], sq_scale[None, :]]
    out_v, out_i, out_s = pl.pallas_call(
        functools.partial(
            _pruned_fused_kernel, k=k, block=block, nblk=nblk,
            check_every=check_every, ascending=ascending, sq=sq,
            inbucket=inbucket,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, k), lambda j, jb: (0, 0)),
            pl.BlockSpec((b, k), lambda j, jb: (0, 0)),
            pl.BlockSpec((b, STATS_PAD), lambda j, jb: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((b, STATS_PAD), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, k), jnp.float32),       # best_v
            pltpu.VMEM((b, k), jnp.int32),         # best_i
            pltpu.VMEM((b, block), jnp.float32),   # cum dot
            pltpu.VMEM((b, block), jnp.float32),   # alive mask
            pltpu.VMEM((1, block), jnp.float32),   # x per-block prefixes
        ],
        interpret=interpret,
    )(*args)
    return out_v, out_i, out_s[:, :4]


def pruned_fused_search(
    q,
    x_blk: jax.Array,
    bsq_blk: jax.Array,
    x_sqnorm: jax.Array,
    valid: jax.Array,
    k: int,
    block: int = 2048,
    ascending: bool = True,
    sq_vmin=None,
    sq_scale=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Host-friendly wrapper over the blocked store mirror. The mirror's
    capacity is pow2 >= 4096, so `block` is clamped down to divide it
    exactly (no padding copy of a [nblk, n, dblk] array on the hot path)."""
    from dingo_tpu.common.config import FLAGS, pallas_interpret

    n = x_blk.shape[1]
    block = min(block, n)
    interpret = pallas_interpret()
    check = max(1, int(FLAGS.get("ivf_prune_check_interval")))
    return pruned_fused_topk(
        jnp.asarray(q), x_blk, bsq_blk, x_sqnorm, valid,
        sq_vmin, sq_scale,
        k=k, block=block, dim_block=int(x_blk.shape[2]), check_every=check,
        ascending=ascending, interpret=interpret, sq=sq_vmin is not None,
        inbucket=bool(FLAGS.get("ivf_prune_inbucket_bound")),
    )
