"""Quick-ADC-style PQ scoring: each (query, probed list) table resident in
VMEM, one probed code bucket scored per grid step, scores only.

Quick ADC (PAPERS.md) keeps the PQ distance tables in SIMD registers and
scans codes through them without leaving the register file. The TPU
analog: the residual table of a (query, coarse rank) stays in VMEM while
the Pallas pipeline DMAs one probed code bucket [cap, m] per grid step
(scalar-prefetched bucket ids and coarse ranks, as ops/pallas_ivf.py
does). A list's spill buckets share their rank's table: the block index
does not change, so the pipeline does not fetch it again. A rank with no
bucket (-1) computes nothing and moves no DMA: its blocks are the last
live rank's.

The kernel writes the ADC scores of every probed slot and selects
nothing: the shortlist is the same single XLA top-k the XLA rank scan
takes (`ivf_pq._adc_shortlist`), so both arms rank by the same code and k'
does not bound the kernel.

The lookup is vector work laid out for the VPU, exact in f32: a step's
codes are [rows, m] int32 with the subspaces on lanes, and the table is
read as [ksub, m], one codeword's m entries a row. For each of the ksub
codewords one compare and one select over the rows' few vregs pick that
codeword's entry wherever a code names it, so every (row, subspace) ends
holding exactly its table entry; one lane sum per row gives the distance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.ops.pallas_topk import NEG_INF

#: code rows scored at once: their [ROWS, m] codes and picked entries stay
#: in vector registers while the ksub codewords go by, and a wider bucket
#: is scored ROWS at a time (a lane-aligned slice of the output row)
ROWS = 128
#: codewords a table tile holds: one f32 vreg of sublanes
TILE = 8
#: ranks an output block holds: one f32 vreg of sublanes
OUT_ROWS = 8
#: grid steps one pallas_call takes: their three scalar-prefetched int32
#: ids (bucket, fetched bucket, fetched table) sit in SMEM, 1 MB on v5e
PREFETCH_STEPS = 1 << 15
#: tables a fetched table block holds: the contraction writes a table's
#: rows one sublane apart, so a block of TABLES consecutive (query, rank)
#: tables is what a DMA can take; ranks go in order, so each block is
#: fetched once
TABLES = 8


def _adc_score_kernel(vp_ref, fb_ref, fc_ref, lut_ref, code_ref, val_ref,
                      out_ref, tbl_ref, dist_ref, held_ref, *, width, nprobe,
                      q0):
    q = pl.program_id(0)
    r = pl.program_id(1)
    at = q * width + r
    m, _, ksub = lut_ref.shape
    cap = code_ref.shape[1]
    rows = min(ROWS, cap)
    row = pl.ds(r % OUT_ROWS, 1)
    live = vp_ref[at] >= 0
    want = (q0 + q) * nprobe + fc_ref[at]   # the (query, rank) table

    @pl.when(r == 0)
    def _new_query():
        held_ref[0] = -1

    @pl.when(jnp.logical_not(live))
    def _skip():
        out_ref[0, row, :] = jnp.full((1, cap), NEG_INF, jnp.float32)

    @pl.when(live & (held_ref[0] != want))
    def _codeword_rows():
        tbl_ref[...] = lut_ref[:, want % TABLES, :].T    # [ksub, m]
        held_ref[0] = want

    @pl.when(live)
    def _score():
        ones = jnp.ones((1, m), jnp.float32)

        def score_rows(c0):
            codes = code_ref[0, pl.ds(c0, rows), :].astype(jnp.int32)

            def tile(t, picked):
                c = pl.multiple_of(t * TILE, TILE)
                entries = tbl_ref[pl.ds(c, TILE), :]     # [TILE, m]
                for i in range(TILE):
                    picked = jnp.where(codes == c + i, entries[i:i + 1, :],
                                       picked)
                return picked

            # unrolled: 9 % faster than the rolled loop on a v5e, the
            # same bits (PERF.md section 6)
            picked = jax.lax.fori_loop(
                0, ksub // TILE, tile, jnp.zeros((rows, m), jnp.float32),
                unroll=True)
            # the lane sum of each row as a row vector [1, rows]: a
            # ones-row contraction at HIGHEST (f32 operands, f32
            # accumulation)
            dist_ref[:, pl.ds(c0, rows)] = jax.lax.dot_general(
                ones, picked, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )

        if cap == rows:
            score_rows(0)     # static offset: rows need not fill a lane tile
        else:
            def body(i, carry):
                score_rows(pl.multiple_of(i * rows, rows))
                return carry

            jax.lax.fori_loop(0, cap // rows, body, 0)
        out_ref[0, row, :] = jnp.where(val_ref[0] > 0.5, -dist_ref[:],
                                       NEG_INF)


def _grid_spec(q0, nq, width, nprobe, m, ksub, cap):
    """The (query, rank) grid of the batch's queries q0 .. q0 + nq: the
    scalar-prefetched ids of all its steps have to fit SMEM."""

    def step(ref, q, r):
        return ref[q * width + r]

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nq, width),
        in_specs=[
            pl.BlockSpec(
                (m, TABLES, ksub),
                lambda q, r, vp, fb, fc: (
                    0, ((q0 + q) * nprobe + step(fc, q, r)) // TABLES, 0),
            ),                                            # resident tables
            pl.BlockSpec(
                (1, cap, m),
                lambda q, r, vp, fb, fc: (step(fb, q, r), 0, 0),
            ),                                            # code bucket
            pl.BlockSpec(
                (1, 1, cap),
                lambda q, r, vp, fb, fc: (step(fb, q, r), 0, 0),
            ),                                            # valid row
        ],
        out_specs=pl.BlockSpec(
            (1, OUT_ROWS, cap),
            lambda q, r, vp, fb, fc: (q, r // OUT_ROWS, 0)),
        scratch_shapes=[
            pltpu.VMEM((ksub, m), jnp.float32),       # codeword-major table
            pltpu.VMEM((1, cap), jnp.float32),        # ADC sums
            pltpu.SMEM((1,), jnp.int32),              # table in tbl_ref
        ],
    )


@sentinel_jit("ops.pallas.pq_adc", static_argnames=("interpret",))
def ivf_pq_adc_scores(
    vprobes: jax.Array,       # [b, budget] int32 virtual bucket ids (-1 pad)
    coarse_pos: jax.Array,    # [b, budget] int32 coarse rank of each probe
    lut_all: jax.Array,       # [m, b, nprobe, ksub] f32 residual ADC tables
    code_buckets: jax.Array,  # [B, cap, m] uint8
    bucket_valid: jax.Array,  # [B, cap] bool/float
    interpret: bool = False,
) -> jax.Array:
    """ADC scores of every probed slot -> [b, budget, cap] f32: minus the
    ADC distance, -inf where the slot is invalid or the rank is -1.

    The tables come subspace-major, as the LUT's batched contraction lays
    them out: the kernel reads them as they lie, TABLES (query, rank)
    tables a block, and turns a table codeword-major in VMEM once (a
    list's spill buckets reuse it)."""
    b, budget = vprobes.shape
    m, _, nprobe, ksub = lut_all.shape
    cap = code_buckets.shape[1]
    if ksub % TILE or (cap > ROWS and cap % ROWS):
        raise ValueError(f"ADC kernel: ksub {ksub} / cap {cap} unsupported")
    # whole output tiles of ranks; the added ranks are -1
    width = -(-budget // OUT_ROWS) * OUT_ROWS
    vprobes = jnp.pad(vprobes, ((0, 0), (0, width - budget)),
                      constant_values=-1)
    coarse_pos = jnp.pad(coarse_pos, ((0, 0), (0, width - budget)))
    # the blocks a step fetches: its own where the rank is live, else the
    # last live rank's at or before it, which the pipeline already holds
    ranks = jnp.arange(width, dtype=jnp.int32)[None, :]
    last = jax.lax.cummax(jnp.where(vprobes >= 0, ranks, -1), axis=1)
    last = jnp.maximum(last, 0)
    fetch_bucket = jnp.maximum(
        jnp.take_along_axis(vprobes, last, axis=1), 0)
    fetch_coarse = jnp.take_along_axis(coarse_pos, last, axis=1)
    lut = lut_all.reshape(m, b * nprobe, ksub)
    valid = bucket_valid.astype(jnp.float32)[:, None, :]
    per_call = max(1, PREFETCH_STEPS // width)
    parts = []
    for q0 in range(0, b, per_call):
        nq = min(per_call, b - q0)
        part = slice(q0, q0 + nq)
        parts.append(pl.pallas_call(
            functools.partial(_adc_score_kernel, width=width, nprobe=nprobe,
                              q0=q0),
            grid_spec=_grid_spec(q0, nq, width, nprobe, m, ksub, cap),
            out_shape=jax.ShapeDtypeStruct((nq, width, cap), jnp.float32),
            interpret=interpret,
        )(vprobes[part].reshape(-1), fetch_bucket[part].reshape(-1),
          fetch_coarse[part].reshape(-1), lut, code_buckets, valid))
    scores = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return scores[:, :budget]
