"""Quick-ADC-style fused PQ scan: LUTs resident in VMEM, codes streamed
per probed bucket.

Quick ADC (PAPERS.md) keeps the PQ distance tables in SIMD registers and
scans codes through them without ever leaving the register file. The TPU
analog: the per-(query, probed-list) residual LUT [m, ksub] stays resident
in VMEM for the whole bucket scan while the Pallas pipeline DMAs exactly
one probed code bucket [cap, m] per grid step (scalar-prefetched probe
ids, same scheme as ops/pallas_ivf.py), and the ADC sum + running top-k
merge happen in VMEM. The XLA path (`ivf_pq._ivfpq_scan_kernel`) instead
gathers a [b, cap, m] code bucket per rank into HBM and reads it back for
a take_along_axis — 3x the necessary HBM traffic, plus the gather itself
lowers badly on TPU.

The in-kernel table lookup is a one-hot contraction (the MXU-native
formulation ops/pq.py:adc_scan uses at the XLA layer), chunked over
subspace groups so the one-hot tile stays a few MB of VMEM:

    dist[c] = sum_g  onehot(codes[c, g*MG:(g+1)*MG]) . lut[g*MG:(g+1)*MG]

Output feeds the existing device-resident exact rerank (ops/rerank.py) —
the ADC scan is the prune, the rerank absorbs the quantization noise.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.ops.pallas_ivf import OUT_PAD, ROW_BLOCK
from dingo_tpu.ops.pallas_topk import NEG_INF, _select_topk

#: subspaces per one-hot group and candidates per one-hot tile: together
#: they bound the [CHUNK, MAX_GROUP * ksub] f32 tile at 2 MB (ksub=256)
#: whatever the bucket width. One tile over a whole cap=1024 bucket needed
#: 41.8 MB of the 16 MB scoped VMEM on v5e.
MAX_GROUP = 8
CHUNK = 256


def _adc_kernel(vp_ref, cp_ref, lut_ref, code_ref, val_ref, slot_ref,
                outv_ref, outi_ref, dist_ref, *, k, m, ksub):
    qi = pl.program_id(0)
    r = pl.program_id(1)
    row = pl.ds(jax.lax.rem(qi, ROW_BLOCK), 1)

    @pl.when(r == 0)
    def _init():
        outv_ref[row, :] = jnp.full(
            (1, outv_ref.shape[1]), NEG_INF, jnp.float32
        )
        outi_ref[row, :] = jnp.full((1, outi_ref.shape[1]), -1, jnp.int32)

    @pl.when(vp_ref[qi, r] >= 0)
    def _scan_bucket():
        lut = lut_ref[0, 0]                              # [m, ksub]
        cap = code_ref.shape[1]
        ch = min(CHUNK, cap)
        kiota = jax.lax.broadcasted_iota(jnp.int32, (1, ksub), 1)

        def scan_chunk(c0):
            codes = code_ref[0, pl.ds(c0, ch), :].astype(jnp.int32)  # [ch, m]
            dist = jnp.zeros((1, ch), jnp.float32)
            # static unrolled group loop: one-hot contraction per MG
            # subspaces
            for g in range(0, m, MAX_GROUP):
                w = min(MAX_GROUP, m - g)
                cg = codes[:, g:g + w]                   # [ch, w]
                oh = (cg[:, :, None] == kiota[None, :, :]).astype(
                    jnp.float32
                )
                ohf = oh.reshape(ch, w * ksub)
                lutg = lut[g:g + w, :].reshape(1, w * ksub)
                dist += jax.lax.dot_general(
                    lutg, ohf, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                )                                        # [1, ch]
            dist_ref[:, pl.ds(c0, ch)] = dist

        if cap == ch:
            scan_chunk(0)        # static offset: ch need not be lane-aligned
        else:
            def body(c, carry):
                scan_chunk(pl.multiple_of(c * ch, ch))
                return carry

            jax.lax.fori_loop(0, cap // ch, body, 0)
        scores = jnp.where(val_ref[0] > 0.5, -dist_ref[:], NEG_INF)
        slot = slot_ref[0].astype(jnp.int32)
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
        outi_ref[row, :] = jnp.where(jnp.isneginf(fv), -1, outi_ref[row, :])


@sentinel_jit("ops.pallas.pq_adc_topk",
              static_argnames=("k", "interpret", "nq"))
def ivf_pq_adc_topk(
    vprobes: jax.Array,      # [b, budget] int32 virtual bucket ids (-1 pad)
    coarse_pos: jax.Array,   # [b, budget] int32 coarse rank of each probe
    lut_all: jax.Array,      # [b, nprobe, m, ksub] f32 residual ADC tables
    code_buckets: jax.Array,  # [B, cap, m] uint8
    bucket_valid: jax.Array,  # [B, cap] bool/float
    bucket_slot: jax.Array,   # [B, cap] int32
    k: int,
    interpret: bool = False,
    nq: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Fused ADC probed-bucket scan -> (scores[b, k], slots[b, k]).

    Scores are negated ADC distances ('larger is better'); a hot list's
    spill buckets share the coarse rank's LUT via coarse_pos, so the LUT
    block index_map re-reads the SAME VMEM-resident table instead of
    recomputing it per bucket (the Quick ADC property)."""
    b = vprobes.shape[0]
    budget = vprobes.shape[1]
    nb, cap, m = code_buckets.shape
    ksub = lut_all.shape[3]
    nq = nq or b

    def bucket_map(q, r, vp, cp):
        return (jnp.maximum(vp[q, r], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq, budget),
        in_specs=[
            pl.BlockSpec(
                (1, 1, m, ksub),
                lambda q, r, vp, cp: (q, cp[q, r], 0, 0),
            ),                                            # resident LUT
            pl.BlockSpec((1, cap, m), bucket_map),        # code bucket
            pl.BlockSpec((1, 1, cap), bucket_map),        # valid
            pl.BlockSpec((1, 1, cap), bucket_map),        # slots
        ],
        out_specs=[
            pl.BlockSpec(
                (ROW_BLOCK, OUT_PAD),
                lambda q, r, vp, cp: (q // ROW_BLOCK, 0),
            ),
        ] * 2,
        scratch_shapes=[pltpu.VMEM((1, cap), jnp.float32)],   # ADC sums
    )
    out_v, out_i = pl.pallas_call(
        functools.partial(_adc_kernel, k=k, m=m, ksub=ksub),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.float32),
            jax.ShapeDtypeStruct((b, OUT_PAD), jnp.int32),
        ],
        interpret=interpret,
    )(
        vprobes,
        coarse_pos,
        lut_all,
        code_buckets,
        bucket_valid.astype(jnp.float32)[:, None, :],
        bucket_slot[:, None, :],
    )
    return out_v[:, :k], out_i[:, :k]


def ivf_pq_adc_search(
    vprobes, coarse_pos, lut_all, code_buckets, bucket_valid, bucket_slot,
    k: int,
):
    """Backend-aware wrapper: ROW_BLOCK-pads the per-query arrays, clamps
    the grid to the real batch; compiled on the TPU, interpreted on the
    CPU (config.pallas_interpret)."""
    from dingo_tpu.common.config import pallas_interpret

    b = vprobes.shape[0]
    pad = (-b) % ROW_BLOCK
    if pad:
        vprobes = jnp.concatenate(
            [vprobes, jnp.full((pad, vprobes.shape[1]), -1, vprobes.dtype)]
        )
        coarse_pos = jnp.concatenate(
            [coarse_pos,
             jnp.zeros((pad, coarse_pos.shape[1]), coarse_pos.dtype)]
        )
        lut_all = jnp.concatenate(
            [lut_all, jnp.zeros((pad,) + lut_all.shape[1:], lut_all.dtype)]
        )
    vals, slots = ivf_pq_adc_topk(
        vprobes, coarse_pos, lut_all, code_buckets, bucket_valid,
        bucket_slot, k=k, interpret=pallas_interpret(), nq=b,
    )
    return vals[:b], slots[:b]
