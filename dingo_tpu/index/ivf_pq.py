"""TpuIvfPq: IVF + product quantization with residual encoding and the
reference's hybrid flat->pq lifecycle.

Reference: VectorIndexIvfPq (src/vector/vector_index_ivf_pq.{h,cc}) is a
**hybrid**: it serves exact search from an internal flat index until trained,
then switches to faiss::IndexIVFPQ (vector_index_ivf_pq.h:113-115,
VectorIndexSubType() vector_index.h:238). Train size derives from
ClusteringParameters.max_points_per_centroid * nlist and
ProductQuantizer(d, m, nbits) (vector_index_ivf_pq.cc:337-341).

TPU-first design:
  codes    — residual PQ (faiss IVFPQ by_residual convention): code(x) =
             pq_encode(x - centroid[assign(x)]). Codes live in a device
             [capacity, m] uint8 array updated incrementally on upsert;
             a bucketed view [nlist, cap_list, m] groups codes by coarse
             list (same scheme as ivf_flat.py).
  search   — one program a request: coarse probes, the residual LUT of
             every (query, probed list), the ADC scores of every probed
             bucket, then ONE top-k' over all of them and the exact rerank.
             The scores come from the Pallas kernel (ops/pallas_pq.py, each
             table resident in VMEM) on the TPU, else from the XLA rank
             scan's compare-select over the ksub codewords.
  fallback — untrained: exact flat-kernel scan over the SlotStore (the
             hybrid contract; NOT an error, unlike IVF_FLAT).
"""

from __future__ import annotations

import json
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dingo_tpu.common.config import FLAGS
from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    InvalidParameter,
    NotTrained,
    SearchResult,
    VectorIndex,
    strip_invalid,
)
from dingo_tpu.index.flat import (
    _SlotStoreIndex,
    _flat_search_kernel,
    _pad_batch,
    _resolve_train_cap,
    integrity_mutation,
)
from dingo_tpu.common.metrics import METRICS
from dingo_tpu.index.ivf_flat import IvfViewMaintenance, coarse_probes
from dingo_tpu.index.ivf_layout import MutableIvfView, expand_probes_ranked
from dingo_tpu.index.slot_store import HostSlotStore, SlotStore, _next_pow2
from dingo_tpu.ops.distance import (
    Metric,
    nearest_index,
    normalize,
    np_normalize,
    pairwise_l2sqr,
    squared_norms,
)
from dingo_tpu.ops.kmeans import (
    MAX_POINTS_PER_CENTROID,
    kmeans_assign,
    train_kmeans,
)
from dingo_tpu.ops.pq import pq_train, split_subvectors
from dingo_tpu.ops.topk import merge_topk
from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.trace import TRACER


HOST_SCAN_CHUNK = 65536
#: rows encoded per device round during train-time (re)encode
ENCODE_CHUNK = 131072


def _chunked_host_scan(vecs_h, sqnorm_h, mask_h, qpad, k, metric):
    """Exact scan streaming host chunks through the flat kernel with a
    running top-k merge (the untrained fallback for host-resident stores;
    slot ids stay global)."""
    from dingo_tpu.ops.distance import metric_ascending, scores_to_distances

    b = qpad.shape[0]
    neg_inf = jnp.float32(-jnp.inf)
    best_v = jnp.full((b, k), neg_inf)
    best_s = jnp.full((b, k), -1, jnp.int32)
    n = vecs_h.shape[0]
    asc = metric_ascending(metric)
    for i in range(0, n, HOST_SCAN_CHUNK):
        hi = min(n, i + HOST_SCAN_CHUNK)
        if not mask_h[i:hi].any():
            continue
        pad = HOST_SCAN_CHUNK - (hi - i)
        chunk = np.asarray(vecs_h[i:hi], np.float32)
        sq = np.asarray(sqnorm_h[i:hi], np.float32)
        m = mask_h[i:hi]
        if pad:
            chunk = np.concatenate(
                [chunk, np.zeros((pad, chunk.shape[1]), np.float32)]
            )
            sq = np.concatenate([sq, np.zeros(pad, np.float32)])
            m = np.concatenate([m, np.zeros(pad, bool)])
        d, sl = _flat_search_kernel(
            jnp.asarray(chunk), jnp.asarray(sq), jnp.asarray(m), qpad,
            k=k, metric=metric, nbits=0,
        )
        # kernel returns wire distances; merge in score space
        vals = -d if asc else d
        gsl = jnp.where(sl >= 0, sl + i, -1)
        best_v, best_s = merge_topk(best_v, best_s, vals, gsl, k)
    best_s = jnp.where(jnp.isneginf(best_v), -1, best_s)
    return scores_to_distances(best_v, metric), best_s


def _exact_rerank_host(store, queries, cand_slots, k, metric):
    """Exact rerank of ADC candidates from a host-resident store:
    one host gather + one device einsum (prune+rerank, diskann/core.py
    recipe). Returns (wire distances [b, k], slots [b, k])."""
    from dingo_tpu.ops.distance import scores_to_distances

    b, kprime = cand_slots.shape
    safe = np.where(cand_slots >= 0, cand_slots, 0)
    flat_idx = safe.reshape(-1)
    rows = np.asarray(store.vecs[flat_idx], np.float32).reshape(
        b, kprime, -1
    )
    dc = jnp.asarray(rows)
    qd = jnp.asarray(queries, jnp.float32)
    dots = jnp.einsum(
        "bd,bkd->bk", qd, dc,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    if metric is Metric.L2:
        # candidate norms come from the store's cache, gathered host-side
        # in the same fancy-index as the rows
        c_sq = jnp.asarray(store.sqnorm[flat_idx].reshape(b, kprime))
        scores = -(squared_norms(qd)[:, None] - 2.0 * dots + c_sq)
    else:
        scores = dots
    scores = jnp.where(jnp.asarray(cand_slots) >= 0, scores,
                       jnp.float32(-jnp.inf))
    vals, pos = jax.lax.top_k(scores, min(k, kprime))
    slots_out = jnp.take_along_axis(jnp.asarray(cand_slots), pos, axis=1)
    slots_out = jnp.where(jnp.isneginf(vals), -1, slots_out)
    if min(k, kprime) < k:
        pad = k - min(k, kprime)
        vals = jnp.pad(vals, ((0, 0), (0, pad)),
                       constant_values=float("-inf"))
        slots_out = jnp.pad(slots_out, ((0, 0), (0, pad)),
                            constant_values=-1)
    return scores_to_distances(vals, metric), slots_out


@sentinel_jit("index.ivfpq.encode_residual")
def _encode_residual(vectors, assign, centroids, codebooks):
    """codes[n, m] uint8 for residuals (vectors - their centroid)."""
    resid = vectors - jnp.take(centroids, assign, axis=0)
    m, ksub, dsub = codebooks.shape
    subs = split_subvectors(resid, m)                  # [m, n, dsub]

    def enc_one(sub_cb):
        return nearest_index(pairwise_l2sqr(*sub_cb))

    # one subspace at a time: each distance table is [n, ksub] (134 MB at
    # 131,072 rows), materialised whole before its argmin (nearest_index)
    return jax.lax.map(enc_one, (subs, codebooks)).T.astype(jnp.uint8)


def _codebook_sqnorms(codebooks):
    """||codeword||^2 per (subspace, codeword): [m, ksub] f32."""
    return jnp.einsum(
        "mkd,mkd->mk", codebooks, codebooks,
        precision=jax.lax.Precision.HIGHEST,
    )


def _residual_lut_tables(resid, codebooks, cb_sq, subspace_major=False):
    """Residual targets [n, d] -> ADC tables [n, m, ksub], or [m, n, ksub]
    where `subspace_major` (the order the batched contraction writes, which
    the Pallas kernel reads as it lies): lut[i, j, c] = ||resid_i_subj -
    codeword_jc||^2. THE one copy of the distance-table formula — both the
    XLA scan kernel and the Pallas scoring kernel build tables here, so
    they cannot drift apart; the order only lays the same values out."""
    m = codebooks.shape[0]
    subs = split_subvectors(resid, m)                  # [m, n, dsub]
    dots = jnp.einsum(
        "mbd,mkd->mbk", subs, codebooks,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    q_sq = jnp.einsum(
        "mbd,mbd->mb", subs, subs, precision=jax.lax.Precision.HIGHEST
    )
    lut = q_sq[:, :, None] - 2.0 * dots + cb_sq[:, None, :]  # [m, n, ksub]
    return lut if subspace_major else jnp.transpose(lut, (1, 0, 2))


def _ivfpq_adc_lut(queries, centroids, probes_coarse, codebooks):
    """Residual ADC tables [m, b, nprobe, ksub] over the coarse probe
    ranking, subspace-major — the input the Pallas scoring kernel
    (ops/pallas_pq.py) keeps resident in VMEM per (query, rank); traced
    inside `ivfpq_search_program`."""
    b, d = queries.shape
    m, ksub, _ = codebooks.shape
    nprobe = probes_coarse.shape[1]
    resid = (
        queries[:, None, :] - jnp.take(centroids, probes_coarse, axis=0)
    ).reshape(b * nprobe, d)
    lut = _residual_lut_tables(resid, codebooks, _codebook_sqnorms(codebooks),
                               subspace_major=True)
    return lut.reshape(m, b, nprobe, ksub)


def _adc_shortlist(scores, slots, k):
    """ONE top-k over every probed slot's ADC score ([b, n] each, -inf where
    nothing was scored) -> (wire distances [b, k], slots [b, k], -1 past
    the live candidates). Both ADC arms select here, by the same code."""
    kk = min(k, scores.shape[1])
    vals, idx = jax.lax.top_k(scores, kk)
    slots = jnp.where(jnp.isneginf(vals), -1,
                      jnp.take_along_axis(slots, idx, axis=1))
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)),
                       constant_values=float("-inf"))
        slots = jnp.pad(slots, ((0, 0), (0, k - kk)), constant_values=-1)
    return -vals, slots    # wire convention: squared-L2-approx ascending


@sentinel_jit("index.ivfpq.scan", static_argnames=("k", "precompute_lut"))
def _ivfpq_scan_kernel(
    code_buckets,      # [B, cap_list, m] uint8 (spill buckets, ivf_layout.py)
    bucket_valid,      # [B, cap_list] bool
    bucket_slot,       # [B, cap_list] int32
    bucket_coarse,     # [B] int32: coarse list of each bucket (for residuals)
    probes_coarse,     # [b, nprobe] int32 coarse probe ranking
    probes,            # [b, budget] int32 virtual bucket ids (-1 pad)
    coarse_pos,        # [b, budget] int32 coarse rank of each virtual probe
    queries,           # [b, d] f32
    centroids,         # [nlist, d] f32
    codebooks,         # [m, ksub, dsub] f32
    k,
    precompute_lut,
):
    """ADC scan over probed lists with per-(query, list) residual LUTs.

    precompute_lut=True builds the [b, nprobe, m, ksub] LUT once over the
    COARSE probe ranking and gathers per rank — a hot list's spill buckets
    then share one LUT instead of recomputing it per bucket. The flag is
    static so callers can fall back when the LUT would not fit HBM."""
    b, d = queries.shape
    m, ksub, dsub = codebooks.shape
    neg_inf = jnp.float32(-jnp.inf)
    cb_sq = _codebook_sqnorms(codebooks)                # [m, ksub]

    def lut_for(resid):
        """residual targets [n, d] -> LUT [n, m, ksub] (shared formula)."""
        return _residual_lut_tables(resid, codebooks, cb_sq)

    if precompute_lut:
        nprobe = probes_coarse.shape[1]
        resid_all = queries[:, None, :] - jnp.take(
            centroids, probes_coarse, axis=0
        )                                               # [b, nprobe, d]
        lut_all = lut_for(resid_all.reshape(b * nprobe, d)).reshape(
            b, nprobe, m, ksub
        )

    def body(_, r):
        vlists = jnp.take(probes, r, axis=1)            # [b] virtual bucket ids
        rank_ok = vlists >= 0
        bkt = jnp.where(rank_ok, vlists, 0)
        if precompute_lut:
            cp = jnp.take(coarse_pos, r, axis=1)        # [b]
            lut = jnp.take_along_axis(
                lut_all, cp[:, None, None, None], axis=1
            )[:, 0]                                     # [b, m, ksub]
        else:
            lists_r = jnp.take(bucket_coarse, bkt)      # coarse list per bucket
            qr = queries - jnp.take(centroids, lists_r, axis=0)
            lut = lut_for(qr)                           # [b, m, ksub]

        codes = jnp.take(code_buckets, bkt, axis=0)      # [b, cap, m]
        val = jnp.take(bucket_valid, bkt, axis=0) & rank_ok[:, None]
        slot = jnp.take(bucket_slot, bkt, axis=0)
        # ADC: dist[b, cap] = sum_m LUT[b, m, codes[b, cap, m]], as a
        # compare-select-sum over the ksub codewords (one fused vector
        # reduction) and not as a gather from the table: a TPU gather
        # costs ~30 ns an element, 350 ms a b = 64 request at nprobe 32
        # (PERF.md section 6, PR 37)
        hit = codes[..., None] == jnp.arange(ksub, dtype=codes.dtype)
        dist = jnp.where(hit, lut[:, None, :, :], 0.0).sum(axis=(2, 3))
        return None, (jnp.where(val, -dist, neg_inf), slot)

    # every probed bucket's scores, then ONE top-k over them: a running
    # merge would sort k + cap entries at every rank, and k is the rerank
    # shortlist (topk x ivfpq_rerank_factor), hundreds wide
    _, (scores, slots) = jax.lax.scan(body, None, jnp.arange(probes.shape[1]))
    return _adc_shortlist(jnp.transpose(scores, (1, 0, 2)).reshape(b, -1),
                          jnp.transpose(slots, (1, 0, 2)).reshape(b, -1), k)


@sentinel_jit("index.ivfpq.search",
              static_argnames=("k", "kscan", "nprobe", "metric", "pallas",
                               "precompute_lut", "interpret"))
def ivfpq_search_program(
    qpad,            # [b, d] f32 padded queries
    centroids,       # [nlist, d]
    c_sqnorm,        # [nlist]
    probe_table,     # [nlist, max_spill] int32 bucket ids per list (-1 pad)
    valid,           # [B, cap] bool: resident bucket_valid, or a filter's
    bucket_slot,     # [B, cap] int32
    bucket_coarse,   # [B] int32 coarse list of each bucket
    code_buckets,    # [B, cap, m] uint8
    codebooks,       # [m, ksub, dsub] f32
    vecs,            # [capacity, d] device rows for the exact rerank, or None
    sqnorm,          # [capacity] f32, or None
    k: int,
    kscan: int,
    nprobe: int,
    metric: Metric,
    pallas: bool,
    precompute_lut: bool,
    interpret: bool = False,
):
    """A trained search's whole device side as ONE program, as IVF_FLAT's
    ``ivf_search_program``: coarse probes, expansion through the view's
    probe table, the residual LUT and the ADC top-`kscan` over the probed
    code buckets, and — where `vecs` is given — the exact fp32 rerank of
    that shortlist to `k` -> (wire distances, slots, probes[b, nprobe],
    vprobes[b, budget]). Without `vecs` the distances are the ADC ones
    (rerank factor 1, or host rows that resolve() reranks). `pallas` scores
    the probed buckets with the Pallas kernel (ops/pallas_pq.py), else with
    the XLA rank scan; both take the shortlist by `_adc_shortlist`."""
    from dingo_tpu.ops.rerank import exact_rerank_device

    probes = coarse_probes(qpad, centroids, c_sqnorm, nprobe)
    vprobes, coarse_pos = expand_probes_ranked(
        probes, probe_table, nprobe, probe_table.shape[1])
    if pallas:
        from dingo_tpu.ops.pallas_pq import ivf_pq_adc_scores

        b = qpad.shape[0]
        scores = ivf_pq_adc_scores(
            vprobes, coarse_pos,
            _ivfpq_adc_lut(qpad, centroids, probes, codebooks),
            code_buckets, valid, interpret=interpret,
        )
        slots = jnp.take(bucket_slot, jnp.maximum(vprobes, 0), axis=0)
        dists, slots = _adc_shortlist(scores.reshape(b, -1),
                                      slots.reshape(b, -1), kscan)
    else:
        dists, slots = _ivfpq_scan_kernel.__wrapped__(
            code_buckets, valid, bucket_slot, bucket_coarse, probes,
            vprobes, coarse_pos, qpad, centroids, codebooks, kscan,
            precompute_lut,
        )
    if vecs is not None:
        dists, slots = exact_rerank_device.__wrapped__(
            vecs, sqnorm, qpad, slots, k, metric)
    return dists, slots, probes, vprobes


class TpuIvfPq(IvfViewMaintenance, _SlotStoreIndex):
    def __init__(self, index_id: int, parameter: IndexParameter):
        VectorIndex.__init__(self, index_id, parameter)
        p = parameter
        if p.dimension <= 0:
            raise InvalidParameter(f"dimension {p.dimension}")
        if p.dimension % p.nsubvector:
            raise InvalidParameter(
                f"dimension {p.dimension} not divisible by m={p.nsubvector}"
            )
        if p.nbits_per_idx != 8:
            raise InvalidParameter("only nbits=8 supported (uint8 codes)")
        if p.metric is Metric.HAMMING:
            raise InvalidParameter("hamming not valid for IVF_PQ")
        from dingo_tpu.index.base import resolve_precision

        self._precision = resolve_precision(p)
        if self._precision == "sq8":
            raise InvalidParameter(
                "IVF_PQ codes are already quantized; sq8 applies to "
                "FLAT/IVF_FLAT (use bf16 here for a smaller exact store)"
            )
        store_dtype = (
            jnp.bfloat16 if self._precision == "bf16" else jnp.dtype(p.dtype)
        )
        store_cls = HostSlotStore if p.host_vectors else SlotStore
        self.store = store_cls(p.dimension, store_dtype)
        self.nlist = p.ncentroids
        self.m = p.nsubvector
        self.ksub = 1 << p.nbits_per_idx
        self.centroids: Optional[jax.Array] = None
        self._c_sqnorm: Optional[jax.Array] = None
        self.codebooks: Optional[jax.Array] = None       # [m, ksub, dsub]
        self._assign_h = np.full((self.store.capacity,), -1, np.int32)
        self._codes: Optional[jax.Array] = None          # [capacity, m] uint8
        self._code_buckets = None                        # [alloc, cap_list, m]
        self._view: Optional[MutableIvfView] = None
        self._view_dirty = True
        self._filter_cache: dict = {}
        self._kernel_metric = p.metric
        self._kernel_nbits = 0

    # -- prep (shared shape checks + cosine normalize) ----------------------
    def _prep_vectors(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dimension:
            raise InvalidParameter(
                f"vector dim {vectors.shape} != {self.dimension}"
            )
        if self.metric is Metric.COSINE and not getattr(
                self, "_rows_prenormalized", False):
            # load() re-ingests rows the store already normalized once;
            # normalizing again drifts low-order bits (||x|| lands NEAR 1,
            # not exactly) and would break the snapshot's bit-exact
            # restore-digest verification
            vectors = np_normalize(vectors)
        return vectors

    def _prep_queries(self, queries: np.ndarray) -> np.ndarray:
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        if queries.shape[1] != self.dimension:
            raise InvalidParameter(
                f"query dim {queries.shape[1]} != {self.dimension}"
            )
        if self.metric is Metric.COSINE:
            queries = np_normalize(queries)
        return queries

    # -- mutation ------------------------------------------------------------
    def _ensure_code_capacity(self) -> None:
        cap = self.store.capacity
        if self._assign_h.shape[0] < cap:
            grown = np.full((cap,), -1, np.int32)
            grown[: self._assign_h.shape[0]] = self._assign_h
            self._assign_h = grown
        if self._codes is not None and self._codes.shape[0] < cap:
            pad = cap - self._codes.shape[0]
            self._codes = jnp.concatenate(
                [self._codes, jnp.zeros((pad, self.m), jnp.uint8)]
            )

    @integrity_mutation
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        vectors = self._prep_vectors(vectors)
        if len(ids) != len(vectors):
            raise InvalidParameter("ids/vectors length mismatch")
        slots = self.store.put(np.asarray(ids, np.int64), vectors)
        self._ensure_code_capacity()
        from dingo_tpu.obs.quality import QUALITY

        # quality plane: the fp32 store/host rows ARE the shadow ground
        # truth for IVF_PQ, so this only syncs mirror-mode oracles
        QUALITY.observe_write(self, np.asarray(ids, np.int64), vectors)
        self._integrity_write(ids, vectors)
        if self.is_trained():
            dv = jnp.asarray(vectors)
            assign = kmeans_assign(dv, self.centroids)
            codes = _encode_residual(dv, assign, self.centroids, self.codebooks)
            assign_h = np.asarray(assign)
            self._assign_h[slots] = assign_h
            self._codes = self._codes.at[jnp.asarray(slots, jnp.int32)].set(codes)
            self._integrity_assign(ids, assign_h)
            self._integrity_codes(ids, codes)
            if self._view is not None and not self._view_dirty:
                # incremental: scatter the fresh codes into the bucketed
                # view instead of invalidating it (rows = device codes)
                self._view_apply_upsert(slots, assign_h, codes)
            else:
                self._invalidate_view()
        else:
            self._view_dirty = True
        self.write_count_since_save += len(ids)

    @integrity_mutation
    def delete(self, ids: np.ndarray) -> None:
        ids = np.asarray(ids, np.int64)
        slots = self.store.remove_slots(ids)
        removed = int((slots >= 0).sum())
        from dingo_tpu.obs.quality import QUALITY

        QUALITY.observe_delete(self, ids)
        self._integrity_delete(ids)
        if removed:
            if self._view is not None and not self._view_dirty:
                self._view_apply_delete(slots[slots >= 0])
            else:
                self._invalidate_view()
        self.write_count_since_save += removed

    # -- training ------------------------------------------------------------
    def need_train(self) -> bool:
        return True

    def is_trained(self) -> bool:
        return self.codebooks is not None

    def _rows_at_slots(self, slots: np.ndarray) -> np.ndarray:
        """Host rows for the given slots (one H2D-free slice for host
        stores; one bounded D2H gather for device stores)."""
        if isinstance(self.store, HostSlotStore):
            return np.asarray(self.store.vecs[slots], np.float32)
        with self.store.device_lock:  # vecs reference is donatable
            return np.asarray(
                jnp.take(self.store.vecs, jnp.asarray(slots, jnp.int32),
                         axis=0),
                np.float32,
            )

    @integrity_mutation
    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        # re-encodes every stored row into _codes chunk by chunk — a
        # scrub overlapping that must classify as raced, not corruption
        # (the decorator's bracket covers the whole method)
        cap = _resolve_train_cap(MAX_POINTS_PER_CENTROID * self.nlist)
        rng = np.random.default_rng(self.id)
        min_train = max(self.nlist, self.ksub)
        if vectors is None:
            # sample SLOTS instead of materializing every live row, and
            # gather them straight to device (ISSUE 18b): device stores
            # never round-trip rows at all, host stores upload only the
            # sample. Conf train.sample_rows=0 lifts the cap entirely —
            # full-corpus training as one chunked device Lloyd.
            live = np.flatnonzero(self.store.ids_by_slot >= 0)
            sel = live if (not cap or len(live) <= cap) else np.sort(
                rng.choice(live, cap, replace=False)
            )
            if len(sel) < min_train:
                raise NotTrained(
                    f"need >= {min_train} train vectors, have {len(sel)}"
                )
            dv = self.store.rows_device(sel)
            if self.metric is Metric.COSINE:
                dv = normalize(dv)
        else:
            vectors = np.asarray(vectors, np.float32)
            if len(vectors) < min_train:
                raise NotTrained(
                    f"need >= {min_train} train vectors, "
                    f"have {len(vectors)}"
                )
            if self.metric is Metric.COSINE:
                vectors = np_normalize(vectors)
            if cap and len(vectors) > cap:
                vectors = vectors[
                    rng.choice(len(vectors), cap, replace=False)
                ]
            dv = jnp.asarray(vectors)
        self.centroids, _ = train_kmeans(dv, k=self.nlist, iters=10, seed=self.id)
        self._c_sqnorm = squared_norms(self.centroids)
        assign = kmeans_assign(dv, self.centroids)
        resid = dv - jnp.take(self.centroids, assign, axis=0)
        self.codebooks = pq_train(resid, m=self.m, ksub=self.ksub, iters=10,
                                  seed=self.id)
        # encode everything stored, CHUNKED — the working set on device is
        # one chunk of rows, never the whole index
        self._codes = jnp.zeros((self.store.capacity, self.m), jnp.uint8)
        self._ensure_code_capacity()
        live = np.flatnonzero(self.store.ids_by_slot >= 0)
        for i in range(0, len(live), ENCODE_CHUNK):
            sl = live[i:i + ENCODE_CHUNK]
            dvv = jnp.asarray(self._rows_at_slots(sl))
            if self.metric is Metric.COSINE:
                dvv = normalize(dvv)
            a = kmeans_assign(dvv, self.centroids)
            codes = _encode_residual(dvv, a, self.centroids, self.codebooks)
            self._assign_h[sl] = np.asarray(a)
            self._codes = self._codes.at[jnp.asarray(sl, jnp.int32)].set(codes)
        # training reassigned + re-encoded every row: rebuild both digests
        self._integrity_reset_assign()
        self._integrity_reset_codes()
        self._invalidate_view()
        # retrain re-encoded every row: results change for identical query
        # bytes, so the serving-edge result cache (keyed on
        # mutation_version) must not serve pre-retrain entries as exact
        self.store.mutation_version += 1

    # -- state-integrity: PQ code artifact -----------------------------------
    def _integrity_codes(self, ids: np.ndarray, codes) -> None:
        """Fold freshly-encoded device codes into the 'pq_codes' digest
        (one bounded D2H of the batch's codes; off the search path and
        gated on integrity.enabled)."""
        from dingo_tpu.obs.integrity import INTEGRITY

        if len(ids) == 0 or not INTEGRITY.tracking(self):
            return
        INTEGRITY.note_write(self, "pq_codes", np.asarray(ids, np.int64),
                             np.asarray(codes, np.uint8))

    def _integrity_reset_codes(self) -> None:
        from dingo_tpu.obs.integrity import INTEGRITY

        if self._codes is None or not INTEGRITY.tracking(self):
            return
        INTEGRITY.reset_artifact(self, "pq_codes")
        live = np.flatnonzero(self.store.ids_by_slot >= 0)
        if len(live):
            codes_h = np.asarray(self._codes)
            self._integrity_codes(self.store.ids_by_slot[live],
                                  codes_h[live])

    # -- bucketed view (IvfViewMaintenance data hooks) -----------------------
    def _materialize_view_data(self, view: MutableIvfView) -> None:
        self._code_buckets = view.gather_rows(self._codes)

    def _scatter_view_data(self, upd, rows) -> None:
        """Scatter freshly-encoded codes ([n, m] uint8, device-resident)
        into the bucketed code view; caller holds device_lock."""
        from dingo_tpu.ops.scatter import pad_buckets, scatter_bucket_update

        if upd.grew_alloc is not None:
            self._code_buckets = pad_buckets(
                self._code_buckets, upd.grew_alloc
            )
        if not upd.appended:
            return
        cap = self._view.cap_list
        pos = np.asarray([p for p, _ in upd.appended], np.int64)
        src = np.asarray([i for _, i in upd.appended], np.int64)
        sel = jnp.take(rows, jnp.asarray(src, jnp.int32), axis=0)
        self._code_buckets = scatter_bucket_update(
            self._code_buckets,
            (pos // cap).astype(np.int32),
            (pos % cap).astype(np.int32),
            sel,
        )

    # -- search --------------------------------------------------------------
    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        nprobe: Optional[int] = None,
    ) -> List[SearchResult]:
        return self.search_async(queries, topk, filter_spec, nprobe)()

    def search_async(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        nprobe: Optional[int] = None,
        staged=None,
    ):
        from dingo_tpu.common.config import pallas_interpret, pallas_ivf_enabled
        from dingo_tpu.obs.heat import HEAT, heat_enabled
        from dingo_tpu.ops.distance import device_wait_begin
        from dingo_tpu.ops.topk import begin_host_fetch

        store = self.store
        topk = int(topk)
        host_rows = isinstance(store, HostSlotStore)
        # index.dispatch: entry to the program enqueued (prep, pad and H2D,
        # the view snapshot, one launch); NOOP when unsampled
        with TRACER.start_child("index.dispatch") as dspan:
            queries = self._prep_queries(queries)
            b = queries.shape[0]
            # staging-ring upload (serving pipeline): claimed only when the
            # identity check proves it was built from THESE queries
            qpad = staged.take(queries) if staged is not None else None
            if qpad is None:
                qpad = jnp.asarray(_pad_batch(queries))
            self._count_search()
            trained = self.is_trained()
            rerank = False      # host rows: the exact rerank runs in resolve
            probes = vprobes = None
            if trained:
                self._ensure_view()
                # request-pinned nprobe wins; else the SLO tuner's
                # override; else the configured default (obs/tuner.py)
                nprobe = min(
                    nprobe
                    or self.tuned("nprobe", self.parameter.default_nprobe),
                    self.nlist,
                )
                k_eff, nprobe = self._shape_buckets(topk, nprobe)
                quality_bucket = f"nprobe={nprobe}"
                factor = self.tuned(
                    "rerank_factor", int(FLAGS.get("ivfpq_rerank_factor"))
                )
                # ADC prune + exact rerank of topk * factor candidates:
                # device rows rerank inside the program, host rows at
                # resolve time (host gather)
                shortlist = factor > 1 and len(store) > 0
                rerank = shortlist and host_rows
                # not capped by the rows: a shortlist longer than the
                # index pads with -1, and the static stays one value
                kscan = max(k_eff, topk * factor) if shortlist else k_eff
                # share one residual LUT across a list's spill buckets when
                # the [b, nprobe, m, ksub] table fits comfortably in HBM
                lut_bytes = qpad.shape[0] * nprobe * self.m * self.ksub * 4
                precompute = lut_bytes <= 256 * 1024 * 1024
                # the Pallas scoring kernel: the IVF_FLAT list kernel's
                # tri-state crossover, where the precomputed LUT (its
                # resident VMEM operand) fits
                arm = "pallas" if (
                    pallas_ivf_enabled(self.dimension) and precompute
                ) else "xla"
                fprep = self._prep_filter_mask(filter_spec)
            else:
                # Hybrid contract: exact flat scan until trained
                # (vector_index_ivf_pq.h:113-115), whatever nprobe was
                # asked: its quality-estimator bucket is "exact", not the
                # caller's nprobe
                arm, quality_bucket = "exact", "exact"
                filtered = (
                    filter_spec is not None and not filter_spec.is_empty()
                )
                if host_rows:
                    mask_h = (
                        filter_spec.slot_mask(store.ids_by_slot) if filtered
                        else store.valid_h
                    )
                else:
                    mask = (
                        jnp.asarray(filter_spec.slot_mask(store.ids_by_slot))
                        if filtered else store.device_mask()
                    )
            # lease BEFORE any kernel dispatch: slots produced by the kernel
            # must stay stable (limbo-parked, not reassigned) until resolve
            # translates and, in rerank mode, gathers host rows for them
            lease = store.begin_search()
            try:
                if arm == "exact" and host_rows:
                    dists, slots = _chunked_host_scan(
                        store.vecs, store.sqnorm, mask_h, qpad,
                        k=topk, metric=self.metric,
                    )
                else:
                    # view snapshot + ONE launch under the device lock:
                    # incremental writes donate the bucket arrays to their
                    # scatter programs (see ivf_flat.search_async);
                    # index.lock_wait: asking for the lock to holding it
                    with TRACER.start_child("index.lock_wait"):
                        store.device_lock.acquire()
                    try:
                        if arm == "exact":
                            dists, slots = _flat_search_kernel(
                                store.vecs, store.sqnorm, mask, qpad,
                                k=topk, metric=self.metric, nbits=0,
                            )
                        else:
                            view = self._view
                            on_device = shortlist and not host_rows
                            dists, slots, probes, vprobes = \
                                ivfpq_search_program(
                                    qpad, self.centroids, self._c_sqnorm,
                                    view.probe_table,
                                    self._bucket_valid_for_filter(
                                        filter_spec, fprep),
                                    view.bucket_slot, view.bucket_coarse,
                                    self._code_buckets, self.codebooks,
                                    store.vecs if on_device else None,
                                    store.sqnorm if on_device else None,
                                    k=topk if on_device else kscan,
                                    kscan=kscan, nprobe=nprobe,
                                    metric=self.metric,
                                    pallas=arm == "pallas",
                                    precompute_lut=precompute,
                                    interpret=(pallas_interpret()
                                               if arm == "pallas" else False),
                                )
                    finally:
                        store.device_lock.release()
            except Exception:
                lease.release()
                raise
            METRICS.counter("ivfpq.scan_arm", region_id=self.id,
                            labels={"arm": arm}).add(1)
            # one-sync epilogue: the heat plane's probed-list ids and, for
            # a sampled request, the probed bucket ids ride the reply's ONE
            # D2H group (ivfpq.probed_codes_per_query costs no extra sync)
            heat_on = heat_enabled() and trained
            if heat_on:
                HEAT.register_layout(self.id, "ivf", self._heat_layout)
            probed = vprobes if dspan.sampled else None
            fetch = begin_host_fetch(dists, slots,
                                     probes if heat_on else None, probed)
        # the device wait of a sampled request, ended at resolve()'s fetch;
        # the ops.<stage> span names the arm that served
        wait = device_wait_begin({"pallas": "pallas_pq_adc", "xla": "pq_scan",
                                  "exact": "flat_scan"}[arm])

        def resolve() -> List[SearchResult]:
            try:
                fetched = jax.device_get(fetch)
                wait.end()
                # index.resolve: the host work after the fetch
                with TRACER.start_child("index.resolve"):
                    if probed is not None:
                        # joined LAST: [-1] whatever else is in the group
                        self._note_probed(
                            METRICS.gauge("ivfpq.probed_codes_per_query",
                                          region_id=self.id),
                            view, fetched[-1][:b])
                    if heat_on:
                        HEAT.observe(self.id, "ivf",
                                     fetched[2][:b])
                    if rerank:
                        # ADC was a prune; the exact rows sit in host
                        # memory (host_vectors mode), so rerank here: two
                        # syncs are INHERENT to this arm (the candidate
                        # slots reach the host before the row gather can
                        # start, and the rerank's output is a second
                        # round trip; adjudicated resolve-sync exception,
                        # see the dingolint baseline)
                        cand = np.asarray(fetched[1])[:b]
                        d_r, s_r = _exact_rerank_host(
                            store, qpad[:b], cand, topk, self.metric
                        )
                        dists_h, slots_h = jax.device_get((d_r, s_r))
                    else:
                        dists_h, slots_h = fetched[0], fetched[1]
                    # shape bucketing may have run a larger k; slice back
                    ids = store.ids_of_slots(slots_h[:b, :topk])
                    # head-sampled shadow scoring (async lane; noop at
                    # rate 0)
                    from dingo_tpu.obs.quality import QUALITY

                    QUALITY.observe_search(
                        self, queries, topk, ids, dists_h[:b, :topk],
                        bucket=quality_bucket, filter_spec=filter_spec,
                    )
                    return [
                        strip_invalid(i, d)
                        for i, d in zip(ids, dists_h[:b, :topk])
                    ]
            finally:
                lease.release()

        return resolve

    def _heat_layout(self) -> Optional[dict]:
        """Heat-plane layout provider: rows per coarse bucket from the
        host assignment array. A resident PQ row costs its codes (m
        bytes) plus the store rows kept for rerank (heat worker
        thread)."""
        assign = self._assign_h
        if assign is None:
            return None
        from dingo_tpu.obs.heat import TIER_BYTES

        rows = np.bincount(assign[assign >= 0].astype(np.int64),
                           minlength=self.nlist)
        tier = self._precision
        return {
            "unit_rows": rows,
            "row_bytes": self.m + self.dimension * TIER_BYTES.get(
                tier, 4.0),
            "tier": tier,
            "dim": self.dimension,
        }

    # -- lifecycle -----------------------------------------------------------
    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        snap = self.store.to_host()
        # f32 on disk: numpy savez can't serialize ml_dtypes bfloat16
        snap["vectors"] = np.asarray(snap["vectors"], np.float32)
        extras = {}
        if self.is_trained():
            extras["centroids"] = np.asarray(self.centroids)
            extras["codebooks"] = np.asarray(self.codebooks)
        np.savez(os.path.join(path, "ivf_pq.npz"), **snap, **extras)
        meta = self._save_meta()
        meta.update(nlist=self.nlist, m=self.m, trained=self.is_trained())
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump(meta, f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        self._check_meta(meta)
        if meta["nlist"] != self.nlist or meta["m"] != self.m:
            raise InvalidParameter("snapshot nlist/m mismatch")
        data = np.load(os.path.join(path, "ivf_pq.npz"))
        store_cls = (
            HostSlotStore if self.parameter.host_vectors else SlotStore
        )
        store_dtype = (
            jnp.bfloat16 if self._precision == "bf16"
            else jnp.dtype(self.parameter.dtype)
        )
        self.store = store_cls(self.dimension, store_dtype,
                               max(len(data["ids"]), 1))
        self._assign_h = np.full((self.store.capacity,), -1, np.int32)
        self._codes = None
        self.centroids = None
        self._c_sqnorm = None
        self.codebooks = None
        if meta.get("trained"):
            self.centroids = jnp.asarray(data["centroids"])
            self._c_sqnorm = squared_norms(self.centroids)
            self.codebooks = jnp.asarray(data["codebooks"])
            self._codes = jnp.zeros((self.store.capacity, self.m), jnp.uint8)
        self._view = None
        self._view_dirty = True
        self._filter_cache.clear()
        if len(data["ids"]):
            # rows on disk are already store-normalized (cosine): skip the
            # re-normalize so the restored bytes match the saved digests
            self._rows_prenormalized = True
            try:
                self.upsert(data["ids"], data["vectors"])
            finally:
                self._rows_prenormalized = False
        self.apply_log_id = meta["apply_log_id"]
        self._view_dirty = True
        self.write_count_since_save = 0
        self._integrity_on_restore(meta)
