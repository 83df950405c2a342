"""TpuShardedIvfPq: an IVF_PQ region sharded over a jax.sharding.Mesh.

Closes the round-2 VERDICT gap chain (next #3): with FLAT and IVF_FLAT
already mesh-sharded, this carries the last BASELINE config-5 index type
so a multi-region hybrid IVF_PQ deployment (10M x 768, scalar
post-filter) can span devices end-to-end.

Design (reference analog: region scatter-gather, SURVEY §7 step 8; PQ
contract src/vector/vector_index_ivf_pq.cc):

  rows/coarse — inherited from TpuShardedIvfFlat: global slot space,
            distributed Lloyd k-means, replicated centroids, per-shard
            skew-proof spill buckets.
  codes   — [S*cap, m] uint8 DEVICE-resident, sharded over "data" like
            the rows; encoding (residual argmin over codebooks) runs as
            one shard_map program so no vector ever crosses shards.
  search  — ONE jit'd shard_map program per shard: coarse-probe the
            replicated centroids, ADC-scan the shard's probed code
            buckets (reusing the single-device `_ivfpq_scan_kernel`),
            take the ADC top-k' candidates, then EXACT-rerank them
            shard-locally — the candidate rows live in this shard's HBM,
            so the rerank is a [b, k', d] einsum with no host round-trip
            (the single-device index must rerank on the host because its
            10M rows only fit in host memory; sharded over the mesh the
            rows fit in device HBM, which is the point) — and finally
            all_gather + merge exact-scored candidates over "data".

The ADC prune + local exact rerank means recall matches the exact
rerank quality of the host-vectors path while keeping the whole search
on-device.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from dingo_tpu.common.config import FLAGS
from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    InvalidParameter,
    NotTrained,
)
from dingo_tpu.index.ivf_flat import coarse_probes
from dingo_tpu.index.ivf_pq import MAX_POINTS_PER_CENTROID, _ivfpq_scan_kernel
from dingo_tpu.index.ivf_layout import expand_probes_ranked
from dingo_tpu.ops.distance import Metric
from dingo_tpu.ops.kmeans import kmeans_assign
from dingo_tpu.ops.pq import pairwise_l2sqr, pq_train, split_subvectors
from dingo_tpu.obs.sentinel import sentinel_jit
from dingo_tpu.ops.topk import merge_sharded_topk
from dingo_tpu.parallel.sharded_ivf import TpuShardedIvfFlat
from dingo_tpu.parallel.sharded_store import (
    account_merge,
    batch_spec,
    pad_query_batch,
)


def _encode_codes(vecs, assign, centroids, codebooks, m):
    """Residual PQ encode -> [n, m] uint8 (rows with assign -1 get 0).
    The ONE encoding pipeline — train-time re-encode and incremental
    upsert must quantize identically or post-train rows silently lose
    recall."""
    safe = jnp.maximum(assign, 0)
    resid = vecs - jnp.take(centroids, safe, axis=0)
    subs = split_subvectors(resid, m)               # [m, n, dsub]

    def enc_one(sub, cb):
        return jnp.argmin(pairwise_l2sqr(sub, cb), axis=1)

    codes = jax.vmap(enc_one)(subs, codebooks).T.astype(jnp.uint8)
    return jnp.where((assign >= 0)[:, None], codes, 0)


@dataclasses.dataclass
class _PqShardedView:
    """Stacked per-shard code-bucket layout, device-resident."""

    cap_list: int
    max_spill: int
    nbuckets: int
    code_buckets: jax.Array       # [S, B, cap_list, m] uint8  P("data")
    bucket_valid: jax.Array       # [S, B, cap_list] bool
    bucket_slot: jax.Array        # [S, B, cap_list] int32 (shard-LOCAL slot)
    bucket_slot_h: np.ndarray     # host copy for filter masking
    probe_table: jax.Array        # [S, nlist, max_spill] int32
    bucket_coarse: jax.Array      # [S, B] int32


class TpuShardedIvfPq(TpuShardedIvfFlat):
    """Mesh-sharded IVF_PQ (reference VectorIndexIvfPq contract)."""

    def __init__(self, index_id: int, parameter: IndexParameter,
                 mesh=None):
        p = parameter
        if p.nsubvector <= 0 or p.dimension % p.nsubvector:
            raise InvalidParameter(
                f"dimension {p.dimension} not divisible by m={p.nsubvector}"
            )
        if p.nbits_per_idx != 8:
            raise InvalidParameter("only nbits=8 supported (uint8 codes)")
        self.m = p.nsubvector
        self.ksub = 1 << p.nbits_per_idx
        self.codebooks: Optional[jax.Array] = None     # [m, ksub, dsub]
        self._codes: Optional[jax.Array] = None        # [S*cap, m] uint8
        self._pq_view: Optional[_PqShardedView] = None
        #: cached per-instance programs (built lazily: their out_shardings
        #: capture self.mesh) — a fresh jax.jit per call would re-trace
        #: every invocation and hide the compiles from the sentinel
        self._code_update_jit = None
        self._gather_rows_jit = None
        super().__init__(index_id, parameter, mesh)
        self._build_pq_programs()

    # -- allocation: codes grow with the gslot space -------------------------
    def _alloc(self, cap: int) -> None:
        old_cap = self.cap_per_shard
        super()._alloc(cap)
        if self._codes is None:
            return   # codes exist only after _encode_all/load (cap > 0)
        S, m = self.n_shards, self.m
        sh = NamedSharding(self.mesh, P("data", None))
        pad = cap - old_cap

        def grow(c):
            c = c.reshape(S, old_cap, m)
            c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
            return c.reshape(S * cap, m)

        # growth cannot donate (output larger than input — no aliasing);
        # sentinel-wrapped so the per-(old_cap, cap) compile is accounted
        self._codes = sentinel_jit(
            "parallel.pq.grow_codes", grow, out_shardings=sh
        )(self._codes)

    # -- programs ------------------------------------------------------------
    def _build_pq_programs(self) -> None:
        mesh = self.mesh
        m = self.m
        metric = self.metric

        def encode_local(vecs, assign, centroids, codebooks):
            # vecs [cap, d], assign [cap] int32 (-1 unassigned)
            return _encode_codes(vecs, assign, centroids, codebooks, m)

        self._encode_all_jit = sentinel_jit("parallel.pq.encode_all", shard_map(
            encode_local, mesh=mesh,
            in_specs=(P("data", None), P("data"), P(None, None),
                      P(None, None, None)),
            out_specs=P("data", None),
            check_vma=False,
        ))

        def gather_codes_local(codes, gidx):
            return jnp.take(codes, gidx[0], axis=0)[None]

        def gather_codes_fn(codes, gidx, B, cap_list):
            f = shard_map(
                gather_codes_local, mesh=mesh,
                in_specs=(P("data", None), P("data", None)),
                out_specs=P("data", None, None),
                check_vma=False,
            )
            out = f(codes, gidx)
            S = mesh.shape["data"]
            return out.reshape(S, B, cap_list, m)

        self._gather_codes_jit = sentinel_jit(
            "parallel.pq.gather_codes",
            gather_codes_fn, static_argnames=("B", "cap_list")
        )

        def local_search(codebkts, bval, bslot, bcoarse, ptable, vecs,
                         sqnorm, centroids, c_sq, codebooks, queries, cap,
                         *, k, kprime, nprobe, max_spill, precompute_lut):
            codebkts, bval, bslot, bcoarse, ptable = (
                a[0] for a in (codebkts, bval, bslot, bcoarse, ptable)
            )
            probes = coarse_probes(queries, centroids, c_sq, nprobe)
            vprobes, cpos = expand_probes_ranked(
                probes, ptable, nprobe, max_spill
            )
            _, slots = _ivfpq_scan_kernel(
                codebkts, bval, bslot, bcoarse, probes, vprobes, cpos,
                queries, centroids, codebooks,
                k=kprime, precompute_lut=precompute_lut,
            )                                          # slots [b, kprime]
            # exact rerank: the candidate rows are THIS shard's — one take
            safe = jnp.maximum(slots, 0)
            rows = jnp.take(vecs, safe, axis=0)        # [b, kprime, d]
            rsq = jnp.take(sqnorm, safe)               # [b, kprime]
            dots = jnp.einsum(
                "bkd,bd->bk", rows, queries,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            if metric is Metric.L2:
                qsq = jnp.einsum(
                    "bd,bd->b", queries, queries,
                    precision=jax.lax.Precision.HIGHEST,
                )
                score = -(qsq[:, None] - 2.0 * dots + rsq)
            else:   # IP / cosine (rows+queries normalized at ingest)
                score = dots
            score = jnp.where(slots >= 0, score, -jnp.inf)
            vals, idx = jax.lax.top_k(score, min(k, score.shape[1]))
            sel = jnp.take_along_axis(slots, idx, axis=1)
            sel = jnp.where(jnp.isneginf(vals), -1, sel)
            shard = jax.lax.axis_index("data")
            gsl = jnp.where(sel >= 0, sel + shard * cap, -1)
            all_vals = jax.lax.all_gather(vals, "data")
            all_gsl = jax.lax.all_gather(gsl, "data")
            return merge_sharded_topk(all_vals, all_gsl, k)

        def search_fn(codebkts, bval, bslot, bcoarse, ptable, vecs, sqnorm,
                      centroids, c_sq, codebooks, queries, cap,
                      k, kprime, nprobe, max_spill, precompute_lut):
            out2 = batch_spec(mesh, None)
            f = shard_map(
                functools.partial(
                    local_search, k=k, kprime=kprime, nprobe=nprobe,
                    max_spill=max_spill, precompute_lut=precompute_lut,
                ),
                mesh=mesh,
                in_specs=(
                    P("data", None, None, None),   # code buckets
                    P("data", None, None),         # bucket_valid
                    P("data", None, None),         # bucket_slot
                    P("data", None),               # bucket_coarse
                    P("data", None, None),         # probe_table
                    P("data", None),               # vecs (rows)
                    P("data"),                     # sqnorm
                    P(None, None),                 # centroids
                    P(None),                       # c_sqnorm
                    P(None, None, None),           # codebooks
                    batch_spec(mesh, None),        # queries (batch-split)
                    P(),                           # cap scalar
                ),
                out_specs=(out2, out2),
                check_vma=False,
            )
            return f(codebkts, bval, bslot, bcoarse, ptable, vecs, sqnorm,
                     centroids, c_sq, codebooks, queries, cap)

        self._pq_search_jit = sentinel_jit(
            "parallel.pq.search",
            search_fn,
            static_argnames=(
                "k", "kprime", "nprobe", "max_spill", "precompute_lut"
            ),
        )

    # -- training ------------------------------------------------------------
    def is_trained(self) -> bool:
        return self.codebooks is not None

    def _rows_at_gslots(self, gslots: np.ndarray) -> np.ndarray:
        """Bounded replicated gather of sample rows from the sharded store
        (XLA inserts the cross-shard collective)."""
        if self._gather_rows_jit is None:
            self._gather_rows_jit = sentinel_jit(
                "parallel.pq.gather_rows",
                lambda v, i: jnp.take(v, i, axis=0),
                out_shardings=NamedSharding(self.mesh, P(None, None)),
            )
        with self._device_lock:
            out = self._gather_rows_jit(
                self._store.vecs, jnp.asarray(gslots, jnp.int32))
        return np.asarray(jax.device_get(out), np.float32)

    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        if vectors is not None:
            vectors = self._prep(np.asarray(vectors, np.float32))
            if len(vectors) < max(self.nlist, self.ksub):
                raise NotTrained(
                    f"need >= {max(self.nlist, self.ksub)} train vectors, "
                    f"have {len(vectors)}"
                )
        else:
            live = int((self.ids_by_gslot >= 0).sum())
            if live < max(self.nlist, self.ksub):
                raise NotTrained(
                    f"need >= {max(self.nlist, self.ksub)} stored vectors, "
                    f"have {live}"
                )
        self.codebooks = None     # parent search must not run mid-train
        super().train(vectors)    # centroids (distributed) + _assign_h
        rng = np.random.default_rng(self.id)
        cap = MAX_POINTS_PER_CENTROID * self.nlist
        if vectors is None:
            live_slots = np.flatnonzero(self.ids_by_gslot >= 0)
            sel = live_slots if len(live_slots) <= cap else np.sort(
                rng.choice(live_slots, cap, replace=False)
            )
            sample = self._rows_at_gslots(sel)
            assign = self._assign_h[sel]
        else:
            sample = vectors if len(vectors) <= cap else vectors[
                rng.choice(len(vectors), cap, replace=False)
            ]
            assign = np.asarray(kmeans_assign(
                jnp.asarray(sample), self.centroids
            ))
        cent_h = np.asarray(jax.device_get(self.centroids))
        resid = sample - cent_h[np.maximum(assign, 0)]
        cb = pq_train(jnp.asarray(resid), m=self.m, ksub=self.ksub,
                      iters=10, seed=self.id)
        self.codebooks = jax.device_put(
            cb, NamedSharding(self.mesh, P(None, None, None))
        )
        self._encode_all()
        self._view_dirty = True

    def _encode_all(self) -> None:
        """(Re)encode every stored row, one shard_map pass, codes sharded."""
        assign_dev = jax.device_put(
            jnp.asarray(self._assign_h, jnp.int32),
            NamedSharding(self.mesh, P("data")),
        )
        with self._device_lock:
            self._codes = self._encode_all_jit(
                self._store.vecs, assign_dev, self.centroids, self.codebooks
            )

    # -- mutation ------------------------------------------------------------
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        vectors = self._prep(vectors)
        ids = np.asarray(ids, np.int64)
        if len(ids) != len(np.unique(ids)):
            last = {int(v): i for i, v in enumerate(ids)}
            keep = sorted(last.values())
            ids, vectors = ids[keep], vectors[keep]
        super().upsert(ids, vectors)
        if self.is_trained() and len(ids):
            slots = np.fromiter(
                (self._id_to_gslot[int(v)] for v in ids), np.int64, len(ids)
            )
            dv = jnp.asarray(vectors)
            assign = jnp.asarray(self._assign_h[slots], jnp.int32)
            codes = _encode_codes(
                dv, assign, self.centroids, self.codebooks, self.m
            )
            if self._code_update_jit is None:
                # cached per-instance: the old inline jax.jit(lambda...)
                # minted a FRESH wrapper per upsert, re-tracing the code
                # scatter on every trained write batch — invisibly,
                # because nothing sentinel-counted it (bare-jit lint)
                self._code_update_jit = sentinel_jit(
                    "parallel.pq.code_update",
                    lambda c, s, v: c.at[s].set(v),
                    out_shardings=NamedSharding(self.mesh,
                                                P("data", None)),
                    donate_argnums=0,
                )
            with self._device_lock:
                self._codes = self._code_update_jit(
                    self._codes, jnp.asarray(slots, jnp.int32), codes)
        self._view_dirty = True

    # -- bucketed view -------------------------------------------------------
    def _rebuild_view(self) -> None:
        (cap_list, spill, B, bucket_slot, bucket_valid, probe_table,
         gather_idx, bucket_coarse) = self._build_shard_layouts()
        sh3 = NamedSharding(self.mesh, P("data", None, None))
        sh2 = NamedSharding(self.mesh, P("data", None))
        gidx_dev = jax.device_put(gather_idx, sh2)
        with self._device_lock:
            code_buckets = self._gather_codes_jit(
                self._codes, gidx_dev, B=B, cap_list=cap_list
            )
        self._pq_view = _PqShardedView(
            cap_list=cap_list,
            max_spill=spill,
            nbuckets=B,
            code_buckets=code_buckets,
            bucket_valid=jax.device_put(bucket_valid, sh3),
            bucket_slot=jax.device_put(bucket_slot, sh3),
            bucket_slot_h=bucket_slot,
            probe_table=jax.device_put(probe_table, sh3),
            bucket_coarse=jax.device_put(bucket_coarse, sh2),
        )
        self._view_dirty = False

    def _pq_bucket_valid_for_filter(
        self, filter_spec: Optional[FilterSpec]
    ):
        return self._filtered_bucket_valid(
            filter_spec, self._pq_view.bucket_valid,
            self._pq_view.bucket_slot_h,
        )

    # -- search --------------------------------------------------------------
    def search_async(self, queries, topk,
                     filter_spec: Optional[FilterSpec] = None,
                     nprobe: Optional[int] = None, **kw):
        if not self.is_trained():
            raise NotTrained("sharded IVF_PQ not trained")
        from dingo_tpu.parallel.tracing import shard_search_span

        with shard_search_span("parallel.pq.search", self.mesh) as span:
            queries = self._prep(np.atleast_2d(np.asarray(queries, np.float32)))
            b = queries.shape[0]
            nprobe = min(nprobe or self.parameter.default_nprobe, self.nlist)
            qpad = jnp.asarray(pad_query_batch(queries, self.mesh))
            k = int(topk)
            kprime = max(
                k, min(self.get_count() or k,
                       k * int(FLAGS.get("ivfpq_rerank_factor") or 1))
            )
            with self._device_lock:
                if self._view_dirty:
                    self._rebuild_view()
                view = self._pq_view
                bval = self._pq_bucket_valid_for_filter(filter_spec)
                q = jax.device_put(
                    qpad,
                    NamedSharding(self.mesh, batch_spec(self.mesh, None)),
                )
                # per-(query, coarse-list) LUT sharing is worthwhile only
                # while the [b, nprobe, m, ksub] table stays comfortably
                # in HBM
                lut_bytes = (
                    qpad.shape[0] * nprobe * self.m * self.ksub * 4
                )
                vals, gslots = self._pq_search_jit(
                    view.code_buckets, bval, view.bucket_slot,
                    view.bucket_coarse, view.probe_table,
                    self._store.vecs, self._store.sqnorm,
                    self.centroids, self._c_sqnorm, self.codebooks, q,
                    jnp.int32(self.cap_per_shard),
                    k=k, kprime=int(kprime), nprobe=int(nprobe),
                    max_spill=int(view.max_spill),
                    precompute_lut=lut_bytes <= 256 * 1024 * 1024,
                )
                ids_by_gslot = self.ids_by_gslot.copy()
            account_merge(self.mesh, int(qpad.shape[0]), k,
                          region_id=self.id)
            span.set_attr("batch", b)
            span.set_attr("nprobe", int(nprobe))
        return self._make_resolve(vals, gslots, b, ids_by_gslot)

    # -- lifecycle -----------------------------------------------------------
    def get_memory_size(self) -> int:
        return int(
            self.total_slots * (self.dimension * 4 + self.m)
            + self.m * self.ksub * (self.dimension // self.m) * 4
        )

    def save(self, path: str) -> None:
        super().save(path)       # rows + centroids + assignments + meta
        if self.is_trained():
            live = np.flatnonzero(self.ids_by_gslot >= 0)
            codes_h = np.asarray(jax.device_get(self._codes))
            np.savez(
                os.path.join(path, "sharded_pq.npz"),
                codebooks=np.asarray(jax.device_get(self.codebooks)),
                ids=self.ids_by_gslot[live],
                codes=codes_h[live],
            )
        meta_path = os.path.join(path, "meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        meta["m"] = self.m
        meta["pq_trained"] = self.is_trained()
        with open(meta_path, "w") as f:
            json.dump(meta, f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("m") != self.m:
            raise InvalidParameter(f"snapshot m {meta.get('m')} != {self.m}")
        self.codebooks = None
        self._codes = None
        super().load(path)       # rows + centroids + assignments
        if meta.get("pq_trained"):
            data = np.load(os.path.join(path, "sharded_pq.npz"))
            self.codebooks = jax.device_put(
                jnp.asarray(data["codebooks"]),
                NamedSharding(self.mesh, P(None, None, None)),
            )
            S, cap = self.n_shards, self.cap_per_shard
            codes_h = np.zeros((S * cap, self.m), np.uint8)
            slots = np.fromiter(
                (self._id_to_gslot[int(v)] for v in data["ids"]),
                np.int64, len(data["ids"]),
            )
            codes_h[slots] = data["codes"]
            self._codes = jax.device_put(
                jnp.asarray(codes_h),
                NamedSharding(self.mesh, P("data", None)),
            )
        self._view_dirty = True
