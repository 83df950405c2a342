"""TpuShardedIvfFlat: an IVF_FLAT region sharded over a jax.sharding.Mesh.

VERDICT round-2 gap: only FLAT regions could live mesh-sharded, so the
BASELINE config-5 shape (multi-region hybrid IVF at 10M scale) had no
executable path. This class carries the full VectorIndex contract for
IVF_FLAT over the mesh — train/upsert/delete/search/save/load, filters,
NotTrained fallback — selectable from the factory behind
FLAGS.use_mesh_sharded_ivf, so a region served through IndexService can
span devices with the rest of the stack unchanged.

Design (reference analog: region sharding + client scatter-gather,
src/handler/raft_apply_handler.cc:702; SURVEY §7 step 8):

  rows    — shard over the mesh "data" axis, inheriting TpuShardedFlat's
            global slot space (shard s owns slots [s*cap, (s+1)*cap)),
            balanced allocation, donated scatters, and doubling growth.
  train   — distributed Lloyd k-means (ShardedFlatStore.train_kmeans:
            per-shard assignment, psum'd statistics); centroids replicate.
  layout  — per-shard skew-proof spill buckets (ivf_layout.build_layout on
            each shard's slot slice, one shared cap_list) stacked into
            [S, B, cap_list, d] device arrays; bucket rows gather ON
            DEVICE from the sharded store (no host round-trip).
  search  — ONE jit'd shard_map program: per shard, coarse-probe the
            replicated centroids, expand to spill buckets, run the same
            running-top-k bucket scan as the single-device index
            (ivf_flat.ivf_scan_scores), then all_gather + merge over
            "data" — XLA lowers the merge to ICI collectives.

The mesh "dim" axis must be 1: the bucket gather is row-local and the
scan kernel contracts the full feature dimension per shard. (Sharding d
as well would force a psum inside the lax.scan body — worse than letting
each shard keep whole rows, since IVF's win is row sparsity, not TP.)
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import List, Optional

import jax
import jax.numpy as jnp

from dingo_tpu.obs.sentinel import sentinel_jit
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    InvalidParameter,
    NotTrained,
    SearchResult,
    VectorIndex,
    strip_invalid,
)
from dingo_tpu.index.ivf_flat import coarse_probes, ivf_scan_scores
from dingo_tpu.index.ivf_layout import (
    MAX_CAP,
    MIN_CAP,
    build_layout,
    expand_probes,
)
from dingo_tpu.index.slot_store import _next_pow2
from dingo_tpu.ops.distance import (
    Metric,
    device_wait_begin,
    scores_to_distances,
    squared_norms,
)
from dingo_tpu.ops.kmeans import kmeans_assign
from dingo_tpu.ops.topk import merge_sharded_topk
from dingo_tpu.parallel.sharded_flat import TpuShardedFlat
from dingo_tpu.parallel.sharded_store import (
    account_merge,
    batch_spec,
    make_mesh,
    pad_query_batch,
)


@dataclasses.dataclass
class _ShardedView:
    """Stacked per-shard bucket layout, device-resident."""

    cap_list: int
    max_spill: int
    nbuckets: int                 # max over shards (short shards padded)
    buckets: jax.Array            # [S, B, cap_list, d]  P("data")
    bucket_sqnorm: jax.Array      # [S, B, cap_list]
    bucket_valid: jax.Array       # [S, B, cap_list] bool
    bucket_slot: jax.Array        # [S, B, cap_list] int32 (shard-LOCAL slot)
    bucket_slot_h: np.ndarray     # host copy for filter masking
    probe_table: jax.Array        # [S, nlist, max_spill] int32


class TpuShardedIvfFlat(TpuShardedFlat):
    """Mesh-sharded IVF_FLAT (reference VectorIndexIvfFlat contract)."""

    def __init__(self, index_id: int, parameter: IndexParameter,
                 mesh=None):
        if parameter.ncentroids <= 0:
            raise InvalidParameter(f"ncentroids {parameter.ncentroids}")
        if mesh is None:
            from dingo_tpu.common.config import FLAGS

            mesh = make_mesh(
                dim=1, batch=int(FLAGS.get("mesh_batch_axis") or 1)
            )
        if mesh.shape["dim"] != 1:
            raise InvalidParameter(
                "sharded IVF needs mesh dim axis == 1 (rows shard, the "
                "feature dim stays whole per shard)"
            )
        self.nlist = parameter.ncentroids
        self.centroids: Optional[jax.Array] = None     # [nlist, d] replicated
        self._c_sqnorm: Optional[jax.Array] = None
        self._view: Optional[_ShardedView] = None
        self._view_dirty = True
        super().__init__(index_id, parameter, mesh)
        self._build_ivf_programs()

    # -- allocation: keep assignments aligned with the gslot space -----------
    def _alloc(self, cap: int) -> None:
        old_cap = self.cap_per_shard
        super()._alloc(cap)
        S = self.n_shards
        if not hasattr(self, "_assign_h") or old_cap == 0:
            self._assign_h = np.full(S * cap, -1, np.int32)
        else:
            grown = np.full(S * cap, -1, np.int32)
            grown.reshape(S, cap)[:, :old_cap] = \
                self._assign_h.reshape(S, old_cap)
            self._assign_h = grown
        self._view_dirty = True

    # -- programs ------------------------------------------------------------
    def _build_ivf_programs(self) -> None:
        mesh = self.mesh
        scan_metric = self.metric

        def local_search(buckets, bsq, bval, bslot, ptable, centroids,
                         c_sq, queries, cap, *, k, nprobe, max_spill):
            # shard-local blocks arrive with a leading length-1 shard axis
            buckets, bsq, bval, bslot, ptable = (
                a[0] for a in (buckets, bsq, bval, bslot, ptable)
            )
            probes = coarse_probes(queries, centroids, c_sq, nprobe)
            vprobes = expand_probes(probes, ptable, nprobe, max_spill)
            vals, slots = ivf_scan_scores(
                buckets, bsq, bval, bslot, vprobes, queries, k, scan_metric
            )
            shard = jax.lax.axis_index("data")
            gslots = jnp.where(slots >= 0, slots + shard * cap, -1)
            all_vals = jax.lax.all_gather(vals, "data")       # [S, b, k]
            all_slots = jax.lax.all_gather(gslots, "data")
            return merge_sharded_topk(all_vals, all_slots, k)

        def search_fn(buckets, bsq, bval, bslot, ptable, centroids, c_sq,
                      queries, cap, k, nprobe, max_spill):
            out2 = batch_spec(mesh, None)
            f = shard_map(
                functools.partial(
                    local_search, k=k, nprobe=nprobe, max_spill=max_spill
                ),
                mesh=mesh,
                in_specs=(
                    P("data", None, None, None),   # buckets
                    P("data", None, None),         # bucket_sqnorm
                    P("data", None, None),         # bucket_valid
                    P("data", None, None),         # bucket_slot
                    P("data", None, None),         # probe_table
                    P(None, None),                 # centroids (replicated)
                    P(None),                       # c_sqnorm
                    batch_spec(mesh, None),        # queries (batch-split)
                    P(),                           # cap scalar
                ),
                out_specs=(out2, out2),
                check_vma=False,
            )
            return f(buckets, bsq, bval, bslot, ptable, centroids, c_sq,
                     queries, cap)

        self._ivf_search_jit = sentinel_jit(
            "parallel.ivf.search",
            search_fn, static_argnames=("k", "nprobe", "max_spill")
        )

        def gather_local(vecs, sqnorm, gidx):
            # vecs [cap, d], sqnorm [cap], gidx [1, B*cap_list]
            idx = gidx[0]
            rows = jnp.take(vecs, idx, axis=0)
            sq = jnp.take(sqnorm, idx)
            return rows[None], sq[None]

        def gather_fn(vecs, sqnorm, gidx, B, cap_list):
            f = shard_map(
                gather_local,
                mesh=mesh,
                in_specs=(P("data", None), P("data"), P("data", None)),
                out_specs=(P("data", None, None), P("data", None)),
                check_vma=False,
            )
            rows, sq = f(vecs, sqnorm, gidx)
            S = mesh.shape["data"]
            d = vecs.shape[1]
            return (
                rows.reshape(S, B, cap_list, d),
                sq.reshape(S, B, cap_list),
            )

        self._gather_view_jit = sentinel_jit(
            "parallel.ivf.gather_view",
            gather_fn, static_argnames=("B", "cap_list")
        )

        def assign_local(vecs, valid, centroids, c_sq):
            dots = jnp.einsum(
                "nd,kd->nk", vecs, centroids,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )
            a = jnp.argmin(-2.0 * dots + c_sq[None, :], axis=1)
            return jnp.where(valid, a.astype(jnp.int32), -1)

        def assign_fn(vecs, valid, centroids, c_sq):
            f = shard_map(
                assign_local,
                mesh=mesh,
                in_specs=(P("data", None), P("data"), P(None, None),
                          P(None)),
                out_specs=P("data"),
                check_vma=False,
            )
            return f(vecs, valid, centroids, c_sq)

        self._assign_jit = sentinel_jit("parallel.ivf.assign", assign_fn)

    # -- training ------------------------------------------------------------
    def need_train(self) -> bool:
        return True

    def is_trained(self) -> bool:
        return self.centroids is not None

    def train(self, vectors: Optional[np.ndarray] = None) -> None:
        """Distributed Lloyd over the sharded rows (or an explicit train
        set, reference Train(vectors) contract)."""
        if vectors is not None:
            from dingo_tpu.ops.kmeans import train_kmeans

            vectors = self._prep(np.asarray(vectors, np.float32))
            if len(vectors) < self.nlist:
                raise NotTrained(
                    f"need >= {self.nlist} train vectors, have {len(vectors)}"
                )
            centroids, _ = train_kmeans(
                jnp.asarray(vectors), k=self.nlist, iters=10, seed=self.id
            )
            centroids = np.asarray(centroids)
        else:
            live = int((self.ids_by_gslot >= 0).sum())
            if live < self.nlist:
                raise NotTrained(
                    f"need >= {self.nlist} stored vectors, have {live}"
                )
            with self._device_lock:
                centroids, _ = self._store.train_kmeans(
                    k=self.nlist, iters=10, seed=self.id
                )
        sharding = NamedSharding(self.mesh, P(None, None))
        self.centroids = jax.device_put(
            jnp.asarray(centroids, jnp.float32), sharding
        )
        self._c_sqnorm = jax.device_put(
            squared_norms(self.centroids), NamedSharding(self.mesh, P(None))
        )
        # (re)assign everything currently stored, on device, sharded
        with self._device_lock:
            assign = np.asarray(jax.device_get(self._assign_jit(
                self._store.vecs, self._store.valid, self.centroids,
                self._c_sqnorm,
            )))
        self._assign_h = np.where(self.ids_by_gslot >= 0, assign, -1) \
            .astype(np.int32)
        self._view_dirty = True

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
            assign = np.asarray(kmeans_assign(
                jnp.asarray(vectors), self.centroids
            ))
            slots = np.fromiter(
                (self._id_to_gslot[int(v)] for v in ids), np.int64, len(ids)
            )
            self._assign_h[slots] = assign
        self._view_dirty = True

    def delete(self, ids: np.ndarray) -> int:
        n = super().delete(ids)
        if n:
            self._view_dirty = True
        return n

    # -- bucketed view -------------------------------------------------------
    def _build_shard_layouts(self):
        """Per-shard spill-bucket layouts stacked to common shapes (host
        arrays); shared by the IVF_FLAT and IVF_PQ sharded views."""
        S, cap = self.n_shards, self.cap_per_shard
        liveness = self.ids_by_gslot >= 0
        assign2 = self._assign_h.reshape(S, cap)
        valid2 = liveness.reshape(S, cap)
        mean = max(1, int(np.ceil(
            liveness.sum() / max(1, S * self.nlist)
        )))
        cap_list = min(MAX_CAP, max(MIN_CAP, _next_pow2(mean)))
        lays = [
            build_layout(assign2[s], valid2[s], self.nlist,
                         cap_hint=cap_list)
            for s in range(S)
        ]
        B = max(l.nbuckets for l in lays)
        spill = max(l.max_spill for l in lays)
        bucket_slot = np.full((S, B, cap_list), -1, np.int32)
        bucket_valid = np.zeros((S, B, cap_list), bool)
        probe_table = np.full((S, self.nlist, spill), -1, np.int32)
        gather_idx = np.zeros((S, B * cap_list), np.int32)
        bucket_coarse = np.zeros((S, B), np.int32)
        for s, l in enumerate(lays):
            bucket_slot[s, : l.nbuckets] = l.bucket_slot_h
            bucket_valid[s, : l.nbuckets] = np.asarray(l.bucket_valid)
            probe_table[s, :, : l.max_spill] = np.asarray(l.probe_table)
            gather_idx[s, : l.nbuckets * cap_list] = np.asarray(l.gather_idx)
            bucket_coarse[s, : l.nbuckets] = np.asarray(l.bucket_coarse)
        return (cap_list, spill, B, bucket_slot, bucket_valid, probe_table,
                gather_idx, bucket_coarse)

    def _rebuild_view(self) -> None:
        (cap_list, spill, B, bucket_slot, bucket_valid, probe_table,
         gather_idx, _) = self._build_shard_layouts()
        sh3 = NamedSharding(self.mesh, P("data", None, None))
        sh2 = NamedSharding(self.mesh, P("data", None))
        gidx_dev = jax.device_put(gather_idx, sh2)
        with self._device_lock:
            buckets, bsq = self._gather_view_jit(
                self._store.vecs, self._store.sqnorm, gidx_dev,
                B=B, cap_list=cap_list,
            )
        self._view = _ShardedView(
            cap_list=cap_list,
            max_spill=spill,
            nbuckets=B,
            buckets=buckets,
            bucket_sqnorm=bsq,
            bucket_valid=jax.device_put(bucket_valid, sh3),
            bucket_slot=jax.device_put(bucket_slot, sh3),
            bucket_slot_h=bucket_slot,
            probe_table=jax.device_put(probe_table, sh3),
        )
        self._view_dirty = False

    def _filtered_bucket_valid(self, filter_spec: Optional[FilterSpec],
                               bucket_valid, bucket_slot_h: np.ndarray):
        """Apply a scalar filter to a stacked per-shard bucket-validity
        array (shared by the IVF_FLAT and IVF_PQ sharded views)."""
        if filter_spec is None or filter_spec.is_empty():
            return bucket_valid
        S, cap = self.n_shards, self.cap_per_shard
        mask2 = filter_spec.slot_mask(self.ids_by_gslot).reshape(S, cap)
        safe = np.where(bucket_slot_h >= 0, bucket_slot_h, 0)
        bmask = np.take_along_axis(
            mask2, safe.reshape(S, -1), axis=1
        ).reshape(bucket_slot_h.shape) & (bucket_slot_h >= 0)
        return jax.device_put(
            bmask, NamedSharding(self.mesh, P("data", None, None))
        )

    def _bucket_valid_for_filter(self, filter_spec: Optional[FilterSpec]):
        return self._filtered_bucket_valid(
            filter_spec, self._view.bucket_valid, self._view.bucket_slot_h
        )

    def _make_resolve(self, vals, gslots, b: int,
                      ids_by_gslot: np.ndarray):
        """Shared resolver: translate merged gslots to vector ids and
        scores to wire distances (the caller snapshots ids_by_gslot under
        its device lock — growth remaps the gslot space)."""
        vals.copy_to_host_async()
        gslots.copy_to_host_async()
        metric = self.metric
        # device wait of a sampled request, ended at the reply's one fetch
        wait = device_wait_begin("mesh_search")

        def resolve() -> List[SearchResult]:
            vals_h, gslots_h = jax.device_get((vals, gslots))
            wait.end()
            vals_h, gslots_h = vals_h[:b], gslots_h[:b]
            safe = np.where(gslots_h >= 0, gslots_h, 0)
            ids = np.where(gslots_h >= 0, ids_by_gslot[safe], -1)
            dists = np.asarray(
                scores_to_distances(jnp.asarray(vals_h), metric)
            )
            return [strip_invalid(i, d) for i, d in zip(ids, dists)]

        return resolve

    # -- search --------------------------------------------------------------
    def search(self, queries, topk, filter_spec=None, nprobe=None, **kw):
        return self.search_async(queries, topk, filter_spec, nprobe)()

    def search_async(self, queries, topk,
                     filter_spec: Optional[FilterSpec] = None,
                     nprobe: Optional[int] = None, **kw):
        if not self.is_trained():
            raise NotTrained("sharded IVF_FLAT not trained")
        from dingo_tpu.parallel.tracing import shard_search_span

        with shard_search_span("parallel.ivf.search", self.mesh) as span:
            queries = self._prep(np.atleast_2d(np.asarray(queries, np.float32)))
            b = queries.shape[0]
            nprobe = min(nprobe or self.parameter.default_nprobe, self.nlist)
            qpad = jnp.asarray(pad_query_batch(queries, self.mesh))
            with self._device_lock:
                if self._view_dirty:
                    self._rebuild_view()
                view = self._view
                bval = self._bucket_valid_for_filter(filter_spec)
                q = jax.device_put(
                    qpad,
                    NamedSharding(self.mesh, batch_spec(self.mesh, None)),
                )
                vals, gslots = self._ivf_search_jit(
                    view.buckets, view.bucket_sqnorm, bval, view.bucket_slot,
                    view.probe_table, self.centroids, self._c_sqnorm, q,
                    jnp.int32(self.cap_per_shard),
                    k=int(topk), nprobe=int(nprobe),
                    max_spill=int(view.max_spill),
                )
                ids_by_gslot = self.ids_by_gslot.copy()
            account_merge(self.mesh, int(qpad.shape[0]), int(topk),
                          region_id=self.id)
            span.set_attr("batch", b)
            span.set_attr("nprobe", int(nprobe))
        return self._make_resolve(vals, gslots, b, ids_by_gslot)

    # -- lifecycle -----------------------------------------------------------
    def save(self, path: str) -> None:
        super().save(path)
        extras = {}
        if self.is_trained():
            live = np.flatnonzero(self.ids_by_gslot >= 0)
            extras = {
                "centroids": np.asarray(jax.device_get(self.centroids)),
                "ids": self.ids_by_gslot[live],
                "assign": self._assign_h[live],
            }
            np.savez(os.path.join(path, "sharded_ivf.npz"), **extras)
        meta_path = os.path.join(path, "meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        meta["nlist"] = self.nlist
        meta["trained"] = self.is_trained()
        with open(meta_path, "w") as f:
            json.dump(meta, f)

    def load(self, path: str) -> None:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        if meta.get("nlist") != self.nlist:
            raise InvalidParameter(
                f"snapshot nlist {meta.get('nlist')} != {self.nlist}"
            )
        self.centroids = None
        self._c_sqnorm = None
        super().load(path)
        if meta.get("trained"):
            data = np.load(os.path.join(path, "sharded_ivf.npz"))
            sharding = NamedSharding(self.mesh, P(None, None))
            self.centroids = jax.device_put(
                jnp.asarray(data["centroids"]), sharding
            )
            self._c_sqnorm = jax.device_put(
                squared_norms(self.centroids),
                NamedSharding(self.mesh, P(None)),
            )
            slots = np.fromiter(
                (self._id_to_gslot[int(v)] for v in data["ids"]),
                np.int64, len(data["ids"]),
            )
            self._assign_h[slots] = data["assign"]
        self._view_dirty = True
