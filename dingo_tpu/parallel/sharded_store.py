"""Region vectors sharded over a TPU mesh: distributed search + train.

The TPU answer to the reference's cross-node scale story (regions +
client-side scatter-gather; brpc fan-out): one region's vectors live in a
jax.sharding.Mesh over a ("batch", "data", "dim") layout —

  batch axis — OPTIONAL query data parallelism (read replicas): the
              coalesced query batch splits across batch replicas, each
              replica scans the full set of row shards against its query
              slice, and vector state REPLICATES over this axis. Present
              only when the mesh is built with batch > 1, so the classic
              2D ("data", "dim") meshes (and every existing snapshot /
              test) are untouched.
  data axis — rows (vectors) sharded, the DP analog of region shards;
              per-device local top-k then all_gather + merge, the ICI
              replacement for the reference's RPC scatter-gather.
  dim axis  — feature dimension sharded (TP): each device holds a d/TP
              column slice, partial dot products psum over the axis.

Everything below runs in one jit'd shard_map program, so XLA inserts the
collectives (psum for partial dots, all_gather for top-k merge) over ICI.
A non-collective FALLBACK search (FLAGS.mesh_collective_merge = false)
stops after the per-shard local top-k and merges the [S, b, k] shortlists
on the host — transfers stay capped at k rows per shard either way; the
full per-shard score matrices never leave the device.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dingo_tpu.ops.distance import Metric, np_normalize
from dingo_tpu.ops.topk import merge_sharded_topk, topk_scores
from dingo_tpu.obs.sentinel import sentinel_jit


def make_mesh(n_devices: Optional[int] = None, data: Optional[int] = None,
              dim: int = 1, batch: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Device mesh for the sharded index family.

    batch == 1 (default) keeps the historical 2-axis ("data", "dim") mesh;
    batch > 1 prepends a "batch" (query DP / replica) axis. `devices`
    restricts the mesh to an explicit device slice (replica groups place
    sibling meshes on disjoint slices of one host's device set).
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = n_devices or len(devs)
    if batch < 1 or batch & (batch - 1):
        raise ValueError(f"mesh batch axis {batch} must be a power of two")
    data = data or (n // (dim * batch))
    assert batch * data * dim == n, \
        f"mesh {batch}x{data}x{dim} != {n} devices"
    if batch == 1:
        return Mesh(
            np.asarray(devs[:n]).reshape(data, dim),
            axis_names=("data", "dim"),
        )
    return Mesh(
        np.asarray(devs[:n]).reshape(batch, data, dim),
        axis_names=("batch", "data", "dim"),
    )


def mesh_has_batch(mesh: Mesh) -> bool:
    return "batch" in mesh.axis_names


def batch_spec(mesh: Mesh, *rest) -> P:
    """PartitionSpec whose leading (query-batch) dim shards over 'batch'
    when the mesh has that axis, replicates otherwise."""
    return P("batch" if mesh_has_batch(mesh) else None, *rest)


def pad_query_batch(queries: np.ndarray, mesh: Mesh) -> np.ndarray:
    """Shape-bucket-ladder padding for the query batch: pow2 (the ladder
    the single-device indexes already compile against) raised to at least
    the batch-axis size so the split stays exact. Padded rows are zero
    queries whose results the caller trims."""
    from dingo_tpu.index.slot_store import _next_pow2

    b = queries.shape[0]
    bb = _next_pow2(max(1, b))   # the ladder single-device indexes use
    if mesh_has_batch(mesh):
        bb = max(bb, mesh.shape["batch"])
    if bb != b:
        queries = np.concatenate(
            [queries, np.zeros((bb - b,) + queries.shape[1:], queries.dtype)]
        )
    return queries


def merge_host_topk(vals: np.ndarray, gslots: np.ndarray,
                    k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side merge of per-shard shortlists [S, b, k'] -> [b, k]
    (the non-collective fallback's reduce step; scores are 'larger is
    better' with -inf/-1 masking, same contract as merge_sharded_topk)."""
    s, b, kk = vals.shape
    flat_v = np.transpose(vals, (1, 0, 2)).reshape(b, s * kk)
    flat_i = np.transpose(gslots, (1, 0, 2)).reshape(b, s * kk)
    order = np.argsort(-flat_v, axis=1, kind="stable")[:, :k]
    out_v = np.take_along_axis(flat_v, order, axis=1)
    out_i = np.take_along_axis(flat_i, order, axis=1)
    out_i = np.where(np.isneginf(out_v), -1, out_i)
    return out_v, out_i


def account_merge(mesh: Mesh, b: int, k: int,
                  region_id: Optional[int] = None) -> None:
    """mesh.* observability for one collective-merge search: the shortlist
    payload the all_gather moves over the interconnect (every shard's
    [b, k] f32 scores + int32 slots, gathered once)."""
    from dingo_tpu.common.metrics import METRICS

    s = mesh.shape["data"]
    METRICS.counter("mesh.searches", region_id=region_id).add(1)
    METRICS.counter("mesh.merge_bytes", region_id=region_id).add(
        s * b * k * 8
    )


def _local_search(vecs, sqnorm, valid, queries, k, ascending):
    """Per-device block: partial dots psum'd over 'dim', local top-k over the
    row shard, then all_gather + merge over 'data'. Runs inside shard_map.
    With a batch axis, `queries` is this replica's query slice and the
    merge happens independently per batch replica."""
    vals, gslots = _local_topk(vecs, sqnorm, valid, queries, k, ascending)
    all_vals = jax.lax.all_gather(vals, "data")         # [S, b, k]
    all_slots = jax.lax.all_gather(gslots, "data")
    return merge_sharded_topk(all_vals, all_slots, k)


def _local_topk(vecs, sqnorm, valid, queries, k, ascending):
    """Shared scan: per-shard scores + local top-k with global slot ids
    (no cross-'data' collective — the fallback path stops here)."""
    if vecs.dtype == jnp.bfloat16:
        # bf16 precision tier: pair the query down so the contraction is a
        # native bf16 MXU matmul (accumulation stays f32 below)
        queries_c = queries.astype(jnp.bfloat16)
    else:
        queries_c = queries
    dots = jnp.einsum(
        "bd,nd->bn", queries_c, vecs,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    dots = jax.lax.psum(dots, "dim")                    # TP partial sums
    if ascending:  # L2: sqnorm is full-row norm (precomputed once, replicated
        # over 'dim'); query norm also psum'd from the local slice
        q_sq = jnp.einsum(
            "bd,bd->b", queries, queries,
            precision=jax.lax.Precision.HIGHEST,
        )
        q_sq = jax.lax.psum(q_sq, "dim")
        scores = -(q_sq[:, None] - 2.0 * dots + sqnorm[None, :])
    else:
        scores = dots
    vals, slots = topk_scores(scores, k, valid=valid)
    # local slot -> global slot
    shard = jax.lax.axis_index("data")
    cap = vecs.shape[0]
    gslots = jnp.where(slots >= 0, slots + shard * cap, -1)
    return vals, gslots


def _kmeans_step(vecs, valid, centroids):
    """One sharded Lloyd iteration: assignment on row shards with psum'd
    statistics over BOTH mesh axes. centroids replicated [k, d_local]."""
    dots = jnp.einsum(
        "nd,kd->nk", vecs, centroids,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    dots = jax.lax.psum(dots, "dim")
    c_sq = jax.lax.psum(
        jnp.einsum("kd,kd->k", centroids, centroids,
                   precision=jax.lax.Precision.HIGHEST),
        "dim",
    )
    x_sq = jax.lax.psum(
        jnp.einsum("nd,nd->n", vecs, vecs,
                   precision=jax.lax.Precision.HIGHEST),
        "dim",
    )
    dist = x_sq[:, None] - 2.0 * dots + c_sq[None, :]
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(jnp.argmin(dist, axis=1), k, dtype=jnp.float32)
    onehot = onehot * valid[:, None]
    sums = jnp.einsum("nk,nd->kd", onehot, vecs,
                      precision=jax.lax.Precision.HIGHEST)
    sums = jax.lax.psum(sums, "data")                   # DP reduce
    counts = jax.lax.psum(onehot.sum(axis=0), "data")
    new_c = jnp.where(
        (counts > 0.5)[:, None], sums / jnp.maximum(counts, 1.0)[:, None],
        centroids,
    )
    return new_c, counts


class ShardedFlatStore:
    """A region's vectors sharded [data, dim] with replicated metadata."""

    def __init__(self, mesh: Mesh, dim: int, metric: Metric = Metric.L2,
                 dtype=jnp.float32):
        if metric not in (Metric.L2, Metric.INNER_PRODUCT, Metric.COSINE):
            raise ValueError(f"unsupported sharded metric {metric}")
        self.mesh = mesh
        self.dim = dim
        self.metric = metric
        #: row storage dtype (f32, or bf16 for the bf16 precision tier —
        #: norms/accumulation stay f32)
        self.dtype = jnp.dtype(dtype)
        self.n_data = mesh.shape["data"]
        self.n_dim = mesh.shape["dim"]
        assert dim % self.n_dim == 0, "dim must divide over mesh 'dim' axis"
        self.cap_per_shard = 0
        self.vecs = None       # [S*cap, d] sharded ('data', 'dim')
        self.sqnorm = None     # [S*cap] sharded ('data',)
        self.valid = None
        self.ids_by_gslot: Optional[np.ndarray] = None  # host, int64
        self._build_programs()

    # -- data placement ------------------------------------------------------
    def load(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        vectors = np.asarray(vectors, np.float32)
        if self.metric is Metric.COSINE:
            vectors = np_normalize(vectors)
        n = vectors.shape[0]
        cap = -(-n // self.n_data)          # ceil
        cap = max(8, cap + (-cap) % 8)      # pad to sublane multiple
        total = cap * self.n_data
        pad = total - n
        vecs = np.concatenate(
            [vectors, np.zeros((pad, self.dim), np.float32)]
        )
        sqnorm = (vecs.astype(np.float64) ** 2).sum(1).astype(np.float32)
        valid = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
        self.ids_by_gslot = np.concatenate(
            [np.asarray(ids, np.int64), np.full(pad, -1, np.int64)]
        )
        self.cap_per_shard = cap
        self.vecs = jax.device_put(
            vecs.astype(self.dtype),
            NamedSharding(self.mesh, P("data", "dim"))
        )
        self.sqnorm = jax.device_put(
            sqnorm, NamedSharding(self.mesh, P("data"))
        )
        self.valid = jax.device_put(
            valid, NamedSharding(self.mesh, P("data"))
        )

    # -- jitted programs (built once per store; arrays are ARGUMENTS, never
    # closed over — a jit cache keyed on static self would bake stale device
    # arrays in after a reload) ----------------------------------------------
    def _build_programs(self):
        mesh = self.mesh
        ascending = self.metric is Metric.L2
        qspec = batch_spec(mesh, "dim")
        out2 = batch_spec(mesh, None)

        def search_fn(vecs, sqnorm, valid, queries, k):
            f = shard_map(
                functools.partial(_local_search, k=k, ascending=ascending),
                mesh=mesh,
                in_specs=(P("data", "dim"), P("data"), P("data"), qspec),
                out_specs=(out2, out2),
                check_vma=False,
            )
            return f(vecs, sqnorm, valid, queries)

        self._search_jit = sentinel_jit("parallel.flat.search", search_fn,
                                        static_argnames=("k",))

        def local_topk_fn(vecs, sqnorm, valid, queries, k):
            # fallback arm: stop after the per-shard top-k; each shard
            # contributes ONE [1, b, k] block stacked over 'data' — the
            # host merge downloads S*b*k entries, never the score matrix
            def body(vecs, sqnorm, valid, queries):
                vals, gslots = _local_topk(
                    vecs, sqnorm, valid, queries, k, ascending
                )
                return vals[None], gslots[None]

            stacked = P(
                "data", "batch" if mesh_has_batch(mesh) else None, None
            )
            f = shard_map(
                body,
                mesh=mesh,
                in_specs=(P("data", "dim"), P("data"), P("data"), qspec),
                out_specs=(stacked, stacked),
                check_vma=False,
            )
            return f(vecs, sqnorm, valid, queries)

        self._local_topk_jit = sentinel_jit(
            "parallel.flat.local_topk", local_topk_fn,
            static_argnames=("k",),
        )

        def train_fn(vecs, valid, centroids0, iters):
            step = shard_map(
                _kmeans_step,
                mesh=mesh,
                in_specs=(P("data", "dim"), P("data"), P(None, "dim")),
                out_specs=(P(None, "dim"), P()),
                check_vma=False,
            )

            def body(c, _):
                c2, counts = step(vecs, valid, c)
                return c2, counts

            centroids, counts = jax.lax.scan(
                body, centroids0, None, length=iters
            )
            return centroids, counts[-1]

        self._train_jit = sentinel_jit("parallel.flat.train", train_fn,
                                       static_argnames=("iters",))

        def sample_fn(vecs, idx):
            # replicated bounded gather: ships ONLY the sampled rows to the
            # host (the old path device_get the whole [S*cap, d] matrix to
            # take <= 64K sample rows — the dominant H2D cost of train on
            # big regions)
            return jnp.take(vecs, idx, axis=0).astype(jnp.float32)

        self._sample_jit = sentinel_jit(
            "parallel.flat.sample_rows", sample_fn,
            out_shardings=NamedSharding(mesh, P(None, None)),
        )

    def search(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (ids [b, k] int64 with -1 padding, distances [b, k])."""
        from dingo_tpu.common.config import FLAGS

        queries = np.asarray(queries, np.float32)
        b = queries.shape[0]
        if self.metric is Metric.COSINE:
            queries = np_normalize(queries)
        queries = pad_query_batch(queries, self.mesh)
        q = jax.device_put(
            queries, NamedSharding(self.mesh, batch_spec(self.mesh, "dim"))
        )
        if FLAGS.get("mesh_collective_merge"):
            vals, gslots = self._search_jit(
                self.vecs, self.sqnorm, self.valid, q, int(k)
            )
            account_merge(self.mesh, queries.shape[0], int(k))
            vals_h, gslots_h = jax.device_get((vals, gslots))
        else:
            vals_h, gslots_h = self._merge_local_host(q, int(k))
        vals_h, gslots_h = vals_h[:b], gslots_h[:b]
        safe = np.where(gslots_h >= 0, gslots_h, 0)
        ids = np.where(gslots_h >= 0, self.ids_by_gslot[safe], -1)
        dists = -vals_h if self.metric is Metric.L2 else vals_h
        return ids, dists

    def _merge_local_host(self, q, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """Non-collective fallback: download each shard's capped [b, k]
        shortlist and merge on the host (reference client-side
        scatter-gather shape, kept as the A/B + debug arm)."""
        from dingo_tpu.common.metrics import METRICS

        vals, gslots = self._local_topk_jit(
            self.vecs, self.sqnorm, self.valid, q, k
        )
        METRICS.counter("mesh.fallback_searches").add(1)
        vals_h, gslots_h = jax.device_get((vals, gslots))   # [S, b, k]
        return merge_host_topk(vals_h, gslots_h, k)

    # -- distributed k-means --------------------------------------------------
    def train_kmeans(self, k: int, iters: int = 10, seed: int = 0):
        """Distributed Lloyd iterations; returns (centroids [k, d], counts)."""
        from dingo_tpu.common.config import train_sample_rows

        rng = np.random.default_rng(seed)
        live = np.flatnonzero(self.ids_by_gslot >= 0)
        # Farthest-first seeding on a host sample (random seeds collapse when
        # a dense blob draws several — same fix as ops/kmeans.py). The sample
        # rows gather ON DEVICE: only [<=train.sample_rows, d] crosses to
        # the host. Note the Lloyd iterations below ALWAYS scan the full
        # sharded corpus — the conf cap (0 = uncapped) bounds only this
        # seeding sample.
        cap = train_sample_rows()
        sample_idx = (
            live if (not cap or len(live) <= cap)
            else rng.choice(live, cap, replace=False)
        )
        sample = np.asarray(jax.device_get(self._sample_jit(
            self.vecs, jnp.asarray(np.sort(sample_idx), jnp.int32)
        )), np.float32)
        chosen = [int(rng.integers(len(sample)))]
        min_d = np.full(len(sample), np.inf, np.float32)
        for _ in range(k - 1):
            c = sample[chosen[-1]]
            d = ((sample - c) ** 2).sum(1)
            np.minimum(min_d, d, out=min_d)
            chosen.append(int(np.argmax(min_d)))
        c0 = sample[chosen]
        c0 = jax.device_put(
            jnp.asarray(c0), NamedSharding(self.mesh, P(None, "dim"))
        )
        centroids, counts = self._train_jit(
            self.vecs, self.valid, c0, int(iters)
        )
        return jax.device_get(centroids), jax.device_get(counts)
