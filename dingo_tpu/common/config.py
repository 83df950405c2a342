"""Two-tier configuration: per-role config file + flag registry.

Reference: src/config/ — YamlConfig (yaml-cpp) loaded per role at boot,
ConfigManager singleton, ConfigHelper typed accessors with defaults
(config_helper.h:25-53), plus gflags for every tunable; yaml values override
gflag defaults at boot (server.cc:500-512).

No yaml parser is baked into this image, so config files are TOML-like
`section.key = value` lines (plus JSON support); the Flag registry plays the
gflags role with runtime mutability for the hot-changeable set.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Callable, Dict, Optional

_UNSET = object()


class Flag:
    def __init__(self, name: str, default: Any, help_: str = "",
                 mutable: bool = False):
        self.name = name
        self.default = default
        self.help = help_
        self.mutable = mutable
        self.value = default


class FlagRegistry:
    """DEFINE_*/FLAGS_* analog with optional hot changes
    (BRPC_VALIDATE_GFLAG pattern, vector_reader.cc:72)."""

    def __init__(self):
        self._flags: Dict[str, Flag] = {}
        self._lock = threading.Lock()

    def define(self, name: str, default: Any, help_: str = "",
               mutable: bool = False) -> None:
        with self._lock:
            if name not in self._flags:
                self._flags[name] = Flag(name, default, help_, mutable)

    def get(self, name: str) -> Any:
        return self._flags[name].value

    def set(self, name: str, value: Any, boot: bool = False) -> None:
        with self._lock:
            flag = self._flags[name]
            if not boot and not flag.mutable:
                raise PermissionError(f"flag {name} is not hot-changeable")
            flag.value = type(flag.default)(value) if flag.default is not None \
                else value

    def all(self) -> Dict[str, Any]:
        return {k: f.value for k, f in self._flags.items()}


FLAGS = FlagRegistry()

# reference limits (index_service.cc:50-51,206; vector_reader.cc:60-61)
FLAGS.define("vector_max_batch_count", 4096)
FLAGS.define("vector_max_request_size", 32 * 1024 * 1024)
FLAGS.define("vector_index_bruteforce_batch_count", 2048, mutable=True)
FLAGS.define("vector_max_range_search_result_count", 1024, mutable=True)
FLAGS.define("enable_async_vector_search", True, mutable=True)
FLAGS.define("search_coalescing_window_ms", 0.0, mutable=True,
             help_="merge concurrent same-shaped VectorSearch RPCs into one "
                   "device batch within this window (0 disables); fills the "
                   "MXU batch dimension instead of spending threads")
FLAGS.define("server_heartbeat_interval_s", 10, mutable=True)
FLAGS.define("raft_snapshot_threshold", 10000, mutable=True)
FLAGS.define("region_max_size_bytes", 256 * 1024 * 1024, mutable=True)
FLAGS.define("split_check_approximate_keys", 1_000_000, mutable=True)
FLAGS.define("gc_retention_ms", 3_600_000, mutable=True)
FLAGS.define("use_pallas_fused_search", "auto", mutable=True,
             help_="route flat L2/IP searches through the fused Pallas "
                   "streaming kernel (no [b,n] HBM materialization). "
                   "'auto' (default) enables it on TPU once the store is "
                   "large enough to amortize the streaming grid "
                   "(capacity >= 2048 — below that one XLA matmul wins). "
                   "True/False force; same tri-state crossover discipline "
                   "as use_pallas_ivf_search")
FLAGS.define("ivfpq_rerank_factor", 8, mutable=True,
             help_="host-vectors IVF_PQ reranks topk*factor ADC candidates "
                   "exactly from host rows (1 disables); same prune+rerank "
                   "recipe as the diskann role")
FLAGS.define("lsm_sync_writes", False, mutable=True,
             help_="fsync the native LSM WAL on every commit: power-loss "
                   "durability instead of process-crash durability. Off by "
                   "default — raft replication is the availability story "
                   "and per-commit fsync costs ~ms (rocksdb's "
                   "WriteOptions.sync analog)")
FLAGS.define("wal_checkpoint_bytes", 64 * 1024 * 1024, mutable=True,
             help_="WalEngine folds the WAL into a checkpoint once it "
                   "exceeds this size, bounding restart replay time")
FLAGS.define("diskann_server_addr", "", mutable=True,
             help_="endpoint of the --role=diskann server; required to "
                   "create VECTOR_INDEX_TYPE_DISKANN indexes")
FLAGS.define("diskann_rerank_io_rows", 8192, mutable=True,
             help_="exact-rerank disk gathers read at most this many "
                   "(sorted, deduplicated) rows per memmap access — an IO "
                   "budget so a big batch*k*rerank_factor fan-out cannot "
                   "issue one unbounded random-read burst on spinning "
                   "or network storage")
FLAGS.define("use_mesh_sharded_flat", False, mutable=True,
             help_="serve FLAT regions from a mesh-sharded index "
                   "(TpuShardedFlat): rows over the 'data' axis, feature "
                   "dim over 'dim', search fan-out/merge via XLA "
                   "collectives over ICI")
FLAGS.define("use_mesh_sharded_ivf", False, mutable=True,
             help_="serve IVF_FLAT regions from a mesh-sharded index "
                   "(TpuShardedIvfFlat): rows shard over 'data', "
                   "distributed k-means train, per-shard bucket scan + "
                   "all_gather top-k merge over ICI")
FLAGS.define("use_mesh_sharded_ivfpq", False, mutable=True,
             help_="serve IVF_PQ regions from a mesh-sharded index "
                   "(TpuShardedIvfPq): codes shard over 'data', per-shard "
                   "ADC prune + shard-local exact rerank + all_gather "
                   "top-k merge over ICI")
FLAGS.define("mesh_dim_axis", 1, mutable=True,
             help_="size of the mesh 'dim' (tensor-parallel) axis used by "
                   "mesh-sharded indexes; 'data' axis = n_devices // dim")
FLAGS.define("mesh_batch_axis", 1, mutable=True,
             help_="size of the mesh 'batch' (query data-parallel) axis: "
                   "coalesced query batches split across batch replicas "
                   "while every replica scans the full set of row shards; "
                   "vector state replicates over this axis (read scaling). "
                   "Must be a power of two so the shape-bucket ladder's "
                   "pow2 batch padding stays divisible; 1 disables")
FLAGS.define("mesh_replicas", 1, mutable=True,
             help_="replica-group fan-out for mesh-sharded regions: the "
                   "factory builds this many full index replicas on "
                   "disjoint device slices and routes searches across "
                   "them (parallel/replica_group.py); writes fan out to "
                   "every member; 1 disables")
FLAGS.define("mesh_replica_route", "rr", mutable=True,
             help_="replica-group routing policy: 'rr' (round robin) or "
                   "'load' (fewest in-flight searches)")
FLAGS.define("mesh_collective_merge", True, mutable=True,
             help_="merge per-shard shortlists ON DEVICE with an in-jit "
                   "all_gather + top_k (the ICI path). Off = the capped "
                   "fallback: each shard ships only its local [b, k] "
                   "shortlist to the host, merged there (debug/A-B arm; "
                   "never transfers full score matrices either way)")
FLAGS.define("balance_replica_mode", "off", mutable=True,
             help_="coordinator replica planning: 'off' or 'auto' (scale "
                   "a region's read-replica count from its measured QPS "
                   "via the store-metrics plane; placement picks the "
                   "least-loaded stores)")
FLAGS.define("balance_replica_qps_target", 50.0, mutable=True,
             help_="replica planning aims for at most this many QPS per "
                   "replica before adding another (auto mode)")
FLAGS.define("ivf_prune_inbucket_bound", True, mutable=True,
             help_="pruned-scan kernels refresh the k-th-best bound "
                   "BETWEEN dimension blocks inside a bucket/row-block "
                   "from the candidates' own suffix-norm lower bounds "
                   "(PDX finer-grained threshold), not only from shortlist "
                   "merges at bucket boundaries; off = PR 6 behavior")
FLAGS.define("metrics_collect_interval_s", 5.0, mutable=True,
             help_="StoreMetricsCollector crontab period; heartbeats also "
                   "refresh snapshots older than this so beats never ship "
                   "stale figures even without the crontab")
FLAGS.define("metrics_http_port", 0, mutable=False,
             help_="bind a plain-HTTP sidecar on this port serving "
                   "/metrics (Prometheus text format) and /vars (JSON); "
                   "0 disables — scrapers can't speak the grpc "
                   "DebugService.MetricsDump")
FLAGS.define("balance_mode", "count", mutable=True,
             help_="leader balancing signal: 'count' (leader tallies) or "
                   "'load' (measured per-region QPS + memory bytes from "
                   "store metrics; falls back to count while metrics are "
                   "missing or stale)")
FLAGS.define("trace_sampling_rate", 0.0, mutable=True,
             help_="fraction of ingress requests recording a full span "
                   "tree into dingo_tpu/trace (0 disables; 1 records "
                   "everything). Decided once at the trace root; children "
                   "and remote hops inherit the decision")
FLAGS.define("slow_query_ms", 500.0, mutable=True,
             help_="a sampled root span slower than this lands in the "
                   "slow-query log (retained separately from the span "
                   "ring so fast-trace churn cannot evict slow evidence)")
FLAGS.define("ivf_compact_interval_s", 60.0, mutable=True,
             help_="period of the IVF view-compaction crontab: restores "
                   "the dense bucket layout (full rebuild) off the search "
                   "path once tombstone/spill garbage accumulates")
FLAGS.define("ivf_compact_tombstone_ratio", 0.25, mutable=True,
             help_="compact an IVF view once tombstoned rows exceed this "
                   "fraction of (live + tombstoned) — dead rows still burn "
                   "scan FLOPs until compaction reclaims them")
FLAGS.define("ivf_compact_spill_ratio", 0.5, mutable=True,
             help_="compact once incremental appends allocated this many "
                   "extra spill buckets relative to the dense build — "
                   "ragged chains cost probe-expansion budget")
FLAGS.define("ivf_shape_bucketing", True, mutable=True,
             help_="round (topk, nprobe) up to the {1,1.5}x-pow2 ladder so "
                   "steady-state serving reuses a handful of compiled "
                   "programs instead of recompiling per request shape; "
                   "results are sliced back to the requested topk")
FLAGS.define("vector_precision", "fp32", mutable=True,
             help_="default precision tier for float FLAT/IVF_FLAT region "
                   "indexes when VectorIndexParameter.precision is unset: "
                   "'fp32' (exact storage+compute), 'bf16' (bf16 storage, "
                   "bf16 MXU multiplies, fp32 accumulate — 2x HBM "
                   "capacity), 'sq8' (uint8 scalar-quantized storage, "
                   "decode-on-the-fly bf16 compute, fp32 accumulate — 4x "
                   "HBM capacity). Per-index override via the parameter")
FLAGS.define("rerank_cache_rows", 0, mutable=True,
             help_="device-resident exact-rerank row cache size (rows per "
                   "bf16/sq8 index; 0 disables the cache). Cached rows "
                   "rerank quantized shortlists ON DEVICE (no host "
                   "gather); uncached candidates keep their quantized "
                   "score, so a partial cache only improves ranking")
FLAGS.define("rerank_cache_dtype", "float32", mutable=True,
             help_="dtype of the rerank row cache: 'float32' (exact "
                   "rerank) or 'bfloat16' (half the cache HBM; rerank is "
                   "then bf16-exact, still above SQ8 fidelity)")
FLAGS.define("quantized_rerank_factor", 4, mutable=True,
             help_="bf16/sq8 searches with a non-empty rerank cache scan "
                   "topk*factor candidates and rerank them exactly on "
                   "device (1 disables the stage)")
FLAGS.define("obs_flight_buffer_s", 30.0, mutable=True,
             help_="flight-recorder metrics window: bundles carry metric "
                   "deltas over the last this-many seconds of ticks (the "
                   "store-metrics crontab drives the tick ring)")
FLAGS.define("obs_flight_max_bundles", 16, mutable=True,
             help_="flight-recorder retention: newest N compressed "
                   "bundles kept in memory (0 disables capturing)")
FLAGS.define("obs_exemplars", True, mutable=True,
             help_="attach trace-id exemplars to latency-series outliers "
                   "in the Prometheus exposition (OpenMetrics syntax) so "
                   "a scrape links a bad bucket to its trace/flight "
                   "bundle")
FLAGS.define("hbm_watermark_interval_s", 10.0, mutable=True,
             help_="period of the process HBM watermark poll (allocator "
                   "bytes-in-use/limit/peak -> hbm.* gauges); per-region "
                   "owner ledgers additionally refresh with every "
                   "store-metrics collection pass")
FLAGS.define("use_pallas_ivf_search", "auto", mutable=True,
             help_="route trained IVF_FLAT searches through the Pallas "
                   "list-DMA kernel (streams only probed buckets to VMEM; "
                   "no per-rank [b,cap,d] gather materialization). 'auto' "
                   "(default) enables it on TPU when dimension >= 256: "
                   "measured on-chip r3 at 1Mx768/nlist=1024/b=64 the "
                   "kernel is 4.9x the XLA path (33 vs 163 ms/batch), but "
                   "at 100Kx128/nlist=64 it LOSES 1.3x (18 vs 14) — thin "
                   "rows starve the per-bucket DMA. True/False force.")
FLAGS.define("ivf_dim_block", 128, mutable=True,
             help_="dimension-block width of the PDX-style vertical scan "
                   "layout (per-block partial distances let the pruning "
                   "kernels stop scanning candidates that cannot beat the "
                   "running k-th best). 128 = one TPU lane tile; an index "
                   "only builds blocked metadata when its (padded) "
                   "dimension is a multiple with >= 2 blocks")
FLAGS.define("ivf_prune_check_interval", 1, mutable=True,
             help_="pruned-scan kernels re-evaluate the partial-distance "
                   "bound every N dimension blocks (1 = every block). "
                   "Larger values trade pruning opportunity for less VPU "
                   "compare/mask overhead per block")
FLAGS.define("ivf_prune_scan", "auto", mutable=True,
             help_="use the early-pruning dimension-blocked scan kernels "
                   "wherever the Pallas path is active and the index has "
                   "blocked metadata. 'auto' (default) = on (the kernels "
                   "fall back to the plain fused scan when the dimension "
                   "doesn't block); False forces the non-pruning kernels")
FLAGS.define("hnsw_max_iters", 48, mutable=True,
             help_="hard cap on lockstep beam-expansion rounds of the "
                   "device HNSW walk (one round = expand every beam entry "
                   "one hop). The walk exits earlier once every query's "
                   "beam has converged; the cap bounds worst-case latency "
                   "on adversarial graphs")
FLAGS.define("hnsw_build_batch", 256, mutable=True,
             help_="rows per device bulk-build insert batch (rounded up "
                   "to a power of two; the final partial batch pads with "
                   "dropped lanes). Larger batches amortize more MXU "
                   "work per dispatch but discover neighbors against a "
                   "staler partial graph")
FLAGS.define("hnsw_build_alpha", 1.0, mutable=True,
             help_="occlusion-pruning diversification factor of the "
                   "device bulk build (DiskANN's alpha): a candidate is "
                   "pruned once it scores closer to an already-kept "
                   "neighbor than to the inserted point, with the kept "
                   "score scaled by alpha^2. >1 keeps longer edges "
                   "(denser graph, better recall on clustered data)")
FLAGS.define("train_sample_rows", 65536, mutable=True,
             help_="train-sample row cap shared by every k-means/PQ "
                   "train path (IVF coarse quantizer, PQ codebooks, the "
                   "sharded plane's seeding sample). Trainers gather at "
                   "most this many stored rows — on device when the rows "
                   "live there, so only the sample (or just centroids) "
                   "ever crosses to the host. 0 = full corpus: every "
                   "live row feeds training and derived caps "
                   "(max_points_per_centroid * nlist) are lifted too")
FLAGS.define("quality_sample_rate", 0.0, mutable=True,
             help_="fraction of live searches re-answered EXACTLY by the "
                   "shadow scan and scored for recall/RBO/score-gap "
                   "(obs/quality.py). Head-sampled like tracing: 0 "
                   "(default) is a zero-alloc noop — no shadow kernels, "
                   "no mirrors, no estimator state; 1 scores every batch "
                   "(bench/tests). Scoring runs on an async lane off the "
                   "request's critical path")
FLAGS.define("quality_slo_recall", 0.95, mutable=True,
             help_="recall@k service-level objective the quality plane "
                   "reports against and the SLO tuner steers toward: the "
                   "tuner tightens knobs while the live estimate's CI "
                   "upper bound sits below this, relaxes when the lower "
                   "bound clears it with margin")
FLAGS.define("quality_window_s", 60.0, mutable=True,
             help_="sliding window of the live quality estimators: "
                   "samples older than this age out of the recall "
                   "estimate/CI (longer = tighter CI, slower reaction)")
FLAGS.define("tuner_enabled", False, mutable=True,
             help_="run the closed-loop SLO parameter controller "
                   "(obs/tuner.py) on the store crontab: one "
                   "cheap-to-expensive ladder step per tick per region, "
                   "driven by the live recall CI vs quality.slo_recall. "
                   "Requires quality.sample_rate > 0 to have a sensor")
FLAGS.define("tuner_interval_s", 30.0, mutable=True,
             help_="period of the quality_tuner crontab (one knob step "
                   "at most per region per tick; the estimator window "
                   "reset after each step is the hysteresis)")
FLAGS.define("tuner_latency_budget_ms", 0.0, mutable=True,
             help_="vector_search p99 budget the tuner respects: it "
                   "never tightens past it, and relaxes while over it "
                   "(if recall allows). 0 = no latency constraint")
FLAGS.define("qos_enabled", False, mutable=True,
             help_="traffic-shaped serving (obs/pressure.py + the QoS "
                   "coalescer): deadline-aware admission, priority batch "
                   "forming, expiry of dead requests before dispatch, and "
                   "graduated shed/degrade under pressure. Off = observe "
                   "nothing, act on nothing (zero-alloc like tracing); "
                   "deadline METADATA still propagates either way so a "
                   "mid-upgrade fleet keeps the chain")
FLAGS.define("qos_default_deadline_ms", 0.0, mutable=True,
             help_="deadline granted to requests arriving WITHOUT an "
                   "x-dingo-deadline-ms header while qos.enabled (0 = no "
                   "implied deadline: headerless requests are never "
                   "expired or deadline-shed)")
FLAGS.define("qos_tenant_header", "x-dingo-tenant", mutable=True,
             help_="gRPC metadata key carrying the tenant id for "
                   "per-tenant demand accounting and admission "
                   "(deployments can point this at an existing auth "
                   "header)")
FLAGS.define("qos_max_queue_ms", 50.0, mutable=True,
             help_="queue-wait bound the QoS layer defends: admission "
                   "sheds low-priority work once the estimated wait "
                   "exceeds it (priority >= 2 is exempt) and the shed "
                   "controller escalates the degrade ladder while the "
                   "recent queue-wait watermark sits above it")
FLAGS.define("qos_shed_policy", "degrade_drop", mutable=True,
             help_="pressure response: 'off' (observe only), 'degrade' "
                   "(knob ladder only: drop rerank -> lower nprobe/ef -> "
                   "advisory sq8), 'drop' (admission shed only), "
                   "'degrade_drop' (both, default)")
FLAGS.define("qos_tenant_queue_rows", 0, mutable=True,
             help_="per-tenant cap on queued query rows inside the "
                   "coalescer (admission sheds the excess with "
                   "reason=tenant_limit); 0 = unlimited")
FLAGS.define("qos_shed_interval_s", 2.0, mutable=True,
             help_="period of the qos_shed crontab driving the graduated "
                   "degrade ladder (one level per tick each way)")
FLAGS.define("integrity_enabled", True, mutable=True,
             help_="maintain incremental per-artifact state digests "
                   "(obs/integrity.py): every index write folds its batch "
                   "into an order-invariant set digest per artifact (rows, "
                   "sq8 codes, blocked mirror, HNSW adjacency, IVF bucket "
                   "assignment) with O(batch) host work; digests ride "
                   "heartbeats for replica divergence detection and gate "
                   "snapshot restores. Off = no ledgers, no scrub, no "
                   "restore verification")
FLAGS.define("integrity_scrub_interval_s", 60.0, mutable=True,
             help_="period of the consistency_scrub crontab: recompute "
                   "full digests from device state (chunked under "
                   "store.device_lock) and check them against the "
                   "incremental ledger — catches silent HBM/restore "
                   "corruption AND ledger bookkeeping bugs")
FLAGS.define("integrity_flight_on_divergence", True, mutable=True,
             help_="capture a flight-recorder bundle (rate-limited per "
                   "reason) when the scrub finds a corrupted artifact or "
                   "the coordinator sees replicas diverge at equal "
                   "applied indices; the bundle carries the digest "
                   "vectors of both sides")
FLAGS.define("retry_rounds", 3, mutable=True,
             help_="full target-rotation rounds the client RetryPolicy "
                   "makes before giving up (each round tries every "
                   "non-breaker-open target once)")
FLAGS.define("retry_base_backoff_ms", 25.0, mutable=True,
             help_="base of the equal-jitter backoff between rotation "
                   "rounds: sleep ~ d/2 + U(0, d/2) where "
                   "d = min(cap, base*2^round) — the d/2 floor guarantees "
                   "an election-scale wait actually happens while the "
                   "jitter half spreads the herd; always clamped to the "
                   "request's remaining deadline budget")
FLAGS.define("retry_max_backoff_ms", 1000.0, mutable=True,
             help_="cap of the equal-jitter backoff between rounds")
FLAGS.define("retry_breaker_threshold", 5, mutable=True,
             help_="consecutive connection-level failures that open a "
                   "target's circuit breaker (in-band responses — even "
                   "NotLeader — count as success: the endpoint is alive)")
FLAGS.define("retry_breaker_cooldown_s", 5.0, mutable=True,
             help_="how long an open breaker skips its target before "
                   "admitting one half-open probe")
FLAGS.define("retry_hedge_enabled", False, mutable=True,
             help_="hedged reads: fire a second VectorSearch attempt at "
                   "the next replica when the primary hasn't answered "
                   "within its p99-derived delay; first success wins. "
                   "Idempotent reads only, budget-gated, attempts "
                   "stamped with x-dingo-attempt")
FLAGS.define("retry_hedge_min_delay_ms", 5.0, mutable=True,
             help_="floor of the hedge delay (covers the cold start "
                   "before enough latency samples exist for a p99)")
FLAGS.define("device_recovery_enabled", True, mutable=True,
             help_="graduated HBM OOM recovery ladder (index/recovery.py): "
                   "on an OOM during device write/search, drop rerank "
                   "caches, evict blocked/adjacency mirrors, retry once; "
                   "if still OOM, mark the region device-degraded (served "
                   "by the host exact path) and schedule background "
                   "re-materialization at lower precision. Off = OOMs "
                   "propagate raw")
FLAGS.define("device_recovery_remat_precision", "sq8", mutable=True,
             help_="precision tier the background re-materialization "
                   "rebuilds a device-degraded region at (advisory-lower "
                   "than the configured tier; the region definition keeps "
                   "its declared precision)")
FLAGS.define("pipeline_enabled", "auto", mutable=True,
             help_="stall-free serving pipeline: the coalescer flush "
                   "thread dispatches every due batch's kernels before any "
                   "resolve runs, resolves drain on a completion lane, and "
                   "query staging double-buffers H2D uploads. 'auto' = "
                   "TPU-only (on CPU the backend is synchronous so overlap "
                   "buys nothing and the extra thread hop costs latency). "
                   "True/False force; same tri-state crossover discipline "
                   "as use_pallas_ivf_search")
FLAGS.define("pipeline_depth", 2, mutable=True,
             help_="staging-ring depth per coalescer key (pow2-ladder "
                   "shaped host buffers): batch N+1's query upload can "
                   "overlap batch N's compute up to this many batches in "
                   "flight. 1 degenerates to the serial path (staging "
                   "still used, no overlap); 2 is classic double "
                   "buffering")
FLAGS.define("cache_enabled", False, mutable=True,
             help_="serving-edge result cache + in-flight query dedupe "
                   "(dingo_tpu/cache/): identical query rows inside one "
                   "coalescer flush window collapse to a single kernel "
                   "row, and exact repeats of plain searches are answered "
                   "from a bounded per-region result cache keyed on "
                   "(query fingerprint, SlotStore.mutation_version, "
                   "resolved params) — a hit costs no queue slot and "
                   "dispatches no kernel")
FLAGS.define("cache_max_bytes", 64 * 1024 * 1024, mutable=True,
             help_="LRU bound on the result cache's host memory across "
                   "all regions (approximate accounting: cached rows are "
                   "(id, distance) pairs). 0 disables caching while "
                   "leaving in-flight dedupe active")
FLAGS.define("cache_stale_versions", 1, mutable=True,
             help_="serve-slightly-stale degrade rung: while a region's "
                   "shed ladder is degraded (qos.degrade_level > 0) a "
                   "lookup may fall back to entries at most this many "
                   "mutation_versions behind the live store. 0 = exact "
                   "version only, always")
FLAGS.define("cache_semantic", False, mutable=True,
             help_="semantic (approximate) cache hits via sq8-quantized "
                   "query fingerprints: near-identical queries that "
                   "quantize to the same codes share a cache entry. "
                   "Gated live by the shadow-quality estimator — "
                   "approximate hits serve only while the windowed "
                   "recall CI lower bound holds quality.slo_recall")
FLAGS.define("cache_tenant_share", 0.5, mutable=True,
             help_="per-tenant fairness bound: the fraction of "
                   "cache.max_bytes any single tenant's entries may "
                   "occupy (its own inserts evict its own LRU tail past "
                   "the share). <= 0 or >= 1 disables the bound")
FLAGS.define("heat_enabled", False, mutable=True,
             help_="workload-heat plane (obs/heat.py): per-region "
                   "exponential-decay access sketches fed from data the "
                   "resolve paths already hold on host (probed IVF "
                   "buckets, FLAT/HNSW result slot ranges) — zero new "
                   "device syncs — plus the derived working-set "
                   "estimator. Off = observe nothing, allocate nothing "
                   "(the quality-plane sampling discipline)")
FLAGS.define("heat_decay_s", 300.0, mutable=True,
             help_="e-folding time constant of the heat sketches: a "
                   "unit untouched for this long keeps 1/e of its mass. "
                   "~5 min tracks traffic shifts faster than the "
                   "coordinator acts on them while riding out "
                   "second-scale burstiness")
FLAGS.define("heat_max_entries", 4096, mutable=True,
             help_="bound on live sketch entries per region: past it the "
                   "coldest units are evicted (their mass is the least "
                   "informative). Memory per region stays O(max_entries)")
FLAGS.define("cost_enabled", True, mutable=True,
             help_="per-(kernel, padded-shape-ladder-point) dispatch "
                   "cost model (obs/cost.py) learned from the completion "
                   "lane's stage timings; consulted by QoS "
                   "estimated_wait_ms and the SLO tuner's latency "
                   "budget. Off = the coalescer falls back to its single "
                   "scalar per-row EWMA")
FLAGS.define("cost_prior_row_ms", 0.5, mutable=True,
             help_="conservative per-row service-time prior the wait "
                   "estimator sheds on before the first measured sample "
                   "lands — the first overload burst must not ride in on "
                   "a 0ms estimate (pessimistic on purpose: over-shedding "
                   "a cold store beats serving it into collapse)")
FLAGS.define("capacity_advise", True, mutable=True,
             help_="coordinator capacity plane: roll per-store HBM "
                   "headroom vs heartbeat working-set demand and emit "
                   "ADVISORY-ONLY tier/split recommendations "
                   "(capacity.* metrics, cluster capacity table). Never "
                   "actuates — tiering and split are roadmap items 1-2")
FLAGS.define("capacity_headroom_target", 0.2, mutable=True,
             help_="fraction of a store's HBM the capacity plane wants "
                   "free: below it the coldest region (most resident "
                   "bytes outside its working set) draws a demote "
                   "advisory")
FLAGS.define("tier_enabled", False, mutable=True,
             help_="memory-tier ladder (index/tiering.py): a store-local "
                   "policy loop demotes cold regions along HBM-fp32/bf16 "
                   "-> HBM-sq8 -> host-RAM sq8 -> mmap'd sq8 codes and "
                   "promotes them back on re-warm, every transition "
                   "digest-gated against the state-integrity ledger. "
                   "Policy inputs are the existing planes: capacity "
                   "demote advisories, heat working-set bytes vs HBM "
                   "headroom, windowed search QPS. Off = regions stay at "
                   "their declared tier (today's behavior)")
FLAGS.define("tier_demote_headroom", 0.15, mutable=True,
             help_="free-HBM fraction below which the tier loop demotes "
                   "the coldest resident region one rung (a tighter "
                   "store-local tripwire under the capacity plane's "
                   "capacity_headroom_target advisory threshold, so "
                   "actuation fires before the allocator does)")
FLAGS.define("tier_promote_qps", 5.0, mutable=True,
             help_="sustained windowed vector-search QPS above which a "
                   "demoted region promotes one rung back toward its "
                   "declared tier (given HBM headroom to fit it); the "
                   "same metrics-plane window the shed controller reads")
FLAGS.define("tier_mmap_dir", "", mutable=True,
             help_="directory for the mmap rung's code files (one "
                   "region_<id>.codes per demoted region); empty = a "
                   "per-process temp directory. Local SSD recommended — "
                   "the paged exact scan's latency is this device's "
                   "read bandwidth")
FLAGS.define("tier_interval_s", 30.0, mutable=True,
             help_="tier policy tick cadence (server crontab): each tick "
                   "applies at most one transition per store — demotions "
                   "and promotions are full-region copies, so pacing them "
                   "keeps the build/copy bandwidth bounded")
FLAGS.define("events_enabled", True, mutable=True,
             help_="control-plane flight recorder (obs/events.py): every "
                   "controller actuation — tuner step, shed ladder move, "
                   "tier transition, recovery rung, replica scale, "
                   "capacity advisory, cache stale rung — records a "
                   "structured event with the evidence it decided on. "
                   "Events ride heartbeats to the coordinator for the "
                   "cluster timeline and `cluster explain`. Off = emit "
                   "is one flag read, nothing is allocated or shipped")
FLAGS.define("events_max_entries", 1024, mutable=True,
             help_="bound on the per-node event ring AND the "
                   "coordinator's merged timeline: past it the oldest "
                   "events fall off (never-shipped ones count into "
                   "event.dropped). Controller decisions are crontab-"
                   "paced, so 1024 covers hours of history")
FLAGS.define("events_heartbeat_batch", 128, mutable=True,
             help_="max events one heartbeat carries to the coordinator "
                   "(each ships exactly once — the collector keeps a "
                   "harvest cursor). 0 keeps the ledger node-local "
                   "(EventDump/flight bundles still see it)")
FLAGS.define("vector_blocked_layout", "auto", mutable=True,
             help_="maintain a dimension-blocked ([n_blocks, capacity, "
                   "block_d]) scan mirror + per-block norms in float/sq8 "
                   "SlotStores so FLAT searches can run the pruned "
                   "streaming kernel. 'auto' = on-TPU only (the mirror "
                   "costs one extra copy of the rows in HBM; on CPU "
                   "nothing reads it unless forced). True/False force")


def bf16_compute_native() -> bool:
    """True where bf16 is native matmul currency (TPU MXU) and the bf16
    tier should SCAN bf16-resident data directly. XLA CPU converts bf16
    scalar-ly (~500M elt/s measured on this image — a [64,512,256] rank
    gather pays ~17 ms of convert alone), so the CPU arm keeps the bf16
    tier's SCAN arrays f32: rows still quantize to bf16 at the write
    boundary (identical recall semantics), only the resident compute copy
    widens. Same backend-crossover discipline as use_pallas_ivf_search."""
    return _on_tpu()


def _parse_tri(flag) -> Optional[bool]:
    """Parse a tri-state backend-crossover flag: None = 'auto' (caller
    applies its measured crossover), True/False force. FLAGS.set coerces
    to the default's type (str), so boolean sets arrive as 'True'/'False'
    strings — parse, don't truth-test."""
    if isinstance(flag, str):
        low = flag.strip().lower()
        if low == "auto":
            return None
        return low in ("true", "1", "on", "yes")
    return bool(flag)


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Interpret-mode choice of the Pallas wrappers: Mosaic compiles for
    the TPU only, so the kernels run compiled there and interpreted on
    the CPU (tests). Any other backend is an error, not a default."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the JAX backend is {backend!r}"
    )


def require_device() -> Dict[str, Any]:
    """Initialise the JAX backend of a process that owns the device (the
    store role, chip_smoke.py's kernel phase) and refuse anything but a
    TPU: with JAX_PLATFORMS unset, jax falls back to the
    CPU when the TPU cannot be initialised, and every 'auto' crossover
    would then quietly resolve to its CPU arm. The only way onto the CPU
    is an explicit JAX_PLATFORMS=cpu in the environment (what the tests
    set). Exits the process otherwise; returns the device as jax reports
    it."""
    import jax

    wanted = os.environ.get("JAX_PLATFORMS", "")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(
            f"no usable JAX backend (JAX_PLATFORMS={wanted!r}): {e}"
        ) from e
    platform = devices[0].platform
    if platform != "tpu" and wanted.strip().lower() != "cpu":
        raise SystemExit(
            f"JAX backend is {platform!r}, not 'tpu' "
            f"(JAX_PLATFORMS={wanted!r}): this process serves from the "
            "chip; set JAX_PLATFORMS=cpu explicitly to run it on the CPU"
        )
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def grpc_options() -> list:
    """Message-size options for every gRPC server and channel: what
    vector_max_request_size admits the transport admits (gRPC's own
    default is 4 MiB — a 4096 x 768 fp32 VectorAdd is 12.6 MB)."""
    limit = int(FLAGS.get("vector_max_request_size"))
    return [
        ("grpc.max_send_message_length", limit),
        ("grpc.max_receive_message_length", limit),
    ]


def pallas_ivf_enabled(dimension: int) -> bool:
    """Resolve the tri-state use_pallas_ivf_search flag for an index."""
    v = _parse_tri(FLAGS.get("use_pallas_ivf_search"))
    if v is None:
        return _on_tpu() and dimension >= 256
    return v


def pallas_fused_enabled(capacity: int) -> bool:
    """Tri-state use_pallas_fused_search crossover for FLAT searches:
    'auto' routes to the streaming kernel on TPU once the store is big
    enough (capacity >= 2048) that avoiding the [b, capacity] HBM score
    matrix beats one fused XLA matmul+top_k; True/False force."""
    v = _parse_tri(FLAGS.get("use_pallas_fused_search"))
    if v is None:
        return _on_tpu() and capacity >= 2048
    return v


def prune_scan_enabled() -> bool:
    """Tri-state ivf_prune_scan: 'auto' = on (the pruned kernels are only
    reachable where the Pallas crossover already fired AND the index has
    blocked metadata, so there is no separate hardware condition)."""
    v = _parse_tri(FLAGS.get("ivf_prune_scan"))
    return True if v is None else v


def train_sample_rows() -> int:
    """Row cap shared by every train path (conf train.sample_rows,
    floor 0). 0 = full corpus: trainers feed every live row and lift
    their derived caps (an explicit opt-in — full-corpus Lloyd over a
    blocked device layout is exactly what the chunked kmeans_fit scan
    compiles to one program for)."""
    try:
        return max(0, int(FLAGS.get("train_sample_rows")))
    except (TypeError, ValueError):
        return 65536


def serving_pipeline_enabled() -> bool:
    """Tri-state pipeline.enabled: 'auto' keeps the overlapped-dispatch
    serving pipeline TPU-only (CPU XLA executes synchronously inside
    dispatch, so there is nothing to overlap — the completion-lane hop
    would only add latency). True/False force."""
    v = _parse_tri(FLAGS.get("pipeline_enabled"))
    if v is None:
        return _on_tpu()
    return v


def pipeline_depth() -> int:
    """Staging-ring depth for the serving pipeline (floor 1)."""
    try:
        return max(1, int(FLAGS.get("pipeline_depth")))
    except (TypeError, ValueError):
        return 2


def result_cache_enabled() -> bool:
    """Whole-subsystem gate for the serving-edge cache (dedupe + result
    cache). One boolean read — with the flag off every hook is a cheap
    early return, mirroring qos_enabled()."""
    v = FLAGS.get("cache_enabled")
    if isinstance(v, str):
        return v.strip().lower() in ("true", "1", "on", "yes")
    return bool(v)


def blocked_layout_enabled() -> bool:
    """Tri-state vector_blocked_layout: 'auto' keeps the blocked FLAT scan
    mirror TPU-only (it duplicates the rows in device memory; the CPU arm
    never routes to the kernel that reads it unless forced)."""
    v = _parse_tri(FLAGS.get("vector_blocked_layout"))
    if v is None:
        return _on_tpu()
    return v


def auto_arms() -> Dict[str, bool]:
    """Every tri-state backend crossover as it resolves in this process
    for an index past the size thresholds (dimension >= 256, capacity >=
    2048). The store publishes these at start (device.auto_arm gauges),
    so what 'auto' meant on a given machine is read, not assumed."""
    return {
        "use_pallas_fused_search": pallas_fused_enabled(2048),
        "use_pallas_ivf_search": pallas_ivf_enabled(256),
        "ivf_prune_scan": prune_scan_enabled(),
        "pipeline_enabled": serving_pipeline_enabled(),
        "vector_blocked_layout": blocked_layout_enabled(),
    }


class Config:
    """Per-role config (ConfigManager + YamlConfig analog)."""

    def __init__(self, values: Optional[Dict[str, Any]] = None):
        self._values = dict(values or {})

    @classmethod
    def load(cls, path: str) -> "Config":
        with open(path) as f:
            text = f.read()
        if path.endswith(".json"):
            return cls(_flatten(json.loads(text)))
        values: Dict[str, Any] = {}
        for line in text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" not in line:
                continue
            key, _, raw = line.partition("=")
            values[key.strip()] = _parse_scalar(raw.strip())
        return cls(values)

    def get(self, key: str, default: Any = _UNSET) -> Any:
        if key in self._values:
            return self._values[key]
        if default is _UNSET:
            raise KeyError(key)
        return default

    def get_int(self, key: str, default: int = 0) -> int:
        return int(self.get(key, default))

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self.get(key, default)
        if isinstance(v, str):
            return v.lower() in ("1", "true", "yes", "on")
        return bool(v)

    def apply_flag_overrides(self, flags: FlagRegistry = FLAGS) -> int:
        """Boot-time yaml-overrides-gflags behavior (server.cc:500-512)."""
        n = 0
        for key, value in self._values.items():
            name = key.replace(".", "_")
            if name in flags._flags:
                flags.set(name, value, boot=True)
                n += 1
        return n


def _parse_scalar(raw: str) -> Any:
    for caster in (int, float):
        try:
            return caster(raw)
        except ValueError:
            pass
    if raw.lower() in ("true", "false"):
        return raw.lower() == "true"
    return raw.strip("\"'")


def _flatten(obj: Dict, prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in obj.items():
        key = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            out.update(_flatten(v, key))
        else:
            out[key] = v
    return out


def compile_cache_dir() -> str:
    """Directory of the persistent XLA compilation cache: where
    JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache — a fixed
    path, because the path is part of the cache key."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        ".jax_cache",
    )


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before first use of jax
    (store role, bench, chip_smoke's kernel phase); returns the directory
    in use. Where JAX_COMPILATION_CACHE_DIR is set jax reads it itself
    and no directory is set in code."""
    cache_dir = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
