"""metric-names: registration-site lint for metric and span names.

Why a lint and not a runtime assert: Prometheus exposition mangles dots
to underscores; a name that's already shaped like an identifier survives
mangling losslessly, and series can't silently collide or drop after the
rename. Dynamic names (f-strings like ``span.{name}``) can't be checked
statically — their static prefix is validated and the runtime mangler
keeps the rest legal — but every literal registration must pass here.

Also linted:
- span names (``TRACER.start_span("...")`` literals): every span name
  feeds a ``span.<name>`` latency series through the tracer bridge, so
  it must survive the same mangling. Span segments may be CamelCase
  (service/method names: ``rpc.DebugService.MetricsDump``), but the name
  must start lowercase and stay inside the identifier-plus-dots alphabet.
- curated metric families: literal registrations under the prefixes in
  FAMILY_NAMES (the device-runtime observability, mesh serving, device
  graph, quality, serving-pressure, and state-integrity planes) must
  name a declared series — dashboards key on these exact names, so
  additions are explicit, not incidental.

History: started life as the standalone ``tools/check_metrics_names.py``
(PR 2), grew the curated families over PRs 5-11, and was folded into the
dingolint framework as its sixth checker in PR 12. The standalone CLI
survives as a thin shim over this module so existing wiring keeps
working.
"""

from __future__ import annotations

import ast
import re
from typing import List, Tuple

from tools.dingolint.core import Checker, Finding, Module, Repo

#: the registration methods on MetricsRegistry
_METHODS = {"counter", "gauge", "latency"}
#: span-minting methods on Tracer (names bridge to `span.<name>` series)
_SPAN_METHODS = {"start_span", "start_child", "start_background"}

#: full-name rule (common/metrics.py METRIC_NAME_RE)
NAME_RE = re.compile(r"^[a-z][a-z0-9_.]*$")
#: rule for the static prefix of an f-string name: same alphabet, and it
#: must not end an identifier segment mid-word ambiguity — a trailing
#: '.'/'_' separator or a clean segment both pass
PREFIX_RE = re.compile(r"^[a-z][a-z0-9_.]*$")
#: span names may carry CamelCase segments (gRPC service/method names)
#: but start lowercase and stay mangle-safe
SPAN_NAME_RE = re.compile(r"^[a-z][a-zA-Z0-9_.]*$")

#: curated families: every literal registration under these prefixes must
#: be one of the declared series (labels ride separately). Extend the set
#: when adding a series — that's the point.
FAMILY_NAMES = {
    "xla": {
        "xla.recompiles",           # jit-cache misses, process total
        "xla.recompiles_by_kernel",  # breakdown (kernel label)
        "xla.cache_hits",           # per-kernel jit-cache hits
        "xla.compile_ms",           # last compile wall-time per kernel
        "xla.compile_ms_total",     # cumulative compile stall
    },
    "hbm": {
        "hbm.bytes_in_use",         # process allocator gauges
        "hbm.bytes_limit",
        "hbm.peak_bytes",
        "hbm.region.bytes",         # per-(region, owner) ledger
        "hbm.region.peak_bytes",
        "hbm.region.total_bytes",   # region totals (distinct names so
        "hbm.region.total_peak_bytes",  # sum() can't double-count)
        "hbm.alloc_failures",
    },
    "device": {
        "device.count",          # devices of the serving process, by
                                 # {platform, kind} as jax reports them
        "device.auto_arm",       # 1/0 per tri-state 'auto' crossover as
                                 # resolved at start, by {flag}
        "device.bytes_in_use",   # allocator bytes per device, by {device}
    },
    "flight": {
        "flight.bundles",        # captured bundles by reason
        "flight.suppressed",     # rate-limited triggers by reason
    },
    "mesh": {
        "mesh.searches",            # collective-merge searches per region
        "mesh.merge_bytes",         # shortlist bytes the all_gather moved
        "mesh.fallback_searches",   # non-collective (host-merge) arm uses
        "mesh.shard_rows",          # per-shard live rows (shard label)
        "mesh.shard_skew",          # max/mean live-row ratio per region
        "mesh.replicas",            # replica-group member count
        "mesh.replica.searches",    # routed searches (replica label)
        "mesh.replica.inflight",    # concurrent searches per replica
        "mesh.replica.search_ms",   # per-replica latency (carries the
                                    # windowed QPS the planner reads)
    },
    "hnsw": {
        "hnsw.device_searches",     # graph-walk searches (PR 8)
        # registered at 0 and never incremented since PR 33 (no native
        # graph): benchmark/metrics/hnsw_*.json read them, and the
        # `benchmark` issue that retires those metrics takes these three
        "hnsw.host_searches",
        "hnsw.adjacency_rebuilds",
        "hnsw.native_adds",
        "hnsw.graph_nodes",         # rows in the adjacency
        "hnsw.mean_hops",           # beam-expansion rounds per walk
        "hnsw.visited_fraction",    # visited-bitmask population / LIVE
                                    # rows
        "hnsw.gathered_rows_per_query",  # rounds x candidate slots a
                                    # round: rows the walk gathers
        "hnsw.beam_occupancy",      # live result-beam entries / beam width
        "hnsw.filter_mask_hits",    # (fingerprint, store version) cache
        "hnsw.filter_mask_misses",
    },
    "ivf": {
        "ivf.inplace_appends",      # view maintenance (PR 3)
        "ivf.tombstones",
        "ivf.compactions",
        "ivf.full_rebuild",
        "ivf.tombstone_ratio",
        "ivf.filter_mask_hits",     # filter-mask cache
        "ivf.filter_mask_misses",
        "ivf.pruned_dim_fraction",  # early-pruning scan: fraction of
                                    # (candidate, dim-block) work skipped
        "ivf.pruned_candidates",    # candidates dropped before their
                                    # last dimension block
        "ivf.probed_rows_per_query",  # rows in the probed buckets, mean
                                    # per query of a sampled batch (gauge)
        "ivf.scan_arm",             # searches by the scan's loop order,
                                    # {arm}: batch | query | xla
        "ivf.batch_scan_buckets",   # buckets the last batch-major scan
                                    # read, each once (gauge)
    },
    "filter": {
        # scalar filters (index/columns.py, index/vector_reader.py)
        "filter.matched_fraction",  # matching live slots over live slots
                                    # of the last predicate mask (gauge)
        "filter.post_rows",         # rows the host post-filter looked up
                                    # (fields the region does not mirror)
    },
    "ivfpq": {
        "ivfpq.probed_codes_per_query",  # live codes in the probed
                                    # buckets, mean per query of a sampled
                                    # batch (gauge)
        "ivfpq.scan_arm",           # searches by the arm that served,
                                    # {arm}: pallas | xla | exact
    },
    "background": {
        "background.busy_ms",       # ms inside background spans (crontab
                                    # jobs, checkpoints, saves, rebuilds,
                                    # full GCs, compiles), by {job}
    },
    "gc": {
        "gc.pause_ms",              # ms inside full collections, by {gen}
    },
    "trace": {
        "trace.spans_recorded",     # spans handed to the trace buffer
        "trace.spans_dropped",      # ring overwrites (oldest span lost)
    },
    "qos": {
        # serving-pressure plane (obs/pressure.py + common/coalescer.py):
        # admission / queue lifecycle
        "qos.admitted",             # requests admitted to the queue
        "qos.demand_rows",          # query rows submitted, by
                                    # {tenant, priority}
        "qos.queue_depth",          # live queued rows (gauge, by
                                    # region + tenant + priority)
        "qos.queue_wait",           # queue-wait latency recorder (us)
        "qos.queue_wait_watermark_ms",  # recent rolling-window max the
                                    # heartbeat rollup ships
        "qos.stage_budget_pct",     # per-stage deadline share (percent,
                                    # stage label: queue / batch_form /
                                    # kernel / rerank)
        # outcomes: throughput vs goodput
        "qos.served",               # every reply
        "qos.served_in_deadline",   # goodput: replies inside their budget
        "qos.deadline_exceeded",    # served but late (flight-bundled)
        "qos.expired",              # dead on arrival / died in queue,
                                    # by {where}
        "qos.shed",                 # admission drops, by {reason}
        # graduated degrade ladder (ShedController)
        "qos.degrade_level",        # current level per region (0-3)
        "qos.degrade_steps",        # ladder moves, by {direction}
        "qos.precision_advisory",   # level-3 sq8 advisory flag per region
    },
    "consistency": {
        # state-integrity plane (obs/integrity.py + coordinator compare):
        # incremental digest maintenance, the corruption scrub, restore
        # verification, and replica divergence
        "consistency.digest_updates",    # write batches folded into a
                                         # ledger (counter, per region)
        "consistency.scrub_runs",        # full-state recompute passes
        "consistency.scrub_slots",       # slots read back and verified
        "consistency.scrub_ms",          # scrub pass latency recorder
        "consistency.scrub_ok",          # per-region verdict gauge (1 ok)
        "consistency.scrub_mismatches",  # device state != ledger, by
                                         # {artifact}
        "consistency.restore_mismatches",  # snapshot load digest veto
        "consistency.divergence",        # coordinator: replicas disagree
                                         # at equal applied indices
        "consistency.diverged_regions",  # currently-flagged region count
        "consistency.replica_mismatch",  # ReplicaGroup post-fanout
                                         # member comparison failed
        "consistency.digest_age_s",      # seconds since the last clean
                                         # full-state verification
    },
    "quality": {
        # live recall observability (obs/quality.py): windowed shadow-
        # scan estimates per region (rollup) and per (kind, precision,
        # bucket) split — labels ride separately
        "quality.recall",           # windowed recall@k estimate
        "quality.recall_ci_low",    # Wilson 95% CI bounds
        "quality.recall_ci_high",
        "quality.rbo",              # rank-biased overlap (order-aware)
        "quality.score_gap_p50",    # relative k-th-best regret quantiles
        "quality.score_gap_p99",
        "quality.samples",          # scored queries (counter)
        "quality.shadow_scans",     # exact shadow kernels dispatched
        "quality.dropped",          # async-lane overflow drops
        "quality.window_queries",   # queries inside the current window
        # SLO tuner (obs/tuner.py)
        "quality.tuner_steps",      # knob steps by {knob, direction}
        "quality.tuner_blocked",    # tighten wanted but latency-blocked
        "quality.tuner_nprobe",     # current tuned serving defaults
        "quality.tuner_ef",
        "quality.tuner_rerank_factor",
        "quality.tuner_precision_target",  # advisory tier (ladder index)
    },
    "cache": {
        # serving-edge result cache + in-flight dedupe (dingo_tpu/cache/)
        "cache.hits",               # replies served from the cache
        "cache.misses",             # rows that fell through every tier
        "cache.dedup_collapsed",    # duplicate in-flight rows merged out
                                    # of kernel batches
        "cache.stale_served",       # hits served from a bounded-stale
                                    # version (degrade-rung only)
        "cache.semantic_served",    # sq8-fingerprint approximate hits
                                    # (SLO-gated)
        "cache.evictions",          # LRU/tenant-fairness evictions
        "cache.bytes",              # store-wide resident bytes (gauge)
        "cache.entries",            # live entries per region (gauge)
    },
    "heat": {
        # workload-heat plane (obs/heat.py): per-region exponential-
        # decay access sketches fed from resolve-path host data
        "heat.touches",             # folded unit touches (counter)
        "heat.bucket_gini",         # traffic-mass Gini over heat units
        "heat.hot_fraction",        # mass on the hottest 10% of units
        "heat.entries",             # live sketch entries (bounded gauge)
        "heat.working_set_bytes",   # bytes to serve {pct}% of traffic,
                                    # by {pct, tier} (what-if tiers too)
        "heat.dropped",             # async-lane overflow drops
    },
    "cost": {
        # per-(kernel, padded-shape) dispatch cost model (obs/cost.py)
        "cost.run_ms",              # EWMA run time per ladder point,
                                    # by {kernel, rows}
        "cost.row_us",              # EWMA per-row cost, by {kernel}
        "cost.samples",             # completion-lane timings folded
    },
    "tier": {
        # memory-tier ladder (index/tiering.py): policy-driven rung
        # moves along HBM -> HBM-sq8 -> host-RAM-sq8 -> mmap-sq8
        "tier.current",             # region's serving rung (gauge,
                                    # ladder index 0-3)
        "tier.demotions",           # completed down-moves, by {to} rung
        "tier.promotions",          # completed up-moves, by {to} rung
        "tier.digest_refusals",     # destination copies vetoed by the
                                    # rows-digest gate before the swap
        "tier.advisories",          # coordinator TIER_DEMOTE commands
                                    # acknowledged per region
        "tier.transition_ms",       # rung-move wall-time recorder (us)
        "tier.mmap_bytes",          # rung-3 on-disk code bytes (gauge)
    },
    "capacity": {
        # coordinator capacity plane (coordinator/capacity.py +
        # control._update_capacity) — demote advisories actuate through
        # the TIER_DEMOTE handshake when tier.enabled (index/tiering.py);
        # the series themselves stay observational
        "capacity.headroom_bytes",  # HBM limit - in-use, by {store}
        "capacity.headroom_fraction",
        "capacity.demand_p99_bytes",  # sum of regions' p99 working sets
        "capacity.resident_bytes",  # sum of regions' device residency
        "capacity.advice_count",    # live advisories per store (gauge)
        "capacity.advisories",      # NEW advisories seen (counter, by
                                    # region + {kind}: demote / split)
    },
    "fault": {
        # fault-domain hardening (PR 14): injection planes, the client
        # resilience policy, and the device-failure recovery ladder
        "fault.injected",           # fired injections, by {point}
                                    # (failpoints + the device-fault shim)
        "fault.transport_faults",   # raft transport faults, by {kind}:
                                    # drop / delay / duplicate / partition
        "fault.retries",            # RetryPolicy re-attempts, by {target}
        "fault.hedges",             # hedged duplicates sent, by {target}
        "fault.hedge_wins",         # hedge answered before the primary
        "fault.breaker_opens",      # circuit transitions to open, by
                                    # {target}
        "fault.budget_exhausted",   # deadline budget died mid-retry-loop
        "fault.cmd_retry_exhausted",  # coordinator command dropped after
                                    # its poison-retry budget
        "fault.oom_recoveries",     # recovery-ladder outcomes, by {rung}:
                                    # drop_rerank / evict_mirrors /
                                    # retry / degrade
        "fault.degraded_regions",   # regions currently device-degraded
        "fault.host_exact_searches",  # searches served by the numpy scan
                                    # of the engine (degraded region)
        "fault.bruteforce_searches",  # searches served by the temp-flat
                                    # scan (index not ready / untrained /
                                    # unsupported)
        "fault.rematerializations",  # degraded regions rebuilt (lower
                                    # precision) from the engine
        "fault.rebuilds",           # scrub-corruption rebuilds from the
                                    # engine
        "fault.recovery_ms",        # ladder wall-time recorder (us)
    },
    "build": {
        # device-side bulk index construction (ISSUE 18):
        # ops/graph_build.py + index/hnsw.py bulk session + manager arm
        "build.rows",               # rows fed through insert_batch
        "build.batches",            # insert_batch dispatches
        "build.reverse_dropped",    # degree-clamped reverse edges dropped
                                    # (device fold, read once at finish)
        "build.device_builds",      # completed bulk sessions per region
        "build.train_failures",     # manager train() raised; untrained
                                    # fallback installed (was silent)
        "build.remat_rebuilds",     # PR 13 re-materializations riding
                                    # the streaming bulk-build arm
    },
    "event": {
        # control-plane flight recorder (obs/events.py): the decision
        # event ledger + the coordinator's merged cluster timeline
        "event.emitted",            # decisions recorded, by {actor}
        "event.dropped",            # unharvested ring-overflow losses
        "event.heartbeat_bytes",    # estimated bytes the last beat's
                                    # event batch added (gauge)
        "event.orphan_knobs",       # live overrides `cluster explain`
                                    # could NOT account for (gauge, per
                                    # region — nonzero = ledger gap)
    },
}


def _name_arg(call: ast.Call):
    """First positional arg or name= kwarg of a registration call."""
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg == "name":
            return kw.value
    return None


def check_tree(tree: ast.AST) -> List[Tuple[int, str]]:
    """All metric/span-name problems in one parsed module."""
    problems: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        if func.attr in _METHODS:
            # only registry-shaped receivers: METRICS.counter(...),
            # m.gauge(...), registry.latency(...) — skip unrelated
            # .counter() methods by requiring a string-ish name argument
            arg = _name_arg(node)
            if arg is None:
                continue
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                name = arg.value
                if not NAME_RE.match(name):
                    problems.append((
                        node.lineno,
                        f"metric name {name!r} is not a lowercase dotted "
                        "identifier",
                    ))
                else:
                    family = name.split(".", 1)[0]
                    known = FAMILY_NAMES.get(family)
                    if known is not None and name not in known:
                        problems.append((
                            node.lineno,
                            f"metric {name!r} is not a declared member of "
                            f"the {family}.* family (extend FAMILY_NAMES "
                            "in tools/dingolint/checkers/metric_names.py)",
                        ))
            elif isinstance(arg, ast.JoinedStr):
                # f-string: validate the leading literal fragment
                if arg.values and isinstance(arg.values[0], ast.Constant):
                    prefix = str(arg.values[0].value)
                    if prefix and not PREFIX_RE.match(prefix.rstrip("._")):
                        problems.append((
                            node.lineno,
                            f"dynamic metric name prefix {prefix!r} is not "
                            "a lowercase dotted identifier",
                        ))
        elif func.attr in _SPAN_METHODS:
            arg = _name_arg(node)
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if not SPAN_NAME_RE.match(arg.value):
                    problems.append((
                        node.lineno,
                        f"span name {arg.value!r} must start lowercase and "
                        "use only [a-zA-Z0-9_.] (it feeds the span.<name> "
                        "metric series)",
                    ))
            elif isinstance(arg, ast.JoinedStr):
                if arg.values and isinstance(arg.values[0], ast.Constant):
                    prefix = str(arg.values[0].value)
                    if prefix and not SPAN_NAME_RE.match(
                            prefix.rstrip("._")):
                        problems.append((
                            node.lineno,
                            f"dynamic span name prefix {prefix!r} must "
                            "start lowercase and use only [a-zA-Z0-9_.]",
                        ))
    return problems


def check_file(path: str) -> List[Tuple[int, str]]:
    """Standalone-CLI compatibility surface (the shim + its tests)."""
    with open(path) as f:
        try:
            tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            return [(e.lineno or 0, f"syntax error: {e.msg}")]
    return check_tree(tree)


class MetricNamesChecker(Checker):
    name = "metric-names"
    description = ("metric/span name literals must be mangle-safe and "
                   "curated families must declare every series")

    def check_module(self, module: Module, repo: Repo) -> List[Finding]:
        out: List[Finding] = []
        for lineno, msg in check_tree(module.tree):
            if module.suppressed(lineno, self.name):
                continue
            # recover the enclosing symbol for a stable fingerprint
            symbol = ""
            for node in ast.walk(module.tree):
                if getattr(node, "lineno", None) == lineno and isinstance(
                        node, ast.Call):
                    symbol = module.qualname_of(node)
                    break
            out.append(Finding(self.name, module.rel, lineno, symbol, msg))
        return out
