"""VectorIndex abstract API + filter model.

Mirrors the reference's abstract index surface (src/vector/vector_index.h:148-229:
Add/Upsert/Delete/Search/RangeSearch/Train/Save/Load/GetCount/GetMemorySize/
NeedToRebuild/NeedToSave) and its FilterFunctor family (vector_index.h:67-146:
RangeFilterFunctor, ConcreteFilterFunctor over faiss::IDSelectorBatch,
SortFilterFunctor).

TPU-first re-design of filtering: the reference's FilterFunctor is an arbitrary
host callback invoked per candidate inside faiss/hnswlib; under XLA that would
be a host round-trip per candidate. Instead every filter mode is *compiled* to
a per-slot validity bitmap on device (FilterSpec.slot_mask): id-range filters
become vectorized compares on the resident id array, id-set filters become a
sorted-array membership test (searchsorted). The bitmap composes with the
tombstone/validity mask and feeds the masked top-k kernel (ops/topk.py).

The reference's *ByParallel ThreadPool sharding (vector_index.h:157-196) has no
analog here: one batched device program already uses the whole chip.
"""

from __future__ import annotations

import abc
import dataclasses
import enum
from typing import List, Optional, Sequence, Tuple

import numpy as np

from dingo_tpu.ops.distance import Metric


class IndexType(enum.Enum):
    """pb::common::VectorIndexType equivalents."""

    FLAT = "flat"
    IVF_FLAT = "ivf_flat"
    IVF_PQ = "ivf_pq"
    HNSW = "hnsw"
    DISKANN = "diskann"
    BRUTEFORCE = "bruteforce"
    BINARY_FLAT = "binary_flat"
    BINARY_IVF_FLAT = "binary_ivf_flat"


class VectorIndexError(Exception):
    """Base error; carries an errno-style code matching pb::error::Errno."""


class NotSupported(VectorIndexError):
    """EVECTOR_NOT_SUPPORT: the reader falls back to brute-force scan
    (reference vector_reader.cc:1814-1833 contract for untrained IVF /
    BRUTEFORCE index types)."""


class NotTrained(VectorIndexError):
    """EVECTOR_INDEX_NOT_TRAIN."""


class InvalidParameter(VectorIndexError):
    """EILLEGAL_PARAMTETERS [sic — reference spells it this way]."""


class SnapshotCorruption(VectorIndexError):
    """A restored snapshot's recomputed state digests diverge from the
    digest vector persisted in its meta.json (obs/integrity.py): the
    files were corrupted at rest or the restore itself mangled data.
    load() raises it BEFORE the index can serve; the manager's
    load-or-build path treats any load failure as 'rebuild from the
    engine', which is exactly the right recovery."""


@dataclasses.dataclass(frozen=True)
class IndexParameter:
    """Union of pb::common::VectorIndexParameter fields we support.

    Defaults follow the reference's conf templates and faiss defaults."""

    index_type: IndexType = IndexType.FLAT
    dimension: int = 0
    metric: Metric = Metric.L2
    # IVF_FLAT / IVF_PQ (vector_index_ivf_flat.h, vector_index_ivf_pq.h)
    ncentroids: int = 2048
    nsubvector: int = 64          # PQ m
    nbits_per_idx: int = 8        # PQ nbits (ksub = 2**nbits)
    default_nprobe: int = 80
    # HNSW (vector_index_hnsw.cc:154-181)
    # rows the region is created for: the slot store and the device
    # adjacency are sized for them at creation (0 = grow by pow2 steps)
    max_elements: int = 0
    efconstruction: int = 200
    nlinks: int = 32              # M
    # storage dtype for device-resident vectors
    dtype: str = "float32"
    # precision tier for float FLAT/IVF_FLAT storage+compute: "" (defer to
    # the vector.precision conf default), "fp32", "bf16" (bf16 storage,
    # fp32 accumulate), or "sq8" (uint8 scalar-quantized storage with
    # device-resident exact rerank). See resolve_precision().
    precision: str = ""
    # keep full vectors in HOST memory (IVF_PQ/DiskANN-class indexes whose
    # search path reads only codes; lifts the HBM cap at 10M x 768 scale)
    host_vectors: bool = False
    # scalar fields flagged for pre-filter acceleration: apply writes a
    # NARROW scalar subset to the vector_scalar_key_speed_up CF so scalar
    # pre-filter scans read it instead of the full scalar CF (reference
    # ScalarSchema.enable_speed_up + VectorIndexUtils::SplitVectorScalarData,
    # raft_apply_handler.cc:1115)
    scalar_speedup_keys: Tuple[str, ...] = ()


#: canonical precision tier names (ARCHITECTURE.md "Precision tiers")
PRECISION_TIERS = ("fp32", "bf16", "sq8")

_PRECISION_ALIASES = {
    "": "fp32", "fp32": "fp32", "f32": "fp32", "float32": "fp32",
    "bf16": "bf16", "bfloat16": "bf16",
    "sq8": "sq8", "int8": "sq8", "uint8": "sq8",
}


def resolve_precision(parameter: IndexParameter) -> str:
    """Effective precision tier for an index: the per-index parameter wins,
    else the `vector.precision` conf default. A legacy parameter that sets
    dtype='bfloat16' directly (pre-tier configs, bench rounds 1-5) resolves
    to the bf16 tier so its behavior is unchanged."""
    p = (parameter.precision or "").strip().lower()
    if not p:
        from dingo_tpu.common.config import FLAGS

        try:
            p = str(FLAGS.get("vector_precision")).strip().lower()
        except KeyError:  # registry not populated (unit contexts)
            p = "fp32"
    tier = _PRECISION_ALIASES.get(p)
    if tier is None:
        raise InvalidParameter(f"unknown precision tier {p!r} "
                               f"(want one of {PRECISION_TIERS})")
    if tier == "fp32" and parameter.dtype in ("bfloat16", "bf16"):
        return "bf16"
    return tier


@dataclasses.dataclass
class FilterSpec:
    """Compiled filter: the TPU equivalent of VectorIndex::FilterFunctor.

    ranges      — list of [lo, hi) id intervals, OR'd (RangeFilterFunctor,
                  vector_index.h:75-84 — used for region split child ranges).
    include_ids — explicit candidate whitelist (ConcreteFilterFunctor /
                  SortFilterFunctor — scalar pre-filter candidates,
                  vector_reader.cc:853).
    exclude_ids — blacklist (IDSelectorNot semantics).
    """

    ranges: Optional[Sequence[Tuple[int, int]]] = None
    include_ids: Optional[np.ndarray] = None
    exclude_ids: Optional[np.ndarray] = None

    def is_empty(self) -> bool:
        return (
            not self.ranges
            and self.include_ids is None
            and self.exclude_ids is None
        )

    def fingerprint(self) -> bytes:
        """Stable content digest — the cache key for compiled per-slot
        masks (the IVF filter-mask cache keys on (fingerprint, view
        version) so a repeated filter skips the numpy mask build + H2D).
        Hashing beats keeping the arrays: an include set can be 100k ids
        and the key must be cheap to compare."""
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        for lo, hi in self.ranges or ():
            h.update(int(lo).to_bytes(8, "little", signed=True))
            h.update(int(hi).to_bytes(8, "little", signed=True))
        for tag, ids in ((b"i", self.include_ids), (b"x", self.exclude_ids)):
            if ids is not None:
                h.update(tag)
                h.update(np.ascontiguousarray(
                    np.asarray(ids, np.int64)
                ).tobytes())
        return h.digest()

    def slot_mask(self, ids_by_slot: np.ndarray) -> np.ndarray:
        """Compile this filter against the HOST id-by-slot array
        [capacity] int64 (-1 = empty slot) -> bool mask [capacity].

        Runs in numpy: 64-bit ids stay off-device (JAX x64-off truncates
        int64), and a [capacity] bool upload per filtered search is cheap."""
        mask = ids_by_slot >= 0
        if self.ranges:
            rmask = np.zeros_like(mask)
            for lo, hi in self.ranges:
                rmask |= (ids_by_slot >= lo) & (ids_by_slot < hi)
            mask &= rmask
        if self.include_ids is not None:
            mask &= np.isin(ids_by_slot, np.asarray(self.include_ids, np.int64))
        if self.exclude_ids is not None and len(self.exclude_ids):
            mask &= ~np.isin(ids_by_slot, np.asarray(self.exclude_ids, np.int64))
        return mask


@dataclasses.dataclass
class SearchResult:
    """Per-query result (pb::index::VectorWithDistanceResult equivalent).

    distances follow the wire convention: L2/hamming ascending,
    IP/cosine descending."""

    ids: np.ndarray        # [k'] int64, no -1 entries
    distances: np.ndarray  # [k'] float32


def strip_invalid(ids: np.ndarray, distances: np.ndarray) -> SearchResult:
    """Drop -1 (masked/padding) entries — the reference returns fewer than
    topN results when the region has fewer candidates."""
    keep = ids >= 0
    return SearchResult(ids=ids[keep], distances=distances[keep])


class VectorIndex(abc.ABC):
    """Abstract ANN index owned per region (vector_index.h:54:
    region_id == vector_index_id)."""

    def __init__(self, index_id: int, parameter: IndexParameter):
        self.id = index_id
        self.parameter = parameter
        self.apply_log_id: int = 0     # wrapper consistency contract (§3.2)
        self.snapshot_log_id: int = 0
        self.write_count_since_save: int = 0
        #: per-region serving-default overrides written by the SLO tuner
        #: (obs/tuner.py): {"nprobe"|"ef"|"rerank_factor": int}. Search
        #: paths consult these via tuned() when the REQUEST didn't pin the
        #: parameter — a client-chosen nprobe/ef always wins. Values are
        #: shape-ladder members, so overrides never mint new programs.
        self.tuning: dict = {}

    def tuned(self, knob: str, fallback: int) -> int:
        """Effective serving default for `knob`: the tuner's override when
        set, else the configured fallback."""
        v = self.tuning.get(knob)
        return int(v) if v else int(fallback)

    # -- metadata ----------------------------------------------------------
    @property
    def dimension(self) -> int:
        return self.parameter.dimension

    @property
    def metric(self) -> Metric:
        return self.parameter.metric

    @property
    def index_type(self) -> IndexType:
        return self.parameter.index_type

    # -- mutation (vector_index.h:148-165) ---------------------------------
    @abc.abstractmethod
    def add(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        """Insert; error on duplicate id (faiss IndexIDMap2 add semantics)."""

    @abc.abstractmethod
    def upsert(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        """Insert-or-replace."""

    @abc.abstractmethod
    def delete(self, ids: np.ndarray) -> None:
        """Remove ids (missing ids are ignored, matching reference logs)."""

    # -- queries (vector_index.h:166-199) ----------------------------------
    @abc.abstractmethod
    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
    ) -> List[SearchResult]:
        ...

    def range_search(
        self,
        queries: np.ndarray,
        radius: float,
        filter_spec: Optional[FilterSpec] = None,
        limit: int = 1024,
    ) -> List[SearchResult]:
        """Results within radius, capped at `limit` per query
        (FLAGS_vector_max_range_search_result_count=1024,
        vector_reader.cc:60). Default: top-limit search + host radius cut."""
        results = self.search(queries, limit, filter_spec)
        out = []
        for r in results:
            if self.metric in (Metric.L2, Metric.HAMMING):
                keep = r.distances <= radius
            else:
                keep = r.distances >= radius
            out.append(SearchResult(r.ids[keep], r.distances[keep]))
        return out

    # -- training (vector_index.h:200-207) ---------------------------------
    def need_train(self) -> bool:
        return False

    def is_trained(self) -> bool:
        return True

    def train(self, vectors: np.ndarray) -> None:  # noqa: B027
        """No-op for non-trainable index types."""

    # -- lifecycle ---------------------------------------------------------
    @abc.abstractmethod
    def save(self, path: str) -> None:
        ...

    @abc.abstractmethod
    def load(self, path: str) -> None:
        ...

    @abc.abstractmethod
    def get_count(self) -> int:
        ...

    def get_deleted_count(self) -> int:
        return 0

    @abc.abstractmethod
    def get_memory_size(self) -> int:
        ...

    def get_device_memory_size(self) -> int:
        """Live device (HBM) bytes attributable to this index: distinct
        jax.Arrays reachable from it (slot-store vecs/sqnorm, centroids,
        PQ codes, ...). Host-only indexes (HNSW graph, numpy stores)
        report 0 — get_memory_size() covers host bytes."""
        from dingo_tpu.metrics.device import live_device_bytes

        return live_device_bytes(self)

    def need_to_rebuild(self) -> bool:
        """Reference default: false; HNSW overrides (deleted > total/2 —
        vector_index_hnsw.cc:577-589; note getCurrentElementCount counts
        tombstones, so the trigger is half of TOTAL, not half of live)."""
        return False

    def need_to_save(self, last_save_log_behind: int) -> bool:
        """Wrapper save policy by write count / log lag
        (vector_index.h:201, wrapper thresholds :497-500)."""
        return False
