"""TpuDiskann: proxy index delegating to the DiskANN server role.

Reference: VectorIndexDiskANN (src/vector/vector_index_diskann.h:24,173)
holds a brpc::Channel to the separate diskann server and forwards
Build/Load/Search (SendRequest :125); the INDEX role treats it like any
other VectorIndex while storage lives remotely. Same shape here over
grpc: upsert pushes rows, build/load drive the remote lifecycle, search
fans one RPC out.

DiskANN semantics differ from in-memory types (the reference's too):
mutations only land before build (push phase); deletes are unsupported;
searches require the remote index LOADED.
"""

from __future__ import annotations

from typing import List, Optional

import grpc
import numpy as np

from dingo_tpu.common.config import FLAGS, grpc_options
from dingo_tpu.index.base import (
    FilterSpec,
    IndexParameter,
    NotSupported,
    SearchResult,
    VectorIndex,
    VectorIndexError,
)
from dingo_tpu.server import convert, pb
from dingo_tpu.server.rpc import ServiceStub


class TpuDiskann(VectorIndex):
    def __init__(self, index_id: int, parameter: IndexParameter,
                 server_addr: Optional[str] = None):
        super().__init__(index_id, parameter)
        if server_addr is None:
            server_addr = FLAGS.get("diskann_server_addr")
        if not server_addr:
            raise VectorIndexError(
                "DISKANN needs FLAGS.diskann_server_addr (the --role=diskann "
                "server endpoint)"
            )
        self.addr = server_addr
        self._channel = grpc.insecure_channel(
            server_addr, options=grpc_options())
        self.stub = ServiceStub(self._channel, "DiskAnnService")
        resp = self.stub.DiskAnnNew(pb.DiskAnnNewRequest(
            vector_index_id=index_id,
            parameter=convert.index_parameter_to_pb(parameter),
        ))
        # "exists" is fine: reconnecting to our own remote state
        if resp.error.errcode and "exists" not in resp.error.errmsg:
            raise VectorIndexError(resp.error.errmsg)

    def _check(self, resp):
        if resp.error.errcode:
            raise VectorIndexError(resp.error.errmsg)
        return resp

    # -- lifecycle over RPC --------------------------------------------------
    def upsert(self, ids: np.ndarray, vectors: np.ndarray,
               has_more: bool = True) -> None:
        req = pb.DiskAnnPushDataRequest(
            vector_index_id=self.id, has_more=has_more,
        )
        req.vector_ids.extend(int(i) for i in ids)
        for row in np.asarray(vectors, np.float32):
            req.vectors.add().values.extend(row.tolist())
        self._check(self.stub.DiskAnnPushData(req))
        self.write_count_since_save += len(ids)

    def add(self, ids: np.ndarray, vectors: np.ndarray) -> None:
        self.upsert(ids, vectors)

    def delete(self, ids: np.ndarray) -> None:
        raise NotSupported("DISKANN does not support delete")

    def build(self, sync: bool = True) -> str:
        resp = self._check(self.stub.DiskAnnBuild(pb.DiskAnnBuildRequest(
            vector_index_id=self.id, sync=sync,
        )))
        return resp.state

    def load_remote(self, try_load: bool = False) -> str:
        resp = self._check(self.stub.DiskAnnLoad(pb.DiskAnnLoadRequest(
            vector_index_id=self.id, try_load=try_load,
        )))
        return resp.state

    def remote_status(self):
        return self._check(self.stub.DiskAnnStatus(
            pb.DiskAnnStatusRequest(vector_index_id=self.id)
        ))

    def search(
        self,
        queries: np.ndarray,
        topk: int,
        filter_spec: Optional[FilterSpec] = None,
        nprobe: Optional[int] = None,
        **kw,
    ) -> List[SearchResult]:
        if filter_spec is not None and not filter_spec.is_empty():
            # reference DiskANN path has no filter support either; reader
            # falls back to brute-force for filtered queries
            raise NotSupported("DISKANN search does not support filters")
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        req = pb.DiskAnnSearchRequest(
            vector_index_id=self.id, top_n=int(topk), nprobe=int(nprobe or 0),
        )
        for row in queries:
            req.vectors.add().values.extend(row.tolist())
        resp = self._check(self.stub.DiskAnnSearch(req))
        out = []
        for r in resp.batch_results:
            ids = np.asarray([i.vector.id for i in r.results], np.int64)
            dists = np.asarray([i.distance for i in r.results], np.float32)
            out.append(SearchResult(ids, dists))
        return out

    def search_async(self, queries, topk, filter_spec=None, **kw):
        res = self.search(queries, topk, filter_spec, **kw)
        return lambda: res

    # -- contract ------------------------------------------------------------
    def need_train(self) -> bool:
        return True

    def is_trained(self) -> bool:
        return self.remote_status().state in ("built", "loaded")

    def get_count(self) -> int:
        return int(self._check(self.stub.DiskAnnCount(
            pb.DiskAnnCountRequest(vector_index_id=self.id)
        )).count)

    def get_memory_size(self) -> int:
        # codes live remotely; the proxy holds nothing
        return 0

    def save(self, path: str) -> None:
        # remote state IS disk-resident; nothing to snapshot locally
        return

    def load(self, path: str) -> None:
        self.load_remote(try_load=True)

    def close(self) -> None:
        self._channel.close()
