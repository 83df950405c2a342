"""DiskAnnService: the --role=diskann server's RPC surface.

Reference: DiskAnnServiceHandle (diskann_service_handle.h:29-62) —
VectorNew/PushData/Build/Load/TryLoad/Search/Reset/Close/Destroy/Status/
Count over brpc, registered by main.cc:1340 for the diskann role.
"""

from __future__ import annotations

import numpy as np

from dingo_tpu.diskann.core import CoreState, DiskAnnError
from dingo_tpu.diskann.item import DiskAnnItemManager
from dingo_tpu.index.base import InvalidParameter
from dingo_tpu.server import convert, pb
from dingo_tpu.server.services import _err


class DiskAnnService:
    def __init__(self, manager: DiskAnnItemManager):
        self.manager = manager

    def _core_or_err(self, index_id, resp):
        core = self.manager.get(index_id)
        if core is None:
            _err(resp, 50001, f"diskann index {index_id} not found")
            return None
        return core

    def DiskAnnNew(self, req: pb.DiskAnnNewRequest):
        resp = pb.DiskAnnNewResponse()
        param = convert.index_parameter_from_pb(req.parameter)
        if param is None:
            return _err(resp, 50002, "missing index parameter")
        try:
            self.manager.create(req.vector_index_id, param)
        except (DiskAnnError, InvalidParameter) as e:
            return _err(resp, 50002, str(e))
        return resp

    def DiskAnnPushData(self, req: pb.DiskAnnPushDataRequest):
        resp = pb.DiskAnnPushDataResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        try:
            vectors = convert.float_rows_from_pb(req.vectors)
            resp.already_recv_vector_count = core.push_data(
                np.asarray(list(req.vector_ids), np.int64),
                vectors, req.has_more,
            )
        except (DiskAnnError, InvalidParameter, ValueError) as e:
            return _err(resp, 50003, str(e))
        return resp

    def DiskAnnBuild(self, req: pb.DiskAnnBuildRequest):
        resp = pb.DiskAnnBuildResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        try:
            if req.sync:
                core.build()
            else:
                self.manager.submit_build(req.vector_index_id)
        except (DiskAnnError, InvalidParameter) as e:
            return _err(resp, 50004, str(e))
        resp.state = core.status().value
        return resp

    def DiskAnnLoad(self, req: pb.DiskAnnLoadRequest):
        resp = pb.DiskAnnLoadResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        try:
            if req.try_load:
                core.try_load()
            else:
                core.load()
        except (DiskAnnError, InvalidParameter) as e:
            return _err(resp, 50005, str(e))
        resp.state = core.status().value
        return resp

    def DiskAnnSearch(self, req: pb.DiskAnnSearchRequest):
        resp = pb.DiskAnnSearchResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        try:
            queries = convert.float_rows_from_pb(req.vectors)
            rows = core.search(queries, int(req.top_n or 10),
                               nprobe=int(req.nprobe) or None)
        except (DiskAnnError, InvalidParameter, ValueError) as e:
            return _err(resp, 50006, str(e))
        for ids, dists in rows:
            r = resp.batch_results.add()
            for vid, dist in zip(ids, dists):
                item = r.results.add()
                item.vector.id = int(vid)
                item.distance = float(dist)
        return resp

    def DiskAnnStatus(self, req: pb.DiskAnnStatusRequest):
        resp = pb.DiskAnnStatusResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        resp.state = core.status().value
        resp.last_error = core.last_error
        resp.count = core.count
        return resp

    def DiskAnnCount(self, req: pb.DiskAnnCountRequest):
        resp = pb.DiskAnnCountResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        resp.count = core.count
        return resp

    def DiskAnnReset(self, req: pb.DiskAnnResetRequest):
        resp = pb.DiskAnnResetResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        core.reset(delete_data_file=req.delete_data_file)
        return resp

    def DiskAnnClose(self, req: pb.DiskAnnCloseRequest):
        resp = pb.DiskAnnCloseResponse()
        core = self._core_or_err(req.vector_index_id, resp)
        if core is None:
            return resp
        core.close()
        return resp

    def DiskAnnDestroy(self, req: pb.DiskAnnDestroyRequest):
        resp = pb.DiskAnnDestroyResponse()
        if self.manager.get(req.vector_index_id) is None:
            return _err(resp, 50001,
                        f"diskann index {req.vector_index_id} not found")
        self.manager.destroy(req.vector_index_id)
        return resp
