"""Spec-driven gRPC glue test: real server + stub over localhost."""

import numpy as np

from elasticdl_tpu.common import rpc, tensor_utils
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb


class _EchoPserver:
    """Minimal servicer implementing the Pserver spec for glue testing."""

    def __init__(self):
        self.version = 0

    def push_model(self, request, context):
        self.version = request.version
        return pb.Empty()

    def push_embedding_table_infos(self, request, context):
        return pb.Empty()

    def pull_embedding_table(self, request, context):
        return pb.IndexedSlices()

    def pull_dense_parameters(self, request, context):
        return pb.PullDenseParametersResponse(
            initialized=True,
            version=self.version,
            dense_parameters=[
                tensor_utils.ndarray_to_tensor_pb(
                    np.arange(6, dtype=np.float32).reshape(2, 3), "w"
                )
            ],
        )

    def pull_embedding_vectors(self, request, context):
        return tensor_utils.ndarray_to_tensor_pb(
            np.tile(np.asarray(request.ids, np.float32)[:, None], (1, 4))
        )

    def push_gradients(self, request, context):
        return pb.PushGradientsResponse(accepted=True, version=self.version + 1)

    # The packed transport shares push_gradients' response.
    push_gradients_packed = push_gradients


def test_stub_server_roundtrip():
    servicer = _EchoPserver()
    server, port = rpc.serve(servicer, rpc.PSERVER_SERVICE, port=0)
    try:
        stub = rpc.Stub(
            rpc.build_channel(f"localhost:{port}"), rpc.PSERVER_SERVICE
        )
        stub.push_model(pb.Model(version=7))
        assert servicer.version == 7

        resp = stub.pull_dense_parameters(pb.PullDenseParametersRequest())
        assert resp.initialized and resp.version == 7
        arr = tensor_utils.tensor_pb_to_ndarray(resp.dense_parameters[0])
        assert arr.shape == (2, 3)

        vec = stub.pull_embedding_vectors(
            pb.PullEmbeddingVectorsRequest(name="e", ids=[2, 9])
        )
        np.testing.assert_allclose(
            tensor_utils.tensor_pb_to_ndarray(vec)[:, 0], [2.0, 9.0]
        )

        push = stub.push_gradients(pb.PushGradientsRequest())
        assert push.accepted and push.version == 8
    finally:
        server.stop(0)
