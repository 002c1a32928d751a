"""Inference result wrapper for the gRPC client (reference grpc/_client.py
InferResult), numpy/BF16/BYTES aware."""

import numpy as np

from tritonclient.utils import (
    deserialize_bf16_tensor,
    deserialize_bytes_tensor,
    triton_to_np_dtype,
)

from . import grpc_service_pb2 as pb


class InferResult:
    """Wraps a ModelInferResponse and exposes numpy access to outputs."""

    def __init__(self, result):
        self._result = result

    @classmethod
    def from_response(cls, response):
        return cls(response)

    def as_numpy(self, name):
        """The output tensor as a numpy array, or None if not present (e.g.
        delivered via shared memory)."""
        index = 0
        for output in self._result.outputs:
            if output.name == name:
                shape = list(output.shape)
                if "shared_memory_region" in output.parameters:
                    # delivered via shared memory: read it from the region
                    return None
                if index < len(self._result.raw_output_contents):
                    raw = self._result.raw_output_contents[index]
                    if output.datatype == "BYTES":
                        return deserialize_bytes_tensor(raw).reshape(shape)
                    if output.datatype == "BF16":
                        return deserialize_bf16_tensor(raw).reshape(shape)
                    np_dtype = triton_to_np_dtype(output.datatype)
                    return np.frombuffer(raw, dtype=np_dtype).reshape(shape)
                # typed contents fallback
                c = output.contents
                for field in (
                    "bool_contents", "int_contents", "int64_contents",
                    "uint_contents", "uint64_contents", "fp32_contents",
                    "fp64_contents", "bytes_contents",
                ):
                    vals = getattr(c, field)
                    if len(vals):
                        if field == "bytes_contents":
                            return np.array(
                                list(vals), dtype=np.object_
                            ).reshape(shape)
                        np_dtype = triton_to_np_dtype(output.datatype)
                        return np.array(vals, dtype=np_dtype).reshape(shape)
                return None
            index += 1
        return None

    def get_output(self, name, as_json=False):
        """The InferOutputTensor protobuf (or dict) for ``name``."""
        for output in self._result.outputs:
            if output.name == name:
                if as_json:
                    from google.protobuf import json_format

                    return json_format.MessageToDict(
                        output, preserving_proto_field_name=True
                    )
                return output
        return None

    def get_response(self, as_json=False):
        if as_json:
            from google.protobuf import json_format

            return json_format.MessageToDict(
                self._result, preserving_proto_field_name=True
            )
        return self._result


#: Response parameter of a streamed response that carries several tokens
#: of one generation (the client asked for such responses): how many.
#: Each output's first axis is then a token, and ``seq`` the first's.
TOKEN_COUNT_PARAM = "token_count"


def token_results(response):
    """One result a token of ``response``: the response itself where it
    carries one, else a :class:`_TokenResult` for each of its
    ``token_count`` tokens, all read from ONE parse of the response."""
    count = response.parameters.get(TOKEN_COUNT_PARAM)
    if count is None:
        return [InferResult(response)]
    whole = InferResult(response)
    arrays = {o.name: whole.as_numpy(o.name) for o in response.outputs}
    seq = response.parameters["seq"].int64_param
    return [_TokenResult(response, arrays, n, seq + n)
            for n in range(count.int64_param)]


class _TokenResult(InferResult):
    """One token of a response that carries several: each output's
    entry at the token's place along the first axis, and the token's own
    ``seq``, so its reader sees what a one-token response gives.  The
    token's own ``ModelInferResponse`` is built only when asked for
    (numeric outputs: a token response carries ``TOKEN`` / ``LOGPROB``)."""

    def __init__(self, response, arrays, index, seq):
        super().__init__(response)
        self._arrays = arrays   # output name -> the response's whole array
        self._index = index
        self._seq = seq
        self._own = None

    def as_numpy(self, name):
        array = self._arrays.get(name)
        return None if array is None else array[self._index:self._index + 1]

    def get_output(self, name, as_json=False):
        return InferResult(self._own_response()).get_output(name, as_json)

    def get_response(self, as_json=False):
        return InferResult(self._own_response()).get_response(as_json)

    def _own_response(self):
        if self._own is None:
            whole = self._result
            own = pb.ModelInferResponse(model_name=whole.model_name,
                                        model_version=whole.model_version,
                                        id=whole.id)
            for key, value in whole.parameters.items():
                if key != TOKEN_COUNT_PARAM:
                    own.parameters[key].CopyFrom(value)
            own.parameters["seq"].int64_param = self._seq
            for output in whole.outputs:
                tensor = own.outputs.add()
                tensor.CopyFrom(output)
                part = self.as_numpy(output.name)
                tensor.shape[:] = part.shape
                own.raw_output_contents.append(part.tobytes())
            self._own = own
        return self._own
