"""gRPC frontend: serves the KServe-v2 GRPCInferenceService (including
decoupled bidirectional streaming and the XLA shared-memory verbs) on a
``grpc.server``, delegating to ``tpuserver.core.InferenceServer``.

The service layer is a generic-handler table over the vendored pb2 messages
(tritonclient/grpc/_service.py) — same wire protocol as the reference's
generated stubs.
"""

import time
from concurrent import futures

import numpy as np

import grpc

from tpuserver._trace import span
from tpuserver.core import (
    MULTI_TOKEN_PARAM,
    InferRequest,
    RequestedOutput,
    ServerError,
    SERVER_EXTENSIONS,
    SERVER_NAME,
    SERVER_VERSION,
    merge_responses,
)
from tritonclient.grpc import grpc_service_pb2 as pb
from tritonclient.grpc._service import METHODS, SERVICE
from tpuserver.tensor_io import (
    array_from_binary as _array_from_raw,
    binary_from_array as _raw_from_array,
)
from tritonclient.utils import triton_to_np_dtype

_TYPED_FIELDS = {
    "BOOL": "bool_contents",
    "INT8": "int_contents",
    "INT16": "int_contents",
    "INT32": "int_contents",
    "INT64": "int64_contents",
    "UINT8": "uint_contents",
    "UINT16": "uint_contents",
    "UINT32": "uint_contents",
    "UINT64": "uint64_contents",
    "FP32": "fp32_contents",
    "FP64": "fp64_contents",
    "BYTES": "bytes_contents",
}


def _param_value(p):
    field = p.WhichOneof("parameter_choice")
    return getattr(p, field) if field else None


def _params_dict(param_map):
    return {k: _param_value(v) for k, v in param_map.items()}


def _merge_waiting(waiting):
    """What waits in a stream's queue, ``(item, response, request)`` in
    order, as ``(item, response)`` pairs to send: every mergeable
    response (``item`` None) of a request joins, in order, the first of
    that request still open, until something else of the request comes.
    Each request keeps its order; no request waits for another."""
    out, open_at = [], {}
    for item, resp, request in waiting:
        if item is None:
            at = open_at.get(request)
            if at is None:
                open_at[request] = len(out)
                out.append([resp])
            else:
                out[at].append(resp)
        else:
            open_at.pop(request, None)
            out.append((item, resp))
    return [(None, merge_responses(e)) if isinstance(e, list) else e
            for e in out]




class _CoreBridge:
    """Protobuf <-> core translation + the RPC method implementations."""

    def __init__(self, core):
        self._core = core

    # -- conversion --------------------------------------------------------

    def _request_from_proto(self, request):
        inputs = {}
        shm_input_regions = []
        raw_cursor = 0  # shm inputs do not consume raw_input_contents slots
        for tensor in request.inputs:
            shape = list(tensor.shape)
            tparams = _params_dict(tensor.parameters)
            if "shared_memory_region" in tparams:
                inputs[tensor.name] = self._core.read_shm_input(
                    tparams["shared_memory_region"],
                    tparams.get("shared_memory_byte_size", 0),
                    tparams.get("shared_memory_offset", 0),
                    tensor.datatype,
                    shape,
                )
                shm_input_regions.append(
                    tparams["shared_memory_region"])
            elif raw_cursor < len(request.raw_input_contents):
                inputs[tensor.name] = _array_from_raw(
                    request.raw_input_contents[raw_cursor], tensor.datatype,
                    shape,
                )
                raw_cursor += 1
            else:
                field = _TYPED_FIELDS.get(tensor.datatype)
                if field is None:
                    raise ServerError(
                        "input '{}' has no data".format(tensor.name)
                    )
                vals = list(getattr(tensor.contents, field))
                if tensor.datatype == "BYTES":
                    arr = np.array(vals, dtype=np.object_).reshape(shape)
                else:
                    arr = np.array(
                        vals, dtype=triton_to_np_dtype(tensor.datatype)
                    ).reshape(shape)
                inputs[tensor.name] = arr
        requested = None
        if request.outputs:
            requested = []
            for out in request.outputs:
                oparams = _params_dict(out.parameters)
                requested.append(
                    RequestedOutput(
                        out.name,
                        binary_data=True,
                        class_count=oparams.get("classification", 0),
                        shm_region=oparams.get("shared_memory_region"),
                        shm_byte_size=oparams.get(
                            "shared_memory_byte_size", 0
                        ),
                        shm_offset=oparams.get("shared_memory_offset", 0),
                    )
                )
        core_request = InferRequest(
            request.model_name,
            request.model_version,
            request.id,
            inputs,
            requested,
            _params_dict(request.parameters),
        )
        # decoupled models pin these for the stream's lifetime (409 on
        # a concurrent unregister of a region backing a live view)
        core_request.shm_input_regions = tuple(shm_input_regions)
        return core_request

    def _response_to_proto(self, resp):
        out = pb.ModelInferResponse(
            model_name=resp.model_name,
            model_version=resp.model_version,
            id=resp.id,
        )
        for key, value in (resp.parameters or {}).items():
            if isinstance(value, bool):
                out.parameters[key].bool_param = value
            elif isinstance(value, int):
                out.parameters[key].int64_param = value
            else:
                out.parameters[key].string_param = str(value)
        for spec, array, delivery in resp.outputs:
            tensor = out.outputs.add()
            tensor.name = spec["name"]
            tensor.datatype = spec["datatype"]
            tensor.shape.extend(int(s) for s in spec["shape"])
            if array is None:  # delivered via shared memory
                tensor.parameters[
                    "shared_memory_region"
                ].string_param = delivery["shm_region"]
                tensor.parameters[
                    "shared_memory_byte_size"
                ].int64_param = delivery["shm_byte_size"]
                if delivery["shm_offset"]:
                    tensor.parameters[
                        "shared_memory_offset"
                    ].int64_param = delivery["shm_offset"]
                out.raw_output_contents.append(b"")
            else:
                out.raw_output_contents.append(
                    _raw_from_array(array, spec["datatype"])
                )
        return out

    # -- unary handlers ----------------------------------------------------

    def ServerLive(self, request, context):
        return pb.ServerLiveResponse(live=True)

    def ServerReady(self, request, context):
        # real core state (starting/draining/watchdog-tripped), not a
        # constant: load balancers must see drain begin before requests
        # start failing
        return pb.ServerReadyResponse(ready=self._core.server_ready())

    def ModelReady(self, request, context):
        return pb.ModelReadyResponse(
            ready=self._core.model_ready(request.name, request.version)
        )

    def ServerMetadata(self, request, context):
        return pb.ServerMetadataResponse(
            name=SERVER_NAME,
            version=SERVER_VERSION,
            extensions=SERVER_EXTENSIONS,
        )

    def ServerMetrics(self, request, context):
        """The Prometheus exposition over gRPC: the SAME snapshot the
        HTTP frontend serves at ``GET /metrics``
        (``core.metrics_text()``), carried in the response's
        ``metrics`` string param — scrapers behind a gRPC-only
        deployment lose nothing."""
        resp = pb.LogSettingsResponse()
        resp.settings["metrics"].string_param = self._core.metrics_text()
        return resp

    def ModelMetadata(self, request, context):
        md = self._core.model_metadata(request.name, request.version)
        resp = pb.ModelMetadataResponse(
            name=md["name"], versions=md["versions"], platform=md["platform"]
        )
        for t in md["inputs"]:
            resp.inputs.add(
                name=t["name"], datatype=t["datatype"], shape=t["shape"]
            )
        for t in md["outputs"]:
            resp.outputs.add(
                name=t["name"], datatype=t["datatype"], shape=t["shape"]
            )
        return resp

    def ModelConfig(self, request, context):
        from google.protobuf import json_format

        cfg = self._core.model_config(request.name, request.version)
        config = json_format.ParseDict(
            cfg, pb.model__config__pb2.ModelConfig(),
            ignore_unknown_fields=True,
        )
        return pb.ModelConfigResponse(config=config)

    def ModelStatistics(self, request, context):
        from google.protobuf import json_format

        stats = self._core.model_statistics(request.name, request.version)
        return json_format.ParseDict(
            stats, pb.ModelStatisticsResponse(), ignore_unknown_fields=True
        )

    def RepositoryIndex(self, request, context):
        resp = pb.RepositoryIndexResponse()
        for entry in self._core.repository_index(ready_only=request.ready):
            resp.models.add(**entry)
        return resp

    def RepositoryModelLoad(self, request, context):
        self._core.load_model(request.model_name)
        return pb.RepositoryModelLoadResponse()

    def RepositoryModelUnload(self, request, context):
        unload_dependents = False
        p = request.parameters.get("unload_dependents")
        if p is not None:
            unload_dependents = bool(_param_value(p))
        self._core.unload_model(request.model_name, unload_dependents)
        return pb.RepositoryModelUnloadResponse()

    # -- shared memory -----------------------------------------------------

    def SystemSharedMemoryStatus(self, request, context):
        resp = pb.SystemSharedMemoryStatusResponse()
        for name, region in self._core.system_shm_status(
            request.name
        ).items():
            resp.regions[name].name = region["name"]
            resp.regions[name].key = region["key"]
            resp.regions[name].offset = region["offset"]
            resp.regions[name].byte_size = region["byte_size"]
        return resp

    def SystemSharedMemoryRegister(self, request, context):
        self._core.register_system_shm(
            request.name, request.key, request.offset, request.byte_size
        )
        return pb.SystemSharedMemoryRegisterResponse()

    def SystemSharedMemoryUnregister(self, request, context):
        self._core.unregister_system_shm(request.name)
        return pb.SystemSharedMemoryUnregisterResponse()

    def CudaSharedMemoryStatus(self, request, context):
        resp = pb.CudaSharedMemoryStatusResponse()
        for name, region in self._core.cuda_shm_status(request.name).items():
            resp.regions[name].name = region["name"]
            resp.regions[name].device_id = region["device_id"]
            resp.regions[name].byte_size = region["byte_size"]
        return resp

    def CudaSharedMemoryRegister(self, request, context):
        self._core.register_cuda_shm(
            request.name, request.raw_handle, request.device_id,
            request.byte_size,
        )
        return pb.CudaSharedMemoryRegisterResponse()

    def CudaSharedMemoryUnregister(self, request, context):
        self._core.unregister_cuda_shm(request.name)
        return pb.CudaSharedMemoryUnregisterResponse()

    def XlaSharedMemoryStatus(self, request, context):
        resp = pb.XlaSharedMemoryStatusResponse()
        for name, region in self._core.xla_shm_status(request.name).items():
            resp.regions[name].name = region["name"]
            resp.regions[name].device_ordinal = region["device_ordinal"]
            resp.regions[name].byte_size = region["byte_size"]
        return resp

    def XlaSharedMemoryRegister(self, request, context):
        self._core.register_xla_shm(
            request.name, request.raw_handle, request.device_ordinal,
            request.byte_size,
        )
        return pb.XlaSharedMemoryRegisterResponse()

    def XlaSharedMemoryUnregister(self, request, context):
        self._core.unregister_xla_shm(request.name)
        return pb.XlaSharedMemoryUnregisterResponse()

    # -- settings ----------------------------------------------------------

    def TraceSetting(self, request, context):
        settings = {}
        for key, val in request.settings.items():
            settings[key] = list(val.value)
        if settings:
            result = self._core.update_trace_settings(
                request.model_name or None, settings
            )
        else:
            result = self._core.get_trace_settings(
                request.model_name or None
            )
        resp = pb.TraceSettingResponse()
        for key, values in result["settings"].items():
            resp.settings[key].value.extend(values)
        return resp

    def LogSettings(self, request, context):
        settings = {}
        for key, val in request.settings.items():
            field = val.WhichOneof("parameter_choice")
            if field is not None:
                settings[key] = getattr(val, field)
        if settings:
            result = self._core.update_log_settings(settings)
        else:
            result = self._core.get_log_settings()
        resp = pb.LogSettingsResponse()
        for key, value in result.items():
            if isinstance(value, bool):
                resp.settings[key].bool_param = value
            elif isinstance(value, int):
                resp.settings[key].uint32_param = value
            else:
                resp.settings[key].string_param = str(value)
        return resp

    # -- inference ---------------------------------------------------------

    @staticmethod
    def _stamp_deadline(core_request, context):
        """Thread the client's gRPC context deadline into the core as a
        monotonic bound (None when the client set none): the scheduler
        expires pending admissions and retires in-flight slots past it,
        and the typed DeadlineExceeded maps back to DEADLINE_EXCEEDED."""
        remaining = context.time_remaining()
        if remaining is not None:
            core_request.deadline = time.monotonic() + remaining
        return core_request

    def ModelInfer(self, request, context):
        core_request = self._stamp_deadline(
            self._request_from_proto(request), context
        )
        resp = self._core.infer(core_request)
        return self._response_to_proto(resp)

    # concurrent in-flight non-decoupled requests per stream: clients
    # pipeline on one bidi stream, and serializing every dispatch would
    # waste the device while a response is in flight
    STREAM_CONCURRENCY = 8

    def ModelStreamInfer(self, request_iterator, context):
        """Bidi stream: each request may yield 0..N responses (decoupled
        models); errors are delivered in-band via error_message so the
        stream survives bad requests (reference server semantics).

        Non-decoupled requests execute concurrently (bounded) and their
        responses interleave in completion order — each response carries
        its request id, matching server stream semantics.  Decoupled
        requests keep strict sequential handling by default: their
        multi-response ordering is part of the model's contract.

        Continuous-batching decoupled models (``concurrent_decoupled``,
        e.g. llama with ``max_slots > 1``) are the exception the core
        reports via ``requires_stream_order``: their stream requests run
        concurrently like unary ones, so several generations submitted
        on ONE bidi stream decode interleaved on the chip — each slot's
        per-step token fans out as a response tagged with its request id
        and the client demultiplexes.  Within one generation, token
        order is still the emission order of its scheduler slot.

        A request carrying ``MULTI_TOKEN_PARAM`` declares that its client
        reads multi-token responses: the handler then sends, each time it
        is free, every mergeable response of that request already waiting
        as ONE (``merge_responses``).  It never waits to fill one.
        """
        import queue as _queue
        import threading as _threading

        # bounded: restores response backpressure that direct generator
        # yields gave (a slow reader must slow producers, not buffer
        # unboundedly)
        out = _queue.Queue(maxsize=self.STREAM_CONCURRENCY * 4)
        inflight = _threading.Semaphore(self.STREAM_CONCURRENCY)
        pending = [0]
        done_feeding = _threading.Event()
        cancelled = _threading.Event()
        lock = _threading.Lock()
        _SENTINEL = object()

        def emit(item, resp=None, request=None):
            """put with cancellation: a gone client must not wedge
            producer threads on a full queue.  ``resp`` is the core
            response ``item`` was built from, whose stamps the handler
            counts when it hands ``item`` on; ``item`` None leaves the
            proto to the handler, which merges ``resp`` with what of
            ``request`` waits behind it."""
            while not cancelled.is_set():
                try:
                    out.put((item, resp, request), timeout=0.25)
                    return True
                except _queue.Full:
                    continue
            return False

        def finish_one():
            with lock:
                pending[0] -= 1
                if pending[0] == 0 and done_feeding.is_set():
                    emit(_SENTINEL)

        def run_one(core_request, bounded=True):
            try:
                for resp in self._core.infer_stream(core_request):
                    if cancelled.is_set() or not context.is_active():
                        break  # stop generating for a gone client
                    with span("frontend.emit"):
                        sent = emit(None if resp.mergeable else
                                    pb.ModelStreamInferResponse(
                                        infer_response=self
                                        ._response_to_proto(resp)),
                                    resp, core_request)
                    if not sent:
                        break
            except ServerError as e:
                emit(pb.ModelStreamInferResponse(error_message=str(e)),
                     None, core_request)
            except Exception as e:
                emit(pb.ModelStreamInferResponse(
                    error_message="unexpected error: {}".format(e)),
                    None, core_request)
            finally:
                if bounded:
                    inflight.release()
                finish_one()

        def feed():
            try:
                for request in request_iterator:
                    if cancelled.is_set():
                        break
                    try:
                        core_request = self._stamp_deadline(
                            self._request_from_proto(request), context)
                        core_request.multi_token = bool(
                            core_request.parameters.pop(
                                MULTI_TOKEN_PARAM, False))
                    except Exception as e:
                        emit(pb.ModelStreamInferResponse(
                            error_message=str(e)))
                        continue
                    try:
                        ordered = self._core.requires_stream_order(
                            core_request.model_name)
                        unbounded = self._core.is_concurrent_decoupled(
                            core_request.model_name)
                    except Exception:
                        ordered = False
                        unbounded = False
                    if not unbounded:
                        # scheduler-backed generations self-limit via
                        # their slot count; holding a semaphore slot for
                        # a whole generation would cap one client stream
                        # at STREAM_CONCURRENCY regardless of max_slots
                        # AND stall this feed loop behind it
                        inflight.acquire()
                    with lock:
                        pending[0] += 1
                    if ordered:
                        # sequential: decoupled response bursts and
                        # sequence-state step order are contractual
                        run_one(core_request)
                    else:
                        _threading.Thread(
                            target=run_one,
                            args=(core_request, not unbounded),
                            daemon=True,
                        ).start()
            except grpc.RpcError:
                pass  # client cancelled/disconnected: normal stream end
            finally:
                done_feeding.set()
                with lock:
                    if pending[0] == 0:
                        emit(_SENTINEL)

        _threading.Thread(target=feed, daemon=True).start()
        try:
            from tpuserver import faults as _faults

            while True:
                waiting = [out.get()]
                while True:
                    try:
                        waiting.append(out.get_nowait())
                    except _queue.Empty:
                        break
                for item, resp in _merge_waiting(waiting):
                    if item is _SENTINEL:
                        return
                    # chaos hook: kill the bidi stream mid-flight (the
                    # raised FaultInjected aborts the RPC with a stream-
                    # level error) so client reconnect+resume is
                    # drivable end-to-end; skip=N drops after the Nth
                    # response
                    _faults.fire("grpc.stream_infer", self._core.fault_scope)
                    if resp is not None:
                        self._core.count_token_handoff(resp)
                    if item is None:
                        item = pb.ModelStreamInferResponse(
                            infer_response=self._response_to_proto(resp))
                    yield item
        finally:
            # reader gone (cancel/deadline/exit): release producers and
            # stop outstanding generation
            cancelled.set()
            while True:
                try:
                    out.get_nowait()
                except _queue.Empty:
                    break


def _wrap_unary(bridge, name):
    method = getattr(bridge, name)

    def handler(request, context):
        try:
            return method(request, context)
        except ServerError as e:
            if getattr(e, "retry_after", None) is not None:
                # the gRPC twin of the HTTP Retry-After header: clients
                # with a retry policy read it from trailing metadata
                context.set_trailing_metadata(
                    (("retry-after", str(int(e.retry_after))),)
                )
            context.abort(_status_code(e.code), str(e))
        except Exception as e:
            context.abort(grpc.StatusCode.INTERNAL, str(e))

    return handler


def _status_code(http_code):
    return {
        400: grpc.StatusCode.INVALID_ARGUMENT,
        404: grpc.StatusCode.NOT_FOUND,
        409: grpc.StatusCode.ABORTED,  # shm region still referenced
        422: grpc.StatusCode.INVALID_ARGUMENT,  # quarantined slot
        429: grpc.StatusCode.RESOURCE_EXHAUSTED,
        500: grpc.StatusCode.INTERNAL,
        501: grpc.StatusCode.UNIMPLEMENTED,
        503: grpc.StatusCode.UNAVAILABLE,
        504: grpc.StatusCode.DEADLINE_EXCEEDED,
    }.get(http_code, grpc.StatusCode.UNKNOWN)


class GrpcFrontend:
    """A grpc.server hosting the full GRPCInferenceService."""

    def __init__(self, core, host="127.0.0.1", port=0, max_workers=32):
        self._core = core
        self._host = host
        self._max_workers = max_workers
        self._requested_port = port
        self._server = None
        self._port = None

    def start(self):
        # kept on the frontend so a fleet-transition test (or an ops
        # hot-swap) can repoint the serving core under a fixed address
        self._bridge = bridge = _CoreBridge(self._core)
        handlers = {}
        for name, (req_cls, resp_cls, kind) in METHODS.items():
            if kind == "unary":
                handlers[name] = grpc.unary_unary_rpc_method_handler(
                    _wrap_unary(bridge, name),
                    request_deserializer=req_cls.FromString,
                    response_serializer=resp_cls.SerializeToString,
                )
            else:
                handlers[name] = grpc.stream_stream_rpc_method_handler(
                    getattr(bridge, name),
                    request_deserializer=req_cls.FromString,
                    response_serializer=resp_cls.SerializeToString,
                )
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=self._max_workers),
            options=[
                ("grpc.max_send_message_length", -1),
                ("grpc.max_receive_message_length", -1),
                # tolerate client-side keepalive pings (role of Triton's
                # --grpc-keepalive-* server flags): no ping strikes, any
                # ping interval accepted even without in-flight data
                ("grpc.http2.max_ping_strikes", 0),
                ("grpc.http2.min_recv_ping_interval_without_data_ms", 10),
                ("grpc.keepalive_permit_without_calls", 1),
            ],
        )
        self._server.add_generic_rpc_handlers(
            (grpc.method_handlers_generic_handler(SERVICE, handlers),)
        )
        self._port = self._server.add_insecure_port(
            "{}:{}".format(self._host, self._requested_port)
        )
        self._server.start()
        self._core.attach_frontend()
        self._attached = True
        return self

    @property
    def port(self):
        return self._port

    @property
    def url(self):
        return "{}:{}".format(self._host, self._port)

    def stop(self, grace=None):
        if self._server is not None:
            # bounded wait: a handler thread wedged in user/model code
            # (e.g. a compile) cannot be interrupted and must not hang
            # the owner's shutdown forever
            if not self._server.stop(grace).wait(timeout=10):
                import logging

                logging.getLogger(__name__).warning(
                    "grpc frontend did not terminate within 10s "
                    "(a handler thread is still running); the port may "
                    "stay bound"
                )
            self._server = None
            if getattr(self, "_attached", False):
                # only an attach that actually happened may detach: an
                # unpaired detach would close a shared core under
                # another live frontend
                self._attached = False
                self._core.detach_frontend()
