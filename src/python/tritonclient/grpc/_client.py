"""Sync gRPC client for the KServe-v2 protocol — full surface of the
reference ``tritonclient.grpc.InferenceServerClient`` (grpc/_client.py:87+):
health, metadata, config, repository control, statistics, trace and log
settings, system/CUDA/XLA shared-memory registration, sync/async infer and
bidirectional (decoupled-capable) streaming.

TPU-first deltas from the reference: XlaSharedMemory* verbs replace the
CUDA-shm path as the on-device plane (CUDA verbs kept for API parity), and
InferInput accepts ``jax.Array``.
"""

import time

import grpc

from tritonclient._auxiliary import (  # noqa: F401 — RetryPolicy re-exported
    CONNECT_ERROR_DETAILS,
    RetryPolicy,
)
from tritonclient.utils import InferenceServerException, raise_error

from . import grpc_service_pb2 as pb
from ._infer_input import InferInput, InferRequestedOutput  # noqa: F401
from ._infer_result import InferResult, token_results
from ._infer_stream import _InferStream, _PulledStream
from ._service import ServiceStub
from ._utils import (
    _get_inference_request,
    get_error_grpc,
    raise_error_grpc,
    retry_after_from_rpc_error,
)

# Reference grpc_client.cc:78-145 keeps a process-wide channel cache with a
# share count; grpc-python channels multiplex internally, so one channel per
# client is the idiomatic equivalent.  Keepalive mirrors KeepAliveOptions
# (reference grpc_client.h:61-82).


class KeepAliveOptions:
    """gRPC keepalive settings (reference grpc_client.h:61-82)."""

    def __init__(
        self,
        keepalive_time_ms=7200000,
        keepalive_timeout_ms=20000,
        keepalive_permit_without_calls=False,
        http2_max_pings_without_data=2,
    ):
        self.keepalive_time_ms = keepalive_time_ms
        self.keepalive_timeout_ms = keepalive_timeout_ms
        self.keepalive_permit_without_calls = keepalive_permit_without_calls
        self.http2_max_pings_without_data = http2_max_pings_without_data


#: gRPC codes the retry policy treats as overload rejections — the wire
#: twins of HTTP 429 (RESOURCE_EXHAUSTED) and 503 (UNAVAILABLE; also what
#: grpc-core surfaces for connection-refused, covering connection errors)
_RETRYABLE_CODES = frozenset(
    (grpc.StatusCode.RESOURCE_EXHAUSTED, grpc.StatusCode.UNAVAILABLE)
)


class InferenceServerClient:
    """A client talking KServe-v2 over gRPC to ``url`` (host:port).

    ``retry_policy`` (a ``tritonclient._auxiliary.RetryPolicy``) opts
    unary RPCs into exponential-backoff retries of RESOURCE_EXHAUSTED /
    UNAVAILABLE failures, honoring the server's ``retry-after``
    trailing metadata; DEADLINE_EXCEEDED and every other code propagate
    immediately.  Default None = no retries."""

    def __init__(
        self,
        url,
        verbose=False,
        ssl=False,
        root_certificates=None,
        private_key=None,
        certificate_chain=None,
        creds=None,
        keepalive_options=None,
        channel_args=None,
        retry_policy=None,
    ):
        if keepalive_options is None:
            keepalive_options = KeepAliveOptions()
        options = [
            ("grpc.max_send_message_length", -1),
            ("grpc.max_receive_message_length", -1),
            ("grpc.keepalive_time_ms", keepalive_options.keepalive_time_ms),
            (
                "grpc.keepalive_timeout_ms",
                keepalive_options.keepalive_timeout_ms,
            ),
            (
                "grpc.keepalive_permit_without_calls",
                int(keepalive_options.keepalive_permit_without_calls),
            ),
            (
                "grpc.http2.max_pings_without_data",
                keepalive_options.http2_max_pings_without_data,
            ),
        ]
        for arg in channel_args or []:
            options.append(arg)
        if creds is not None:
            self._channel = grpc.secure_channel(url, creds, options=options)
        elif ssl:
            rc = open(root_certificates, "rb").read() if (
                root_certificates
            ) else None
            pk = open(private_key, "rb").read() if private_key else None
            cc = open(certificate_chain, "rb").read() if (
                certificate_chain
            ) else None
            credentials = grpc.ssl_channel_credentials(
                root_certificates=rc, private_key=pk, certificate_chain=cc
            )
            self._channel = grpc.secure_channel(
                url, credentials, options=options
            )
        else:
            self._channel = grpc.insecure_channel(url, options=options)
        self._stub = ServiceStub(self._channel)
        self._url = url
        self._channel_options = options
        self._secure = creds is not None or ssl
        self._verbose = verbose
        self._stream = None
        self._retry_policy = retry_policy

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self):
        return self

    def __exit__(self, type_, value, traceback):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def close(self):
        """Close the client: stop any active stream and the channel."""
        self.stop_stream()
        self._channel.close()

    def _rebind(self, url):
        """Re-point this client at ``url`` (insecure channels only):
        close the current channel and open a fresh one.  The
        ``generate_stream`` fallback rotation uses this between
        reconnect attempts — the single bidi-stream slot is empty at
        that point, so no in-flight RPC rides the old channel."""
        if url == self._url:
            return
        if self._secure:
            raise_error(
                "fallback_urls requires insecure channels (per-url TLS "
                "material cannot be assumed to transfer)")
        self._channel.close()
        self._channel = grpc.insecure_channel(
            url, options=self._channel_options)
        self._stub = ServiceStub(self._channel)
        self._url = url

    # -- helpers -----------------------------------------------------------

    def _metadata(self, headers):
        if headers is None:
            return None
        return tuple(headers.items())

    @staticmethod
    def _is_connect_failure(rpc_error):
        """Whether an UNAVAILABLE provably failed before the request
        left the client (grpc-core's connect-phase detail strings,
        shared with the pool's classifier).  Best-effort: an
        unrecognized detail is treated as possibly mid-call, i.e. NOT
        safely retryable."""
        try:
            details = (rpc_error.details() or "").lower()
        except Exception:
            return False
        return any(marker in details for marker in CONNECT_ERROR_DETAILS)

    @staticmethod
    def _retry_after_of(rpc_error):
        """The server's ``retry-after`` trailing-metadata value (the
        gRPC twin of the HTTP header), or None."""
        return retry_after_from_rpc_error(rpc_error)

    def _call(self, name, request, headers=None, timeout=None):
        if self._verbose:
            print("{}, metadata {}\n{}".format(name, headers, request))
        policy = self._retry_policy
        # the retry loop's wall-clock budget: the sooner of the caller's
        # RPC timeout and the policy's max_total_s — a server Retry-After
        # hint may never sleep past either
        budget_s = None
        if policy is not None:
            if timeout is not None:
                budget_s = float(timeout)
            if policy.max_total_s is not None:
                budget_s = (
                    policy.max_total_s
                    if budget_s is None
                    else min(budget_s, policy.max_total_s)
                )
        budget_deadline = (
            time.monotonic() + budget_s if budget_s is not None else None
        )
        attempt = 0
        while True:
            try:
                response = getattr(self._stub, name)(
                    request=request,
                    metadata=self._metadata(headers),
                    timeout=timeout,
                )
                if self._verbose:
                    print(response)
                return response
            except grpc.RpcError as rpc_error:
                # retry only typed overload/unreachable rejections (the
                # server shed the request before work, or never saw it);
                # DEADLINE_EXCEEDED and everything else may have
                # executed server-side and must propagate.
                # UNAVAILABLE conflates a server-typed 503, a connect
                # failure, AND a mid-call reset (the dangerous one): it
                # is retryable only when the server's retry-after
                # trailer proves a typed shed, or when the detail
                # string marks a connect-phase failure (the request
                # never left the client).
                code = rpc_error.code() if policy is not None else None
                retry_after = (
                    self._retry_after_of(rpc_error)
                    if code in _RETRYABLE_CODES
                    else None
                )
                if code == grpc.StatusCode.RESOURCE_EXHAUSTED:
                    retryable = True
                elif code == grpc.StatusCode.UNAVAILABLE:
                    retryable = retry_after is not None or (
                        policy.retry_connection_errors
                        and self._is_connect_failure(rpc_error)
                    )
                else:
                    retryable = False
                remaining = (
                    budget_deadline - time.monotonic()
                    if budget_deadline is not None
                    else None
                )
                if (
                    retryable
                    and attempt + 1 < policy.max_attempts
                    and (remaining is None or remaining > 0)
                ):
                    time.sleep(
                        policy.backoff_s(attempt, retry_after, remaining)
                    )
                    attempt += 1
                    continue
                raise_error_grpc(rpc_error)

    @staticmethod
    def _as_json(message, as_json):
        if not as_json:
            return message
        from google.protobuf import json_format

        return json_format.MessageToDict(
            message, preserving_proto_field_name=True
        )

    # -- health / metadata -------------------------------------------------

    def is_server_live(self, headers=None, client_timeout=None):
        return self._call(
            "ServerLive", pb.ServerLiveRequest(), headers, client_timeout
        ).live

    def is_server_ready(self, headers=None, client_timeout=None):
        return self._call(
            "ServerReady", pb.ServerReadyRequest(), headers, client_timeout
        ).ready

    def is_model_ready(
        self, model_name, model_version="", headers=None, client_timeout=None
    ):
        return self._call(
            "ModelReady",
            pb.ModelReadyRequest(name=model_name, version=model_version),
            headers,
            client_timeout,
        ).ready

    def get_server_metadata(
        self, headers=None, as_json=False, client_timeout=None
    ):
        return self._as_json(
            self._call(
                "ServerMetadata", pb.ServerMetadataRequest(), headers,
                client_timeout,
            ),
            as_json,
        )

    def get_model_metadata(
        self, model_name, model_version="", headers=None, as_json=False,
        client_timeout=None,
    ):
        return self._as_json(
            self._call(
                "ModelMetadata",
                pb.ModelMetadataRequest(
                    name=model_name, version=model_version
                ),
                headers,
                client_timeout,
            ),
            as_json,
        )

    def get_model_config(
        self, model_name, model_version="", headers=None, as_json=False,
        client_timeout=None,
    ):
        return self._as_json(
            self._call(
                "ModelConfig",
                pb.ModelConfigRequest(
                    name=model_name, version=model_version
                ),
                headers,
                client_timeout,
            ),
            as_json,
        )

    # -- repository --------------------------------------------------------

    def get_model_repository_index(
        self, headers=None, as_json=False, client_timeout=None
    ):
        return self._as_json(
            self._call(
                "RepositoryIndex", pb.RepositoryIndexRequest(), headers,
                client_timeout,
            ),
            as_json,
        )

    def load_model(
        self, model_name, headers=None, config=None, files=None,
        client_timeout=None,
    ):
        request = pb.RepositoryModelLoadRequest(model_name=model_name)
        if config is not None:
            request.parameters["config"].string_param = config
        for path, content in (files or {}).items():
            request.parameters[path].bytes_param = content
        self._call("RepositoryModelLoad", request, headers, client_timeout)

    def unload_model(
        self, model_name, headers=None, unload_dependents=False,
        client_timeout=None,
    ):
        request = pb.RepositoryModelUnloadRequest(model_name=model_name)
        request.parameters["unload_dependents"].bool_param = (
            unload_dependents
        )
        self._call("RepositoryModelUnload", request, headers, client_timeout)

    # -- statistics / settings ---------------------------------------------

    def get_inference_statistics(
        self, model_name="", model_version="", headers=None, as_json=False,
        client_timeout=None,
    ):
        return self._as_json(
            self._call(
                "ModelStatistics",
                pb.ModelStatisticsRequest(
                    name=model_name, version=model_version
                ),
                headers,
                client_timeout,
            ),
            as_json,
        )

    def update_trace_settings(
        self, model_name=None, settings=None, headers=None, as_json=False,
        client_timeout=None,
    ):
        request = pb.TraceSettingRequest(model_name=model_name or "")
        for key, value in (settings or {}).items():
            if value is None:
                request.settings[key].Clear()
                continue
            if isinstance(value, (list, tuple)):
                request.settings[key].value.extend(str(v) for v in value)
            else:
                request.settings[key].value.append(str(value))
        return self._as_json(
            self._call("TraceSetting", request, headers, client_timeout),
            as_json,
        )

    def get_trace_settings(
        self, model_name=None, headers=None, as_json=False,
        client_timeout=None,
    ):
        return self._as_json(
            self._call(
                "TraceSetting",
                pb.TraceSettingRequest(model_name=model_name or ""),
                headers,
                client_timeout,
            ),
            as_json,
        )

    def update_log_settings(
        self, settings, headers=None, as_json=False, client_timeout=None
    ):
        request = pb.LogSettingsRequest()
        for key, value in settings.items():
            if isinstance(value, bool):
                request.settings[key].bool_param = value
            elif isinstance(value, int):
                request.settings[key].uint32_param = value
            elif isinstance(value, str):
                request.settings[key].string_param = value
            else:
                raise_error(
                    "unsupported log setting type for '{}'".format(key)
                )
        return self._as_json(
            self._call("LogSettings", request, headers, client_timeout),
            as_json,
        )

    def get_log_settings(
        self, headers=None, as_json=False, client_timeout=None
    ):
        return self._as_json(
            self._call(
                "LogSettings", pb.LogSettingsRequest(), headers,
                client_timeout,
            ),
            as_json,
        )

    def get_metrics(self, headers=None, client_timeout=None):
        """The server's Prometheus text exposition via the
        ServerMetrics-style unary — byte-identical to the HTTP
        frontend's ``GET /metrics`` (the gRPC twin of scraping it)."""
        resp = self._call(
            "ServerMetrics", pb.ServerMetadataRequest(), headers,
            client_timeout,
        )
        return resp.settings["metrics"].string_param

    # -- shared memory -----------------------------------------------------

    def get_system_shared_memory_status(
        self, region_name="", headers=None, as_json=False,
        client_timeout=None,
    ):
        return self._as_json(
            self._call(
                "SystemSharedMemoryStatus",
                pb.SystemSharedMemoryStatusRequest(name=region_name),
                headers,
                client_timeout,
            ),
            as_json,
        )

    def register_system_shared_memory(
        self, name, key, byte_size, offset=0, headers=None,
        client_timeout=None,
    ):
        self._call(
            "SystemSharedMemoryRegister",
            pb.SystemSharedMemoryRegisterRequest(
                name=name, key=key, offset=offset, byte_size=byte_size
            ),
            headers,
            client_timeout,
        )

    def unregister_system_shared_memory(
        self, name="", headers=None, client_timeout=None
    ):
        self._call(
            "SystemSharedMemoryUnregister",
            pb.SystemSharedMemoryUnregisterRequest(name=name),
            headers,
            client_timeout,
        )

    def get_cuda_shared_memory_status(
        self, region_name="", headers=None, as_json=False,
        client_timeout=None,
    ):
        return self._as_json(
            self._call(
                "CudaSharedMemoryStatus",
                pb.CudaSharedMemoryStatusRequest(name=region_name),
                headers,
                client_timeout,
            ),
            as_json,
        )

    def register_cuda_shared_memory(
        self, name, raw_handle, device_id, byte_size, headers=None,
        client_timeout=None,
    ):
        self._call(
            "CudaSharedMemoryRegister",
            pb.CudaSharedMemoryRegisterRequest(
                name=name, raw_handle=raw_handle, device_id=device_id,
                byte_size=byte_size,
            ),
            headers,
            client_timeout,
        )

    def unregister_cuda_shared_memory(
        self, name="", headers=None, client_timeout=None
    ):
        self._call(
            "CudaSharedMemoryUnregister",
            pb.CudaSharedMemoryUnregisterRequest(name=name),
            headers,
            client_timeout,
        )

    def get_xla_shared_memory_status(
        self, region_name="", headers=None, as_json=False,
        client_timeout=None,
    ):
        """Status of registered XLA/TPU shared-memory regions (the TPU
        generalization of the CUDA-shm verbs, reference grpc_client.h:365)."""
        return self._as_json(
            self._call(
                "XlaSharedMemoryStatus",
                pb.XlaSharedMemoryStatusRequest(name=region_name),
                headers,
                client_timeout,
            ),
            as_json,
        )

    def register_xla_shared_memory(
        self, name, raw_handle, device_ordinal, byte_size, headers=None,
        client_timeout=None,
    ):
        """Register a TPU HBM region by its serialized handle (see
        tritonclient.utils.xla_shared_memory.get_raw_handle)."""
        self._call(
            "XlaSharedMemoryRegister",
            pb.XlaSharedMemoryRegisterRequest(
                name=name, raw_handle=raw_handle,
                device_ordinal=device_ordinal, byte_size=byte_size,
            ),
            headers,
            client_timeout,
        )

    def unregister_xla_shared_memory(
        self, name="", headers=None, client_timeout=None
    ):
        self._call(
            "XlaSharedMemoryUnregister",
            pb.XlaSharedMemoryUnregisterRequest(name=name),
            headers,
            client_timeout,
        )

    # -- inference ---------------------------------------------------------

    def infer(
        self,
        model_name,
        inputs,
        model_version="",
        outputs=None,
        request_id="",
        sequence_id=0,
        sequence_start=False,
        sequence_end=False,
        priority=0,
        timeout=None,
        client_timeout=None,
        headers=None,
        parameters=None,
    ):
        """Synchronous inference (reference grpc/_client.py:1248)."""
        request = _get_inference_request(
            model_name=model_name,
            inputs=inputs,
            model_version=model_version,
            request_id=request_id,
            outputs=outputs,
            sequence_id=sequence_id,
            sequence_start=sequence_start,
            sequence_end=sequence_end,
            priority=priority,
            timeout=timeout,
            parameters=parameters,
        )
        response = self._call("ModelInfer", request, headers, client_timeout)
        return InferResult(response)

    def async_infer(
        self,
        model_name,
        inputs,
        callback,
        model_version="",
        outputs=None,
        request_id="",
        sequence_id=0,
        sequence_start=False,
        sequence_end=False,
        priority=0,
        timeout=None,
        client_timeout=None,
        headers=None,
        parameters=None,
    ):
        """Asynchronous inference; ``callback(result, error)`` fires on a
        gRPC completion thread (reference grpc/_client.py:1392)."""
        request = _get_inference_request(
            model_name=model_name,
            inputs=inputs,
            model_version=model_version,
            request_id=request_id,
            outputs=outputs,
            sequence_id=sequence_id,
            sequence_start=sequence_start,
            sequence_end=sequence_end,
            priority=priority,
            timeout=timeout,
            parameters=parameters,
        )
        if self._verbose:
            print("async_infer\n{}".format(request))
        future = self._stub.ModelInfer.future(
            request=request,
            metadata=self._metadata(headers),
            timeout=client_timeout,
        )

        def done(fut):
            try:
                response = fut.result()
                if self._verbose:
                    print(response)
                callback(InferResult(response), None)
            except grpc.RpcError as rpc_error:
                callback(None, get_error_grpc(rpc_error))
            except Exception as e:
                callback(None, InferenceServerException(str(e)))

        future.add_done_callback(done)
        return future

    # -- streaming ---------------------------------------------------------

    def start_stream(
        self, callback, stream_timeout=None, headers=None,
        compression_algorithm=None,
    ):
        """Open the bidirectional ModelStreamInfer stream; responses (and
        stream errors) are delivered to ``callback(result, error)``
        (reference grpc/_client.py:1520)."""
        if self._stream is not None:
            raise_error(
                "cannot start another stream with one already active"
            )
        self._stream = _InferStream(callback, self._verbose)
        try:
            response_iterator = self._stub.ModelStreamInfer(
                self._stream._request_iterator,
                metadata=self._metadata(headers),
                timeout=stream_timeout,
                compression=compression_algorithm,
            )
            self._stream._init_handler(response_iterator)
        except grpc.RpcError as rpc_error:
            self._stream = None
            raise_error_grpc(rpc_error)

    def stop_stream(self, cancel_requests=False):
        """Close the active stream, if any."""
        if self._stream is not None:
            self._stream.close(cancel_requests)
            self._stream = None

    def async_stream_infer(
        self,
        model_name,
        inputs,
        model_version="",
        outputs=None,
        request_id="",
        sequence_id=0,
        sequence_start=False,
        sequence_end=False,
        enable_empty_final_response=False,
        priority=0,
        timeout=None,
        parameters=None,
    ):
        """Enqueue a request on the active stream (reference
        grpc/_client.py:1586)."""
        if self._stream is None:
            raise_error("stream not available, use start_stream() first")
        request = _get_inference_request(
            model_name=model_name,
            inputs=inputs,
            model_version=model_version,
            request_id=request_id,
            outputs=outputs,
            sequence_id=sequence_id,
            sequence_start=sequence_start,
            sequence_end=sequence_end,
            priority=priority,
            timeout=timeout,
            parameters=parameters,
        )
        if enable_empty_final_response:
            request.parameters[
                "triton_enable_empty_final_response"
            ].bool_param = True
        if self._verbose:
            print("async_stream_infer\n{}".format(request))
        self._stream._enqueue_request(request)

    def generate_stream(
        self,
        model_name,
        inputs,
        model_version="",
        outputs=None,
        request_id="",
        parameters=None,
        headers=None,
        resume=True,
        max_reconnects=5,
        reconnect_backoff_s=0.05,
        read_timeout=600.0,
        on_reconnect=None,
        fallback_urls=None,
    ):
        """Synchronous generator over ONE decoupled generation with
        transparent reconnect+resume, yielding an ``InferResult`` per
        streamed token (the terminal empty-final response is
        consumed, not yielded).

        The call declares (``multi_token_responses``) that it reads
        responses carrying several tokens: a server may then send every
        token of the generation already waiting in one response, which
        is parsed once and yielded a token at a time, each result with
        its token's own ``seq``, as one-token responses give them.

        ``fallback_urls`` (``host:port`` peers — a respawned server on
        a new address, or sibling endpoints fronting the same fleet)
        makes each reconnect attempt rotate through the target list by
        re-binding the channel (insecure channels only): a
        connect-refused primary retries the resume against the peer
        under the same ``max_reconnects`` + backoff budget, because
        behind a resilient fleet seq continuity — not endpoint
        identity — is the resume contract.

        Owns the client's single bidi-stream slot for the call's
        duration (``start_stream`` semantics — raises if a stream is
        already active).  Each response of a resumable server
        generation carries ``generation_id`` and the 0-based token
        ``seq`` in its response parameters; on a *stream-level* failure
        (RpcError — the transport died) the call re-opens the stream
        and sends a resume request (``resume_generation_id`` +
        ``resume_from_seq``), the server replays the missed tokens and
        splices the live continuation, and duplicates are dropped by
        ``seq`` — no duplicated or missing tokens.  Resume is
        **same-endpoint only** (replay state is replica-local).
        In-band ``error_message`` responses raise immediately — those
        are typed server failures (quarantined slot, expired resume
        id), not transport faults.  ``on_reconnect(attempt, exc)``
        fires before each reattempt.

        A model that generates by diffusion over blocks sends one
        response a FINISHED BLOCK: ``TOKEN``, ``LOGPROB``, ``POSITION``
        and ``UNMASK_PASS``, each of the block's length, in position
        order; ``parameters={"denoising_steps": n,
        "confidence_threshold": tau}`` are its per-request settings, and
        ``seq`` counts blocks.  It does not replay a dropped stream
        yet: call with ``resume=False``."""
        if self._stream is not None:
            raise_error(
                "cannot generate_stream with a stream already active"
            )
        base_params = dict(parameters or {})
        gen_id = base_params.get("generation_id")
        # reconnect target rotation (attempt N re-binds the channel to
        # targets[N % len]); validated up front so a bad url fails the
        # call, not a mid-generation reconnect
        targets = [self._url]
        for fb in fallback_urls or ():
            if not isinstance(fb, str) or ":" not in fb:
                raise_error(
                    "fallback_urls entries must be host:port strings "
                    "(got {!r})".format(fb))
            targets.append(fb)
        if len(targets) > 1 and self._secure:
            raise_error(
                "fallback_urls requires insecure channels (per-url TLS "
                "material cannot be assumed to transfer)")

        class _StreamDropped(Exception):
            def __init__(self, error):
                self.error = error

        try:
            yield from self._generate_stream_rotating(
                targets, model_name, inputs, model_version, outputs,
                request_id, base_params, headers, resume,
                max_reconnects, reconnect_backoff_s, read_timeout,
                on_reconnect, gen_id, _StreamDropped)
        finally:
            # the rotation must not outlive the call: a client left
            # bound to the last fallback would silently route every
            # later RPC (and its owner pool's breaker accounting) at
            # the wrong endpoint
            if len(targets) > 1:
                self._rebind(targets[0])

    def _generate_stream_rotating(
            self, targets, model_name, inputs, model_version, outputs,
            request_id, base_params, headers, resume, max_reconnects,
            reconnect_backoff_s, read_timeout, on_reconnect, gen_id,
            _StreamDropped):
        last_seq = -1
        yielded_any = False
        attempt = 0
        while True:
            if len(targets) > 1:
                self._rebind(targets[attempt % len(targets)])
            send_params = dict(base_params)
            # this reader takes a response of several tokens apart
            send_params["multi_token_responses"] = True
            sent_resume = gen_id is not None and last_seq >= 0
            if sent_resume:
                # mid-generation reconnect: ask the server to replay
                # from the first seq we have not seen
                send_params.pop("generation_id", None)
                send_params["resume_generation_id"] = gen_id
                send_params["resume_from_seq"] = last_seq + 1
            request = _get_inference_request(
                model_name=model_name,
                inputs=inputs,
                model_version=model_version,
                request_id=request_id,
                outputs=outputs,
                parameters=send_params,
            )
            request.parameters[
                "triton_enable_empty_final_response"
            ].bool_param = True
            if self._verbose:
                print("generate_stream\n{}".format(request))
            # one request, then the half-close: grpc sends both and the
            # server's request side ends there; the caller's own thread
            # reads the call, so a response crosses no thread and no
            # queue of this client on its way here
            self._stream = _PulledStream(
                self._stub.ModelStreamInfer(
                    iter((request,)), metadata=self._metadata(headers)),
                read_timeout, self._verbose)
            try:
                try:
                    for response in self._stream:
                        if response.error_message:
                            error = InferenceServerException(
                                response.error_message)
                            if (sent_resume and "unknown or expired "
                                    "generation id" in str(error)):
                                # OUR resume named a generation this
                                # server does not (yet) hold — under a
                                # fleet router that's a transition
                                # (restart, handoff in progress), not a
                                # verdict: seq continuity is the resume
                                # contract, not endpoint identity, so
                                # ride the reconnect path bounded by
                                # max_reconnects
                                raise _StreamDropped(error)
                            # in-band server error: terminal
                            raise error
                        resp = response.infer_response
                        final = resp.parameters.get("triton_final_response")
                        if final is not None and final.bool_param:
                            return
                        if "generation_id" in resp.parameters:
                            gen_id = resp.parameters[
                                "generation_id"].string_param
                        seq = (resp.parameters["seq"].int64_param
                               if "seq" in resp.parameters else None)
                        for n, result in enumerate(token_results(resp)):
                            if seq is not None:
                                if seq + n <= last_seq:
                                    continue  # replayed duplicate
                                last_seq = seq + n
                            yielded_any = True
                            yield result
                    return  # the server ended the call
                except grpc.RpcError as rpc_error:
                    # the transport died (refused, reset, aborted by the
                    # server): in-band errors never end the call this way
                    raise _StreamDropped(get_error_grpc(rpc_error))
            except _StreamDropped as drop:
                # resume is only safe with a resume token (the server
                # marked the generation resumable) OR before anything
                # was delivered (a fresh re-send cannot duplicate);
                # re-running a non-resumable generation after yielding
                # tokens would duplicate them
                attempt += 1
                if (not resume or attempt > max_reconnects
                        or (yielded_any and (gen_id is None
                                             or last_seq < 0))):
                    if yielded_any and (gen_id is None or last_seq < 0):
                        raise InferenceServerException(
                            "stream lost mid-generation and the "
                            "generation is not resumable (no "
                            "generation_id/seq on its responses): "
                            "{}".format(drop.error))
                    raise drop.error
                if on_reconnect is not None:
                    on_reconnect(attempt, drop.error)
                time.sleep(
                    min(reconnect_backoff_s * (2 ** (attempt - 1)), 2.0))
            finally:
                self.stop_stream(cancel_requests=True)
