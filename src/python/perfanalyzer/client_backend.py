"""Backend-neutral client abstraction for the load managers.

Role of the reference's ``client_backend/`` layer
(client_backend.h:250-620): the load managers and profiler speak one
small interface; four concrete backends map it onto the stack's real
entry points:

- ``http``      — ``tritonclient.http`` against a live HTTP frontend
- ``grpc``      — ``tritonclient.grpc`` against a live gRPC frontend
- ``inprocess`` — wraps ``tpuserver.core.InferenceServer`` directly
                  (the analogue of the reference's Triton C-API
                  backend: no sockets, so the client/transport overhead
                  is isolated from the model cost)
- ``pool``      — drives ``tritonclient.EndpointPool`` over N replica
                  URLs, so failover/hedging behavior can be load-tested

A backend hands out *prepared* requests (inputs pre-serialized once,
outside the timed path), executes them synchronously (``infer``) or
asynchronously (``submit`` + completion callback — what the
concurrency manager's context free-list rides on), snapshots server
statistics, and — where the transport supports decoupled models —
streams generations token-by-token for the generation profiler.
"""

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np


class BackendError(Exception):
    """A request failed inside a backend (wraps the transport error)."""


def _coerce_int(value, default=0):
    try:
        return int(value)
    except (TypeError, ValueError):
        return default


class ClientBackend:
    """The interface the load managers and profiler consume.

    ``capacity`` is the backend's true in-flight ceiling (executor
    threads / pooled connections), or None when the transport
    multiplexes without a fixed bound (gRPC async).  Size it via
    ``max_inflight`` at construction: a load level above the capacity
    would silently measure the backend's own queueing, not the server.
    """

    kind = "?"
    supports_generation = False

    def __init__(self, max_inflight=None):
        self._executor = None
        self._executor_lock = threading.Lock()
        # an explicit bound is honored EXACTLY (a user capping
        # outstanding requests means it); only the unspecified case
        # gets the roomy default
        self._executor_workers = (max(1, int(max_inflight))
                                  if max_inflight else 64)
        self.capacity = self._executor_workers

    # -- metadata / statistics --------------------------------------------

    def model_metadata(self, model):
        raise NotImplementedError

    def model_config(self, model):
        raise NotImplementedError

    def server_statistics(self, model):
        """Cumulative stats dict ``{"model_stats": [...]}`` (KServe
        statistics extension shape, both clients' native form)."""
        raise NotImplementedError

    def stats_snapshot(self, model):
        """Flat cumulative-counter snapshot for the profiler's window
        diffs (see :func:`perfanalyzer.metrics.server_stats_snapshot`).
        Multi-replica backends override to attach per-replica data so
        deltas can be paired replica-by-replica."""
        from perfanalyzer.metrics import server_stats_snapshot

        return server_stats_snapshot(self.server_statistics(model), model)

    def router_snapshot(self):
        """Cumulative fleet-router counters (``failovers``,
        ``handoffs``, ``resumed_streams``, ``shed``) when the target is
        a ``tpuserver.router.FleetRouter``, else None.  Only transports
        that can reach ``/router/stats`` override this — the profiler
        diffs the snapshot per load level so router-absorbed faults
        surface in the report next to ``resumed_streams``."""
        return None

    def prefix_cache_snapshot(self):
        """Cumulative radix prefix-cache counters ``{"hits": tokens,
        "misses": tokens}`` from the target's telemetry
        (``tpu_prefix_cache_*_total``), or None when the transport
        cannot reach them.  Against a fleet router the counters are
        the churn-safe FLEET aggregate, so the generation profiler's
        window delta is the fleet-wide hit rate — the number that
        proves prefix-affinity routing keeps sibling prompts on warm
        replicas."""
        return None

    # -- inference --------------------------------------------------------

    def prepare(self, model, input_sets):
        """Pre-serialize ``input_sets`` (list of name->np.ndarray dicts)
        into backend-native request handles.  Runs once per load level,
        OUTSIDE any measurement window — the timed path then only
        dispatches."""
        return [self._prepare_one(model, s) for s in input_sets]

    def _prepare_one(self, model, inputs):
        raise NotImplementedError

    def infer(self, prepared):
        """Synchronous inference of one prepared request; raises
        :class:`BackendError` on failure."""
        raise NotImplementedError

    def submit(self, prepared, on_done):
        """Non-blocking dispatch; ``on_done(error_or_None)`` fires on a
        completion thread.  Default implementation runs :meth:`infer`
        on a shared executor; backends with native async (gRPC)
        override."""
        executor = self._ensure_executor()

        def run():
            try:
                self.infer(prepared)
            except Exception as e:  # noqa: BLE001 — handed to on_done
                on_done(e)
                return
            on_done(None)

        executor.submit(run)

    def _ensure_executor(self):
        if self._executor is None:
            with self._executor_lock:
                if self._executor is None:
                    self._executor = ThreadPoolExecutor(
                        max_workers=self._executor_workers,
                        thread_name_prefix="perfanalyzer-" + self.kind,
                    )
        return self._executor

    # -- generation (decoupled streaming) ---------------------------------

    def generate_stream(self, model, inputs, parameters=None, stats=None):
        """Generator yielding the token count of each streamed response
        as it arrives (1 for the llama TOKEN-per-response contract).
        The generation profiler timestamps each yield: first yield =
        TTFT, gaps = inter-token latencies.

        ``stats`` (optional dict) receives per-stream bookkeeping the
        profiler folds into its report: backends that transparently
        reconnect+resume a dropped stream bump ``stats["resumes"]`` per
        reconnect, so under-chaos runs surface degradation instead of
        silently re-splicing broken streams."""
        raise NotImplementedError(
            "backend '{}' does not support generation mode".format(
                self.kind))

    def release_thread_resources(self):
        """Called by a generation worker as it exits; backends that
        pin per-thread resources (the gRPC stream client) free them
        here so swept levels don't accumulate channels."""

    # -- shared-memory data plane -----------------------------------------

    def shm_register(self, name, kind, key=None, raw_handle=None,
                     byte_size=0, device_ordinal=0):
        """Register a client-created region (``kind`` 'system' or
        'xla') with the serving target."""
        raise NotImplementedError(
            "backend '{}' does not support shared memory".format(self.kind))

    def shm_unregister(self, name, kind):
        raise NotImplementedError(
            "backend '{}' does not support shared memory".format(self.kind))

    def prepare_shm(self, model, input_refs, output_refs=None):
        """Prepared requests whose inputs are :func:`shm_input_ref`
        descriptors (one dict per input set) and whose outputs land in
        shared memory (``output_refs``: list of ``(name, region,
        byte_size, offset)``), for :meth:`infer`/``submit``."""
        raise NotImplementedError(
            "backend '{}' does not support shared memory".format(self.kind))

    def close(self):
        if self._executor is not None:
            self._executor.shutdown(wait=False)
            self._executor = None


def _np_wire_dtype(arr):
    from tritonclient.utils import np_to_triton_dtype

    if arr.dtype == np.object_:
        return "BYTES"
    return np_to_triton_dtype(arr.dtype)


def shm_input_ref(region, byte_size, offset, datatype, shape):
    """A shared-memory input reference: the value a prepared request
    carries instead of tensor bytes (the wire then moves ~40 bytes of
    descriptor where the data plane moves the tensor).  Understood by
    every backend's prepare/generate path and by the clients'
    ``generate_stream``."""
    return {
        "shared_memory_region": region,
        "shared_memory_byte_size": int(byte_size),
        "shared_memory_offset": int(offset),
        "datatype": datatype,
        "shape": list(shape),
    }


def _is_shm_ref(value):
    return isinstance(value, dict) and "shared_memory_region" in value


def _prepare_infer_inputs(mod, inputs, binary_data=None):
    """Shared input serialization for the socket backends: one
    ``InferInput`` per tensor, dtype mapped once (``binary_data`` is
    the HTTP wire toggle; gRPC's set_data_from_numpy takes no such
    argument).  A :func:`shm_input_ref` value becomes a shared-memory
    reference instead of inline bytes."""
    prepared = []
    for name, arr in inputs.items():
        if _is_shm_ref(arr):
            tin = mod.InferInput(name, list(arr["shape"]), arr["datatype"])
            tin.set_shared_memory(
                arr["shared_memory_region"],
                arr["shared_memory_byte_size"],
                arr.get("shared_memory_offset", 0))
            prepared.append(tin)
            continue
        tin = mod.InferInput(name, list(arr.shape), _np_wire_dtype(arr))
        if binary_data is None:
            tin.set_data_from_numpy(arr)
        else:
            tin.set_data_from_numpy(arr, binary_data=binary_data)
        prepared.append(tin)
    return prepared


def _response_token_count(outputs):
    """Tokens carried by one decoupled response, from its output list
    (dicts with name/shape).  Prefer a TOKEN/OUTPUT_IDS tensor's
    element count; fall back to 1 response = 1 step."""
    for entry in outputs or []:
        if entry.get("name") in ("TOKEN", "OUTPUT_IDS", "output_ids"):
            n = 1
            for d in entry.get("shape", []) or []:
                n *= max(1, _coerce_int(d, 1))
            data = entry.get("data")
            if isinstance(data, list) and data:
                n = len(data)
            return max(1, n)
    return 1


# -- in-process backend ----------------------------------------------------


class InProcessBackend(ClientBackend):
    """Drives ``tpuserver.core.InferenceServer`` with no transport at
    all — the floor every other backend's overhead is measured against
    (the reference's C-API backend role)."""

    kind = "inprocess"
    supports_generation = True

    def __init__(self, core, max_inflight=None):
        super().__init__(max_inflight)
        self.core = core

    def model_metadata(self, model):
        return self.core.model_metadata(model)

    def model_config(self, model):
        return self.core.model_config(model)

    def server_statistics(self, model):
        return self.core.model_statistics(model)

    def _prepare_one(self, model, inputs):
        from tpuserver.core import InferRequest

        return InferRequest(model, inputs=dict(inputs))

    def shm_register(self, name, kind, key=None, raw_handle=None,
                     byte_size=0, device_ordinal=0):
        from tpuserver.core import ServerError

        try:
            if kind == "system":
                self.core.register_system_shm(name, key, 0, byte_size)
            else:
                self.core.register_xla_shm(
                    name, raw_handle, device_ordinal, byte_size)
        except ServerError as e:
            raise BackendError(str(e)) from e

    def shm_unregister(self, name, kind):
        from tpuserver.core import ServerError

        try:
            if kind == "system":
                self.core.unregister_system_shm(name)
            else:
                self.core.unregister_xla_shm(name)
        except ServerError as e:
            raise BackendError(str(e)) from e

    def prepare_shm(self, model, input_refs, output_refs=None):
        return [("shm", model, dict(refs), list(output_refs or []))
                for refs in input_refs]

    def _resolve_refs(self, inputs):
        """Materialize shm references through the core's bounds-checked
        resolve path — for an in-process XLA region this returns the
        live device segment itself: the zero-copy plane."""
        out = {}
        for name, value in inputs.items():
            if _is_shm_ref(value):
                out[name] = self.core.read_shm_input(
                    value["shared_memory_region"],
                    value["shared_memory_byte_size"],
                    value.get("shared_memory_offset", 0),
                    value["datatype"],
                    value["shape"])
            else:
                out[name] = value
        return out

    def infer(self, prepared):
        from tpuserver.core import (
            InferRequest,
            RequestedOutput,
            ServerError,
        )

        try:
            if isinstance(prepared, tuple) and prepared[0] == "shm":
                _, model, refs, out_refs = prepared
                requested = None
                if out_refs:
                    requested = [
                        RequestedOutput(
                            n, binary_data=False, shm_region=region,
                            shm_byte_size=size, shm_offset=offset)
                        for n, region, size, offset in out_refs
                    ]
                req = InferRequest(
                    model, inputs=self._resolve_refs(refs),
                    requested_outputs=requested)
                self.core.infer(req)
                return
            # a fresh request object per call: InferRequest carries
            # per-call deadline state the core stamps on it
            req = InferRequest(prepared.model_name,
                               inputs=prepared.inputs)
            self.core.infer(req)
        except ServerError as e:
            raise BackendError(str(e)) from e

    def generate_stream(self, model, inputs, parameters=None, stats=None):
        from tpuserver.core import InferRequest, ServerError

        try:
            req = InferRequest(model, inputs=self._resolve_refs(inputs),
                               parameters=dict(parameters or {}))
            for resp in self.core.infer_stream(req):
                yield _response_token_count(
                    [spec for spec, _, _ in resp.outputs])
        except ServerError as e:
            raise BackendError(str(e)) from e

    def prefix_cache_snapshot(self):
        hits = misses = 0
        seen = False
        for stats in (self.core.health_snapshot().get("models")
                      or {}).values():
            if isinstance(stats, dict) and "prefix_hits" in stats:
                seen = True
                hits += _coerce_int(stats.get("prefix_hits"))
                misses += _coerce_int(stats.get("prefix_misses"))
        return {"hits": hits, "misses": misses} if seen else None


# -- socket-backend shared shm support --------------------------------------


class _TritonClientShmMixin:
    """Shared-memory support for the socket backends: the tritonclient
    http/grpc APIs are name-identical (register/unregister verbs,
    ``InferInput.set_shared_memory``, ``InferRequestedOutput``), so the
    register/unregister/prepare/infer plumbing lives once here —
    ``self.client`` is the transport client, ``self._mod`` its module."""

    def shm_register(self, name, kind, key=None, raw_handle=None,
                     byte_size=0, device_ordinal=0):
        from tritonclient.utils import InferenceServerException

        try:
            if kind == "system":
                self.client.register_system_shared_memory(
                    name, key, byte_size)
            else:
                self.client.register_xla_shared_memory(
                    name, raw_handle, device_ordinal, byte_size)
        except InferenceServerException as e:
            raise BackendError(str(e)) from e

    def shm_unregister(self, name, kind):
        from tritonclient.utils import InferenceServerException

        try:
            if kind == "system":
                self.client.unregister_system_shared_memory(name)
            else:
                self.client.unregister_xla_shared_memory(name)
        except InferenceServerException as e:
            raise BackendError(str(e)) from e

    def prepare_shm(self, model, input_refs, output_refs=None):
        prepared = []
        for refs in input_refs:
            tins = _prepare_infer_inputs(self._mod, refs)
            touts = None
            if output_refs:
                touts = []
                for name, region, size, offset in output_refs:
                    tout = self._mod.InferRequestedOutput(name)
                    tout.set_shared_memory(region, size, offset)
                    touts.append(tout)
            prepared.append((model, tins, touts))
        return prepared

    def infer(self, prepared):
        from tritonclient.utils import InferenceServerException

        model, infer_inputs = prepared[0], prepared[1]
        outputs = prepared[2] if len(prepared) > 2 else None
        try:
            if outputs is not None:
                self.client.infer(model, infer_inputs, outputs=outputs)
            else:
                self.client.infer(model, infer_inputs)
        except InferenceServerException as e:
            raise BackendError(str(e)) from e


# -- HTTP backend ----------------------------------------------------------

#: under-chaos reconnect budget for generation streams: the client
#: library's default 5-attempt budget backs off for ~1.5 s total,
#: which a supervised fleet's process-heal window outlasts when kill
#: faults COMPOSE (prefill + decode replica SIGKILLed in one campaign
#: cycle: two serial respawns + router re-admission).  Perf streams
#: must ride the heal out — the degradation is already reported as
#: resumed_streams/resume_events, never as a user-visible error
#: (found by tools/chaos_campaign.py --proof seed 10, pinned in
#: tests/test_chaos_campaign.py).
GENERATION_MAX_RECONNECTS = 10


class HttpBackend(_TritonClientShmMixin, ClientBackend):
    """``tritonclient.http`` against a live frontend; generation rides
    the ``/v2/models/{m}/generate_stream`` SSE endpoint."""

    kind = "http"
    supports_generation = True

    def __init__(self, url, max_inflight=None):
        super().__init__(max_inflight)
        import tritonclient.http as httpclient

        self._mod = httpclient
        self.url = url
        # the pooled-connection count must match the executor: fewer
        # connections than workers and requests queue INSIDE the
        # client, polluting the measured latency
        self.client = httpclient.InferenceServerClient(
            url, concurrency=self._executor_workers)
        # tri-state: None = not yet probed, False = target is a plain
        # replica (the 404 verdict is cached), True = fleet router
        self._is_router = None

    def _http_get(self, path):
        """One raw GET against the target's host:port, outside the
        triton client (these probe NON-KServe surfaces: /router/stats,
        /metrics).  Returns ``(status, body_bytes)``, or None on a
        port-less url or a transport/protocol error — the shared
        plumbing of every snapshot probe on this backend."""
        import http.client as _http_client

        host, sep, port = self.url.rpartition(":")
        if not sep or not port.isdigit():
            return None
        conn = _http_client.HTTPConnection(host, int(port), timeout=5)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read()
        except (OSError, ValueError, _http_client.HTTPException):
            return None
        finally:
            conn.close()

    def router_snapshot(self):
        """``/router/stats`` counters when the url fronts a
        FleetRouter; a plain replica answers 404 once and is never
        probed again."""
        if self._is_router is False:
            return None
        import json as _json

        got = self._http_get("/router/stats")
        if got is None:
            # port-less url can never reach a router: latch; a
            # transport error is transient: do not latch the verdict
            host, sep, port = self.url.rpartition(":")
            if not sep or not port.isdigit():
                self._is_router = False
            return None
        status, body = got
        if status != 200:
            self._is_router = False
            return None
        try:
            snap = _json.loads(body)
        except ValueError:
            return None
        self._is_router = True
        out = {
            "failovers": _coerce_int(snap.get("failovers")),
            "handoffs": _coerce_int(snap.get("handoffs")),
            "resumed_streams": _coerce_int(snap.get("resumed_streams")),
            "shed": _coerce_int(snap.get("shed")),
        }
        # tail-latency defense + router-HA counters: present only on
        # routers that carry them, so the delta attach can tell "zero
        # events" from "router predates the counters"
        for key in ("ejections", "hedges", "takeovers",
                    "recovered_generations"):
            if key in snap:
                out[key] = _coerce_int(snap.get(key))
        supervisor = snap.get("supervisor")
        if isinstance(supervisor, dict):
            # the router fronts a supervised fleet: its process-level
            # healing/scaling counters window-diff exactly like the
            # router's own (metrics.SUPERVISOR_COUNTERS)
            for key in ("replica_restarts", "scale_up_events",
                        "scale_down_events", "retired_replicas",
                        # crash-durability counters (ISSUE 18):
                        # presence-guarded like the rest so a
                        # supervisor predating the manifest never
                        # fabricates a delta
                        "adoptions", "clean_handovers",
                        "stale_children_reaped", "manifest_records"):
                if key in supervisor:
                    out["supervisor_" + key] = _coerce_int(
                        supervisor.get(key))
        return out

    def prefix_cache_snapshot(self):
        """The target's ``/metrics`` prefix-cache counters summed
        across label sets — against a router this is the fleet
        aggregate (replica restarts and churn already folded in)."""
        from tpuserver.metrics import parse_prometheus_text

        got = self._http_get("/metrics")
        if got is None or got[0] != 200:
            return None
        families = parse_prometheus_text(
            got[1].decode("utf-8", errors="replace"))
        out = {}
        for key, fam_name in (("hits", "tpu_prefix_cache_hits_total"),
                              ("misses", "tpu_prefix_cache_misses_total")):
            fam = families.get(fam_name)
            if fam is None:
                return None  # pre-paging server: no column
            out[key] = int(sum(v for _, _, v in fam["samples"]))
        return out

    def model_metadata(self, model):
        return self.client.get_model_metadata(model)

    def model_config(self, model):
        return self.client.get_model_config(model)

    def server_statistics(self, model):
        return self.client.get_inference_statistics(model)

    def _prepare_one(self, model, inputs):
        return (model, _prepare_infer_inputs(
            self._mod, inputs, binary_data=True))

    def generate_stream(self, model, inputs, parameters=None, stats=None):
        """Stream over /generate_stream SSE via the client's resumable
        path: a connection dropped mid-generation transparently
        reconnects with ``Last-Event-ID`` and splices (same-endpoint
        resume); every reconnect is counted into ``stats["resumes"]``
        so chaos runs report ``resumed_streams`` instead of silently
        hiding the degradation."""
        from tritonclient.utils import InferenceServerException

        def on_reconnect(attempt, exc):
            if stats is not None:
                stats["resumes"] = stats.get("resumes", 0) + 1

        try:
            for event in self.client.generate_stream(
                    model, dict(inputs),
                    parameters=dict(parameters or {}),
                    max_reconnects=GENERATION_MAX_RECONNECTS,
                    on_reconnect=on_reconnect):
                yield _response_token_count(event.get("outputs"))
        except InferenceServerException as e:
            raise BackendError(str(e)) from e

    def close(self):
        super().close()
        self.client.close()


# -- gRPC backend ----------------------------------------------------------


class GrpcBackend(_TritonClientShmMixin, ClientBackend):
    """``tritonclient.grpc``; ``submit`` uses the client's native
    completion-callback async path (no extra thread per in-flight
    request), and generation rides a decoupled bidi stream."""

    kind = "grpc"
    supports_generation = True

    def __init__(self, url, max_inflight=None):
        super().__init__(max_inflight)
        # native async callbacks: the channel multiplexes without a
        # fixed in-flight ceiling
        self.capacity = None
        import tritonclient.grpc as grpcclient

        self._mod = grpcclient
        self.url = url
        self.client = grpcclient.InferenceServerClient(url)
        # generation streams are per-thread: one gRPC client owns at
        # most one bidi stream, and generation workers run concurrently
        self._stream_local = threading.local()
        self._stream_clients = []  # guarded-by: _stream_clients_lock
        self._stream_clients_lock = threading.Lock()

    def model_metadata(self, model):
        return self.client.get_model_metadata(model, as_json=True)

    def model_config(self, model):
        cfg = self.client.get_model_config(model, as_json=True)
        return cfg.get("config", cfg)

    def server_statistics(self, model):
        return self.client.get_inference_statistics(model, as_json=True)

    def _prepare_one(self, model, inputs):
        return (model, _prepare_infer_inputs(self._mod, inputs))

    def submit(self, prepared, on_done):
        model, infer_inputs = prepared[0], prepared[1]
        outputs = prepared[2] if len(prepared) > 2 else None

        def callback(result, error):
            on_done(error)

        if outputs is not None:
            self.client.async_infer(
                model, infer_inputs, callback, outputs=outputs)
        else:
            self.client.async_infer(model, infer_inputs, callback)

    def _thread_client(self):
        client = getattr(self._stream_local, "client", None)
        if client is None:
            client = self._mod.InferenceServerClient(self.url)
            self._stream_local.client = client
            with self._stream_clients_lock:
                self._stream_clients.append(client)
        return client

    def release_thread_resources(self):
        # a generation worker's thread-local channel dies with the
        # worker: a 1:64 sweep would otherwise hold every past level's
        # channels open until backend.close()
        client = getattr(self._stream_local, "client", None)
        if client is None:
            return
        self._stream_local.client = None
        with self._stream_clients_lock:
            try:
                self._stream_clients.remove(client)
            except ValueError:
                pass
        try:
            client.close()
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass

    def generate_stream(self, model, inputs, parameters=None, stats=None):
        """Decoupled bidi stream via the client's resumable path: a
        stream-level drop re-opens the stream with a resume token and
        splices (same-endpoint resume); reconnects are counted into
        ``stats["resumes"]`` for the profiler's ``resumed_streams``."""
        from tritonclient.utils import InferenceServerException

        client = self._thread_client()
        prepared = self._prepare_one(model, inputs)[1]

        def on_reconnect(attempt, exc):
            if stats is not None:
                stats["resumes"] = stats.get("resumes", 0) + 1

        try:
            for result in client.generate_stream(
                    model, prepared,
                    parameters=dict(parameters) if parameters else None,
                    max_reconnects=GENERATION_MAX_RECONNECTS,
                    on_reconnect=on_reconnect):
                resp = result.get_response()
                yield _response_token_count([
                    {"name": out.name, "shape": list(out.shape)}
                    for out in resp.outputs
                ])
        except InferenceServerException as e:
            raise BackendError(str(e)) from e

    def close(self):
        super().close()
        with self._stream_clients_lock:
            clients, self._stream_clients = self._stream_clients, []
        for client in clients:
            try:
                client.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        self.client.close()


# -- multi-replica pool backend --------------------------------------------


class PoolBackend(ClientBackend):
    """Drives ``tritonclient.EndpointPool`` over N replica URLs, so the
    failover/hedging layer itself can be put under measured load.

    Server statistics are summed across ALL replicas (each queried
    directly): the pool spreads requests over the fleet, so a single
    endpoint's counters would undercount the window.
    """

    kind = "pool"
    supports_generation = False

    def __init__(self, urls, max_inflight=None, **pool_kwargs):
        super().__init__(max_inflight)
        import tritonclient.http as httpclient

        self._mod = httpclient
        self.urls = list(urls)
        self.pool = httpclient.EndpointPool(self.urls, **pool_kwargs)
        # direct per-replica clients for statistics aggregation only
        self._stat_clients = [
            httpclient.InferenceServerClient(u) for u in self.urls
        ]

    def model_metadata(self, model):
        return self.pool.get_model_metadata(model)

    def model_config(self, model):
        return self.pool.get_model_config(model)

    def _per_replica_snapshots(self, model):
        from perfanalyzer.metrics import server_stats_snapshot

        snaps = {}
        for url, client in zip(self.urls, self._stat_clients):
            try:
                snaps[url] = server_stats_snapshot(
                    client.get_inference_statistics(model), model)
            except Exception:  # noqa: BLE001 — a drained/dead replica
                # must not abort the profile: load-testing failover IS
                # this backend's purpose; the delta pairing in
                # metrics.server_stats_delta drops replicas missing
                # from either end of a window.
                continue
        return snaps

    def stats_snapshot(self, model):
        """Summed flat snapshot PLUS the per-replica map: window deltas
        pair each replica with itself, so a replica dying or reviving
        mid-window never subtracts/adds its lifetime counters into one
        window's delta."""
        from perfanalyzer.metrics import zero_snapshot

        snaps = self._per_replica_snapshots(model)
        total = zero_snapshot()
        for snap in snaps.values():
            for key, val in snap.items():
                total[key] += val
        total["_replicas"] = snaps
        return total

    def server_statistics(self, model):
        # summed model_stats shape for API parity with the other
        # backends (the profiler itself uses stats_snapshot)
        total = self.stats_snapshot(model)
        merged = {
            "name": model,
            "inference_count": total["inference_count"],
            "execution_count": total["execution_count"],
            "inference_stats": {
                key: {
                    "count": total[key + "_count"],
                    "ns": total[key + "_ns"],
                }
                for key in ("success", "fail", "queue", "compute_input",
                            "compute_infer", "compute_output")
            },
        }
        return {"model_stats": [merged]}

    def _prepare_one(self, model, inputs):
        return (model, _prepare_infer_inputs(
            self._mod, inputs, binary_data=True))

    def infer(self, prepared):
        from tritonclient.utils import InferenceServerException

        model, infer_inputs = prepared
        try:
            self.pool.infer(model, infer_inputs)
        except InferenceServerException as e:
            raise BackendError(str(e)) from e

    def close(self):
        super().close()
        for client in self._stat_clients:
            try:
                client.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        self.pool.close()


# -- shared-memory infer-data manager ---------------------------------------


class ShmInferDataManager:
    """Client-side shared-memory staging for one perf_analyzer worker
    (role of the reference's ``InferDataManagerShm``): every input set
    of the rotation pool is written ONCE into a created-and-registered
    region outside any measurement window; the prepared requests then
    carry ``{region, offset}`` references, so the timed wire moves
    ~40-byte descriptors while the tensors ride the shm data plane.
    ``kind='xla'`` regions also park device segments — against an
    in-process server the resolve path returns the live ``jax.Array``
    itself (zero host copies).

    Region names are namespaced by a per-worker ``tag`` (default: the
    pid plus a random suffix), so N distributed workers driving one
    server never collide; :meth:`close` unregisters and unlinks every
    region this manager created — the per-worker region lifecycle.
    """

    def __init__(self, backend, kind, tag=None):
        if kind not in ("system", "xla"):
            raise ValueError(
                "shared-memory kind must be 'system' or 'xla' "
                "(got {!r})".format(kind))
        import os as _os
        import uuid as _uuid

        self.backend = backend
        self.kind = kind
        self.tag = "{}_{}".format(
            tag if tag is not None else _os.getpid(),
            _uuid.uuid4().hex[:6])
        self._regions = []  # (name, handle)

    # -- region lifecycle --------------------------------------------------

    def create_region(self, label, byte_size):
        """Create + register one region; returns ``(name, handle)``.
        The handle stays client-owned (this side reads rings / output
        regions through it)."""
        name = "pa_{}_{}".format(self.tag, label)
        if self.kind == "system":
            from tritonclient.utils import shared_memory as sysshm

            key = "/" + name
            handle = sysshm.create_shared_memory_region(
                name, key, byte_size)
            try:
                self.backend.shm_register(
                    name, "system", key=key, byte_size=byte_size)
            except Exception:
                sysshm.destroy_shared_memory_region(handle)
                raise
        else:
            from tritonclient.utils import xla_shared_memory as xshm

            handle = xshm.create_shared_memory_region(name, byte_size)
            try:
                self.backend.shm_register(
                    name, "xla", raw_handle=xshm.get_raw_handle(handle),
                    byte_size=byte_size)
            except Exception:
                xshm.destroy_shared_memory_region(handle)
                raise
        self._regions.append((name, handle))
        return name, handle

    def write(self, handle, arrays, offset=0):
        """Stage arrays at ``offset`` — for xla regions as device
        arrays when jax is importable (the zero-copy in-process form;
        the host window syncs automatically for a cross-process
        server), host bytes otherwise."""
        if self.kind == "system":
            from tritonclient.utils import shared_memory as sysshm

            sysshm.set_shared_memory_region(handle, arrays, offset=offset)
            return
        from tritonclient.utils import xla_shared_memory as xshm

        try:
            import jax.numpy as jnp

            arrays = [jnp.asarray(a) for a in arrays]
        except Exception:  # noqa: BLE001 — host staging still works
            pass
        xshm.set_shared_memory_region(handle, arrays, offset=offset)

    def close(self):
        """Unregister (server side) and unlink (client side) every
        region this worker created."""
        regions, self._regions = self._regions, []
        for name, handle in regions:
            try:
                self.backend.shm_unregister(name, self.kind)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            try:
                if self.kind == "system":
                    from tritonclient.utils import shared_memory as sysshm

                    sysshm.destroy_shared_memory_region(handle)
                else:
                    from tritonclient.utils import (
                        xla_shared_memory as xshm,
                    )

                    xshm.destroy_shared_memory_region(handle)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    # -- staging -----------------------------------------------------------

    def stage_input_sets(self, input_sets):
        """Write the whole rotation pool into per-input regions (one
        region per input name, one slot per set) and return the
        reference dicts ``prepare_shm`` consumes — one per set."""
        sets = list(input_sets)
        if not sets:
            return []
        refs = [dict() for _ in sets]
        for name in sets[0]:
            arrays = [np.ascontiguousarray(s[name]) for s in sets]
            first = arrays[0]
            if first.dtype == np.object_:
                raise ValueError(
                    "shared-memory mode needs fixed-size dtypes; input "
                    "'{}' is BYTES".format(name))
            nbytes = first.nbytes
            if any(a.nbytes != nbytes for a in arrays):
                raise ValueError(
                    "input '{}': every pool set must share one shape "
                    "in shared-memory mode".format(name))
            label = "in_" + "".join(
                c for c in name.lower() if c.isalnum())[:24]
            region, handle = self.create_region(
                label, nbytes * len(arrays))
            datatype = _np_wire_dtype(first)
            for i, a in enumerate(arrays):
                self.write(handle, [a], offset=i * nbytes)
                refs[i][name] = shm_input_ref(
                    region, nbytes, i * nbytes, datatype, a.shape)
        return refs

    def stage_outputs(self, output_names, byte_size):
        """One output region with a ``byte_size`` slot per declared
        output; returns the ``(name, region, byte_size, offset)`` list
        ``prepare_shm`` consumes."""
        names = list(output_names)
        if not names:
            return []
        region, _ = self.create_region("out", byte_size * len(names))
        return [
            (n, region, byte_size, j * byte_size)
            for j, n in enumerate(names)
        ]


# -- factory ---------------------------------------------------------------


def create_backend(kind, url=None, urls=None, core=None,
                   max_inflight=None, **kwargs):
    """Build a backend by name (the CLI's ``--backend`` flag).

    ``http``/``grpc`` need ``url``; ``pool`` needs ``urls`` (list);
    ``inprocess`` needs ``core`` (an ``InferenceServer``).
    ``max_inflight`` sizes the backend's executor/connection pool so
    the requested load level actually reaches the server.
    """
    if kind == "inprocess":
        if core is None:
            raise ValueError("inprocess backend needs core=")
        return InProcessBackend(core, max_inflight=max_inflight)
    if kind == "http":
        if not url:
            raise ValueError("http backend needs url=")
        return HttpBackend(url, max_inflight=max_inflight, **kwargs)
    if kind == "grpc":
        if not url:
            raise ValueError("grpc backend needs url=")
        return GrpcBackend(url, max_inflight=max_inflight)
    if kind == "pool":
        if not urls:
            raise ValueError("pool backend needs urls=")
        return PoolBackend(urls, max_inflight=max_inflight, **kwargs)
    raise ValueError(
        "unknown backend '{}' (want http, grpc, inprocess, or "
        "pool)".format(kind))


# -- input synthesis -------------------------------------------------------


def build_input_pool(metadata, config, pool_size=16, batch_size=1,
                     shape_overrides=None, const_overrides=None, seed=0):
    """A rotating pool of DISTINCT random input sets for one model.

    Measurement hygiene (docs/benchmarking.md rule 1): every context
    rotates through distinct inputs so no (executable, values) pair
    repeats back-to-back.  Shapes come from the model metadata; dynamic
    dims (-1) must be pinned via ``shape_overrides`` (name -> dims).
    ``const_overrides`` (name -> scalar) fills an input with one fixed
    value instead of random data — for control inputs like a
    ``DELAY_US`` knob that must not be randomized.  Models with
    ``max_batch_size > 0`` get a leading ``batch_size`` axis, matching
    Triton config semantics.
    """
    from tritonclient.utils import triton_to_np_dtype

    shape_overrides = shape_overrides or {}
    const_overrides = const_overrides or {}
    batched = _coerce_int(config.get("max_batch_size", 0)) > 0
    pool = []
    for i in range(pool_size):
        rng = np.random.RandomState(seed + i)
        inputs = {}
        for spec in metadata.get("inputs", []):
            name = spec["name"]
            dims = list(shape_overrides.get(name, spec["shape"]))
            dims = [_coerce_int(d) for d in dims]
            if any(d < 1 for d in dims):
                raise ValueError(
                    "input '{}' has dynamic dims {}; pin them with "
                    "--shape {}:d1,d2,...".format(name, dims, name))
            if batched:
                dims = [batch_size] + dims
            datatype = spec["datatype"]
            if name in const_overrides:
                np_dtype = (np.object_ if datatype == "BYTES"
                            else triton_to_np_dtype(datatype))
                value = const_overrides[name]
                if datatype == "BYTES":
                    value = str(value).encode("utf-8")
                inputs[name] = np.full(dims, value, dtype=np_dtype)
            elif datatype == "BYTES":
                flat = np.array(
                    ["req{}-{}".format(i, j).encode("utf-8")
                     for j in range(int(np.prod(dims)))],
                    dtype=np.object_)
                inputs[name] = flat.reshape(dims)
            else:
                np_dtype = triton_to_np_dtype(datatype)
                if np_dtype is None:
                    raise ValueError(
                        "cannot synthesize datatype '{}' for input "
                        "'{}'".format(datatype, name))
                if np.issubdtype(np_dtype, np.integer):
                    inputs[name] = rng.randint(
                        0, 100, size=dims).astype(np_dtype)
                elif np_dtype == np.bool_:
                    inputs[name] = rng.randint(
                        0, 2, size=dims).astype(np.bool_)
                else:
                    inputs[name] = rng.rand(*dims).astype(np_dtype)
        pool.append(inputs)
    return pool
