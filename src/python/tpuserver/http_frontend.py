"""HTTP/REST frontend: serves the KServe-v2 protocol (with the binary-tensor
extension) over a threaded socket server, delegating to
``tpuserver.core.InferenceServer``.

The request plumbing is hand-rolled rather than ``BaseHTTPRequestHandler``:
the stdlib handler parses headers through the email package (~300us per
request) and writes status/headers/body in separate syscalls; at the
quick-start benchmark's ~700us round trip that is most of the budget.
The framing itself (byte-split header parsing, one-``write`` responses,
chunked SSE) lives in ``tpuserver._http_base.BaseHttpHandler``, shared
with the fleet router — this module owns only the replica's route
table (role of the reference server's C++ evhtp frontend on the
latency-critical path)."""

import gzip
import json
import re
import socketserver
import threading
import zlib
from urllib.parse import unquote

import numpy as np

from tpuserver._http_base import BaseHttpHandler, ClientGone
from tpuserver._trace import span
from tpuserver.tensor_io import (
    array_from_binary as _array_from_binary,
    binary_from_array as _binary_from_array,
)
from tpuserver.core import (
    InferenceServer,
    InferRequest,
    RequestedOutput,
    ServerError,
)
from tritonclient.utils import triton_to_np_dtype

_MODEL_URI = re.compile(
    r"^/v2/models/(?P<model>[^/]+)(/versions/(?P<version>[^/]+))?"
    r"(?P<rest>/.*)?$"
)
_SHM_URI = re.compile(
    r"^/v2/(?P<kind>systemsharedmemory|cudasharedmemory|xlasharedmemory)"
    r"(/region/(?P<region>[^/]+))?/(?P<verb>status|register|unregister)$"
)
_REPO_URI = re.compile(
    r"^/v2/repository(/models/(?P<model>[^/]+)/(?P<verb>load|unload)|/index)$"
)
_KVEXPORT_URI = re.compile(
    r"^/v2/kvexport/(?P<gen>[^/]+)(?P<release>/release)?$"
)


def _array_from_json_data(data, datatype, shape):
    if datatype == "BYTES":
        flat = []
        stack = [data]
        while stack:
            item = stack.pop()
            if isinstance(item, list):
                stack.extend(reversed(item))
            else:
                flat.append(
                    item.encode("utf-8") if isinstance(item, str) else item
                )
        return np.array(flat, dtype=np.object_).reshape(shape)
    np_dtype = triton_to_np_dtype(datatype)
    return np.asarray(data, dtype=np_dtype).reshape(shape)


class _Handler(BaseHttpHandler):
    """The replica's route table over the shared framing: every
    request executes against the local ``InferenceServer``."""

    server_token = b"tpu-triton-server"

    @property
    def core(self):
        return self.server.core

    def _dispatch(self, method):
        try:
            self._route(method)
        except ServerError as e:
            headers = None
            if getattr(e, "retry_after", None) is not None:
                # overload shedding contract: 429/503 carry Retry-After
                # so retrying clients back off instead of hammering
                headers = {"Retry-After": int(e.retry_after)}
            self._send_error_json(str(e), e.code, headers)
        except ValueError as e:
            self._send_error_json("malformed request: {}".format(e), 400)
        except (BrokenPipeError, ConnectionResetError, ClientGone):
            raise  # dead socket (incl. injected drops): handle() ends it
        except Exception as e:  # pragma: no cover
            self._send_error_json("internal error: {}".format(e), 500)

    def _send_metrics(self, core):
        """Prometheus exposition (role of Triton's :8002/metrics;
        scraped by perf_analyzer --collect-metrics, reference
        metrics_manager.h:44-91).  The snapshot itself is the core's
        ``metrics_text()`` — the nv_* compatibility families plus the
        tpu_* registry (docs/observability.md) — so the HTTP route and
        the gRPC ServerMetrics unary serve identical bytes."""
        self._send(
            200, core.metrics_text().encode("utf-8"),
            content_type="text/plain")

    def _route(self, method):
        path = self.path.split("?", 1)[0]
        core = self.core

        if path == "/v2/health/live":
            return self._send(200)
        if path == "/v2/health/ready":
            # real readiness (starting/draining/watchdog-tripped all
            # report 503), not a constant — load balancers route on this
            return self._send(200 if core.server_ready() else 503)
        if path == "/v2/health/stats":
            # cheap routing-signal snapshot (lifecycle + scheduler
            # counters, no per-model inference statistics): what the
            # fleet router's prober polls at sub-second cadence
            return self._send_json(core.health_snapshot())
        if path == "/v2" or path == "/v2/":
            return self._send_json(core.server_metadata())
        if path == "/v2/models/stats":
            return self._send_json(core.model_statistics())
        if path == "/metrics":
            return self._send_metrics(core)
        if path == "/v2/logging":
            if method == "POST":
                return self._send_json(
                    core.update_log_settings(json.loads(self._read_body()))
                )
            return self._send_json(core.get_log_settings())
        if path == "/v2/trace/setting":
            if method == "POST":
                return self._send_json(
                    core.update_trace_settings(
                        None, json.loads(self._read_body())
                    )["settings"]
                )
            return self._send_json(core.get_trace_settings()["settings"])

        m = _KVEXPORT_URI.match(path)
        if m:
            # disaggregated transfer control plane: GET hands out the
            # one-shot wire descriptor of a prefill leg's KV export
            # (typed 404 when gone, 409 when already claimed); POST
            # .../release drops it (idempotent) once the decode leg
            # admitted — or never, and the replay TTL sweep reaps it
            gen_id = unquote(m.group("gen"))
            if m.group("release"):
                if method != "POST":
                    raise ServerError(
                        "kvexport release requires POST", code=405)
                core.drop_kv_region(gen_id)
                return self._send_json({})
            if method != "GET":
                raise ServerError(
                    "kvexport descriptor fetch requires GET", code=405)
            return self._send_json(core.kv_export_descriptor(gen_id))

        m = _REPO_URI.match(path)
        if m:
            if m.group("verb") == "load":
                core.load_model(unquote(m.group("model")))
                return self._send_json({})
            if m.group("verb") == "unload":
                unload_dependents = False
                body = self._read_body()
                if body:
                    params = json.loads(body).get("parameters", {})
                    unload_dependents = params.get("unload_dependents", False)
                core.unload_model(unquote(m.group("model")), unload_dependents)
                return self._send_json({})
            return self._send_json(core.repository_index())

        m = _SHM_URI.match(path)
        if m:
            return self._route_shm(m)

        m = _MODEL_URI.match(path)
        if m:
            model = unquote(m.group("model"))
            version = m.group("version") or ""
            rest = m.group("rest") or ""
            if rest == "/ready":
                if core.model_ready(model, version):
                    return self._send(200)
                return self._send(400)
            if rest == "" or rest == "/":
                return self._send_json(core.model_metadata(model, version))
            if rest == "/config":
                return self._send_json(core.model_config(model, version))
            if rest == "/stats":
                return self._send_json(core.model_statistics(model, version))
            if rest == "/trace/setting":
                if method == "POST":
                    return self._send_json(
                        core.update_trace_settings(
                            model, json.loads(self._read_body())
                        )["settings"]
                    )
                return self._send_json(
                    core.get_trace_settings(model)["settings"]
                )
            if rest == "/infer" and method == "POST":
                return self._route_infer(model, version)
            if rest in ("/generate", "/generate_stream") and method == "POST":
                return self._route_generate(
                    model, version, stream=rest.endswith("_stream")
                )
        raise ServerError("unknown endpoint: " + path, code=404)

    def _route_shm(self, m):
        core = self.core
        kind = m.group("kind")
        region = unquote(m.group("region")) if m.group("region") else ""
        verb = m.group("verb")
        if kind == "systemsharedmemory":
            if verb == "status":
                return self._send_json(core.system_shm_status(region))
            if verb == "register":
                req = json.loads(self._read_body())
                core.register_system_shm(
                    region, req["key"], req.get("offset", 0), req["byte_size"]
                )
                return self._send_json({})
            core.unregister_system_shm(region)
            return self._send_json({})
        if kind == "cudasharedmemory":
            if verb == "status":
                return self._send_json(core.cuda_shm_status(region))
            if verb == "register":
                req = json.loads(self._read_body())
                core.register_cuda_shm(
                    region, req.get("raw_handle", {}).get("b64", ""),
                    req.get("device_id", 0), req["byte_size"],
                )
                return self._send_json({})
            core.unregister_cuda_shm(region)
            return self._send_json({})
        # xlasharedmemory
        if verb == "status":
            return self._send_json(core.xla_shm_status(region))
        if verb == "register":
            req = json.loads(self._read_body())
            core.register_xla_shm(
                region, req.get("raw_handle", {}).get("b64", ""),
                req.get("device_ordinal", 0), req["byte_size"],
            )
            return self._send_json({})
        core.unregister_xla_shm(region)
        return self._send_json({})

    # -- generate (decoupled streaming over HTTP) -------------------------

    def _route_generate(self, model, version, stream):
        """KServe-style generate endpoints for decoupled models.

        The request body is the infer JSON shape (``inputs`` with
        ``data``, optional ``parameters``).  ``/generate`` collects the
        whole decoupled burst into one JSON response (each output's
        per-step values concatenated along a leading step axis);
        ``/generate_stream`` emits one SSE event per decoupled response
        over a chunked transfer — the HTTP fan-out of the continuous-
        batching scheduler's per-step tokens (each chunk leaves as soon
        as its decode step retires, so concurrent requests on separate
        connections interleave at token granularity).
        """
        core = self.core
        body = self._read_body()
        request_json = json.loads(body)
        parameters = dict(request_json.get("parameters", {}))
        if stream:
            # SSE-standard reconnection: a client that lost its
            # connection re-POSTs the same body with Last-Event-ID
            # "<generation_id>/<seq>"; the scheduler replays from
            # seq + 1 and splices the live continuation
            last_id = self.headers.get("Last-Event-ID")
            if last_id:
                # LAST slash: a client-chosen generation_id may itself
                # contain '/' (e.g. "tenant/abc"); the seq is always
                # the final segment
                gen_id, sep, seq = last_id.rpartition("/")
                if sep and gen_id:
                    try:
                        parameters.setdefault(
                            "resume_from_seq", int(seq) + 1)
                        parameters.setdefault(
                            "resume_generation_id", gen_id)
                    except ValueError:
                        pass  # malformed id: treat as a fresh request
        inputs = {}
        shm_input_regions = []
        for tin in request_json.get("inputs", []):
            datatype = tin.get("datatype")
            if not datatype:
                raise ServerError(
                    "generate input '{}' needs a datatype".format(
                        tin.get("name"))
                )
            tparams = tin.get("parameters", {})
            if "shared_memory_region" in tparams:
                # generation admissions accept PROMPT_IDS (and any
                # other input) by shm region reference: resolved
                # through the same bounds-checked core path as /infer;
                # for an in-process XLA region the model consumes the
                # device segment view directly — zero host staging
                inputs[tin["name"]] = core.read_shm_input(
                    tparams["shared_memory_region"],
                    tparams.get("shared_memory_byte_size", 0),
                    tparams.get("shared_memory_offset", 0),
                    datatype,
                    tin["shape"],
                )
                shm_input_regions.append(tparams["shared_memory_region"])
            else:
                inputs[tin["name"]] = _array_from_json_data(
                    tin.get("data"), datatype, tin["shape"]
                )
        request = InferRequest(
            model, version, request_json.get("id", ""), inputs, None,
            parameters,
        )
        # the model pins these for the stream's lifetime: the region
        # backing a live device view must conflict on unregister (409)
        request.shm_input_regions = tuple(shm_input_regions)

        def response_json(resp):
            out = {
                "model_name": resp.model_name,
                "model_version": resp.model_version,
                "outputs": [],
            }
            if resp.id:
                out["id"] = resp.id
            for spec, array, _ in resp.outputs:
                entry = dict(spec)
                if array is not None:
                    entry["data"] = (
                        [v.decode("utf-8", errors="replace")
                         if isinstance(v, bytes) else str(v)
                         for v in array.reshape(-1)]
                        if spec["datatype"] == "BYTES"
                        else array.reshape(-1).tolist()
                    )
                out["outputs"].append(entry)
            return out

        if not stream:
            merged = None
            for resp in core.infer_stream(request):
                piece = response_json(resp)
                if merged is None:
                    merged = piece
                    for entry in merged["outputs"]:
                        entry["shape"] = [1] + list(entry["shape"])
                else:
                    by_name = {e["name"]: e for e in merged["outputs"]}
                    for entry in piece["outputs"]:
                        tgt = by_name.get(entry["name"])
                        if tgt is None:
                            merged["outputs"].append(entry)
                            entry["shape"] = [1] + list(entry["shape"])
                        else:
                            tgt["data"].extend(entry["data"])
                            tgt["shape"][0] += 1
            if merged is None:
                merged = {"model_name": model, "model_version": version,
                          "outputs": []}
            return self._send_json(merged)

        # SSE over chunked transfer: the stream must start before the
        # generation finishes, so errors after the first token arrive
        # in-band as an {"error": ...} event (the status line is gone)
        from tpuserver import faults as _faults

        try:
            for resp in core.infer_stream(request):
                with span("frontend.emit"):
                    self._ensure_started()
                    payload = response_json(resp)
                    event = b""
                    if resp.parameters:
                        wire = {k: v for k, v in resp.parameters.items()
                                if not k.startswith("triton_")}
                        if wire:
                            payload["parameters"] = wire
                        gen_id = resp.parameters.get("generation_id")
                        seq = resp.parameters.get("seq")
                        if gen_id is not None and seq is not None:
                            # the SSE id the browser/client hands back
                            # as Last-Event-ID on reconnect
                            event += "id: {}/{}\n".format(
                                gen_id, seq).encode("utf-8")
                    # chaos hook: sever the connection mid-stream (no
                    # terminal chunk) so client auto-resume is drivable
                    # end-to-end; skip=N drops after the Nth event
                    _faults.fire("http.generate_stream", core.fault_scope)
                    # counted before the write, as the gRPC handler
                    # counts before its yield: a client that holds the
                    # event finds it counted
                    core.count_token_handoff(resp)
                    self._send_chunk(
                        event + b"data: "
                        + json.dumps(payload).encode("utf-8")
                        + b"\n\n"
                    )
        except _faults.FaultInjected:
            try:
                self.connection.close()
            finally:
                raise BrokenPipeError("injected mid-stream disconnect")
        except ServerError as e:
            if not self._started:
                raise
            self._send_chunk(
                b"data: " + json.dumps({"error": str(e)}).encode("utf-8")
                + b"\n\n"
            )
            self._end_chunks()
            return
        self._ensure_started()
        # explicit terminal event: a premature TCP close mid-chunked
        # stream is NOT reliably distinguishable from a clean end by
        # every HTTP client (stdlib line iteration just stops), so
        # completion is in-band — a stream that ends WITHOUT this
        # marker (or an error event) was dropped, and resuming clients
        # reconnect with Last-Event-ID
        self._send_chunk(b'data: {"final": true}\n\n')
        self._end_chunks()

    # -- inference --------------------------------------------------------

    def _route_infer(self, model, version):
        core = self.core
        body = self._read_body()
        header_length = self.headers.get("Inference-Header-Content-Length")
        if header_length is not None:
            json_len = int(header_length)
            request_json = json.loads(body[:json_len])
            binary = body[json_len:]
        else:
            request_json = json.loads(body)
            binary = b""

        parameters = dict(request_json.get("parameters", {}))
        binary_all_outputs = parameters.pop("binary_data_output", False)

        declared_in = None  # resolved lazily: most clients send datatypes

        inputs = {}
        offset = 0
        for tin in request_json.get("inputs", []):
            name = tin["name"]
            datatype = tin.get("datatype")
            if not datatype:
                if declared_in is None:
                    try:
                        model_meta = core.model_metadata(model, version)
                    except ServerError:
                        model_meta = {"inputs": []}
                    declared_in = {
                        t["name"]: t for t in model_meta.get("inputs", [])
                    }
                datatype = declared_in.get(name, {}).get("datatype")
            shape = tin["shape"]
            tparams = tin.get("parameters", {})
            if "shared_memory_region" in tparams:
                inputs[name] = core.read_shm_input(
                    tparams["shared_memory_region"],
                    tparams.get("shared_memory_byte_size", 0),
                    tparams.get("shared_memory_offset", 0),
                    datatype,
                    shape,
                )
            elif "binary_data_size" in tparams:
                size = tparams["binary_data_size"]
                raw = binary[offset : offset + size]
                offset += size
                inputs[name] = _array_from_binary(raw, datatype, shape)
            elif "data" in tin:
                inputs[name] = _array_from_json_data(
                    tin["data"], datatype, shape
                )
            else:
                raise ServerError(
                    "input '{}' has no data and no shared-memory "
                    "reference".format(name)
                )

        requested = None
        if "outputs" in request_json:
            requested = []
            for tout in request_json["outputs"]:
                oparams = tout.get("parameters", {})
                requested.append(
                    RequestedOutput(
                        tout["name"],
                        binary_data=oparams.get("binary_data", False)
                        or binary_all_outputs,
                        class_count=oparams.get("classification", 0),
                        shm_region=oparams.get("shared_memory_region"),
                        shm_byte_size=oparams.get(
                            "shared_memory_byte_size", 0
                        ),
                        shm_offset=oparams.get("shared_memory_offset", 0),
                    )
                )

        request = InferRequest(
            model,
            version,
            request_json.get("id", ""),
            inputs,
            requested,
            parameters,
        )
        response = core.infer(request)

        # Assemble response: JSON header + binary section.
        out_json = {
            "model_name": response.model_name,
            "model_version": response.model_version,
            "outputs": [],
        }
        if response.id:
            out_json["id"] = response.id
        binary_parts = []
        for spec, array, delivery in response.outputs:
            entry = dict(spec)
            oparams = {}
            if array is None:
                oparams["shared_memory_region"] = delivery["shm_region"]
                oparams["shared_memory_byte_size"] = delivery["shm_byte_size"]
                if delivery["shm_offset"]:
                    oparams["shared_memory_offset"] = delivery["shm_offset"]
            elif (requested is not None and delivery["binary_data"]) or (
                requested is None and binary_all_outputs
            ):
                raw = _binary_from_array(array, spec["datatype"])
                oparams["binary_data_size"] = len(raw)
                binary_parts.append(raw)
            else:
                if spec["datatype"] == "BYTES":
                    entry["data"] = [
                        v.decode("utf-8", errors="replace")
                        if isinstance(v, bytes)
                        else str(v)
                        for v in array.reshape(-1)
                    ]
                elif spec["datatype"] == "BF16":
                    raise ServerError(
                        "BF16 outputs require binary_data=true"
                    )
                else:
                    entry["data"] = array.reshape(-1).tolist()
            if oparams:
                entry["parameters"] = oparams
            out_json["outputs"].append(entry)

        header = json.dumps(out_json).encode("utf-8")
        headers = {}
        if binary_parts:
            payload = header + b"".join(binary_parts)
            headers["Inference-Header-Content-Length"] = str(len(header))
            content_type = "application/octet-stream"
        else:
            payload = header
            content_type = "application/json"

        accept_encoding = self.headers.get("Accept-Encoding", "")
        if "gzip" in accept_encoding:
            payload = gzip.compress(payload)
            headers["Content-Encoding"] = "gzip"
        elif "deflate" in accept_encoding:
            payload = zlib.compress(payload)
            headers["Content-Encoding"] = "deflate"
        self._send(200, payload, headers, content_type)


class _Server(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class HttpFrontend:
    """Threaded HTTP server wrapper: ``start()``/``stop()``; ``port`` is
    resolved after start (pass 0 to pick a free port)."""

    def __init__(self, core, host="127.0.0.1", port=0, verbose=False):
        self._core = core
        self._httpd = _Server((host, port), _Handler)
        self._httpd.core = core
        self._httpd.verbose = verbose
        self._thread = None

    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def url(self):
        return "{}:{}".format(self._httpd.server_address[0], self.port)

    def start(self):
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.05},
            daemon=True,
        )
        self._thread.start()
        self._core.attach_frontend()
        self._attached = True
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if getattr(self, "_attached", False):
            # only an attach that actually happened may detach (see
            # grpc_frontend.stop)
            self._attached = False
            self._core.detach_frontend()
