"""Sync HTTP/REST client for the KServe-v2 protocol.

Re-implements the full surface of reference http/_client.py:94-1600.  The
reference rides a geventhttpclient connection pool with gevent greenlets for
``async_infer``; this implementation keeps the same semantics on a stdlib
``http.client`` keep-alive connection pool plus a thread pool — no monkey
patching, and it composes cleanly with jax (which gevent does not).
"""

import base64
import json
import queue
import socket
import ssl as ssl_module
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from urllib.parse import quote, urlparse

from tritonclient._auxiliary import InferStat, RequestTimers, RetryPolicy
from tritonclient.http._infer_input import InferInput
from tritonclient.http._infer_result import InferResult
from tritonclient.http._requested_output import InferRequestedOutput
from tritonclient.http._utils import (
    _compress_request_body,
    _get_error_message,
    _get_inference_request,
    _get_query_string,
)
from tritonclient.utils import InferenceServerException, raise_error

__all__ = [
    "InferenceServerClient",
    "InferInput",
    "InferRequestedOutput",
    "InferResult",
    "InferAsyncRequest",
    "RetryPolicy",
]


class InferAsyncRequest:
    """Handle for an in-flight ``async_infer`` request; ``get_result()``
    blocks until the response arrives (reference http/_client.py:40-92)."""

    def __init__(self, future, verbose=False):
        self._future = future
        self._verbose = verbose

    def get_result(self, block=True, timeout=None):
        """Get the InferResult (or raise the request's exception)."""
        if not block and not self._future.done():
            raise_error("request not yet completed")
        return self._future.result(timeout=timeout)

    def cancelled(self):
        return self._future.cancelled()


class _PooledConnection:
    """A keep-alive HTTP/1.1 connection with raw send/recv helpers.

    Plain-HTTP requests ride a hand-rolled socket path: stdlib
    http.client burns ~250 us/request in its email-module header parser,
    which dominates small-tensor infer latency (the reference picks
    geventhttpclient's C parser for the same reason,
    reference http/_client.py:155-180).  HTTPS falls back to
    http.client for its TLS plumbing.
    """

    def __init__(self, scheme, host, port, connection_timeout, network_timeout,
                 ssl_context):
        self._scheme = scheme
        self._host = host
        self._port = port
        self._connection_timeout = connection_timeout
        self._network_timeout = network_timeout
        self._conn = None  # https fallback (http.client connection)
        self._sock = None
        self._buf = bytearray()
        if scheme == "https":
            import http.client

            self._conn = http.client.HTTPSConnection(
                host, port, timeout=connection_timeout, context=ssl_context
            )

    # -- https fallback ----------------------------------------------------

    def _request_https(self, method, path, body, headers):
        if self._conn.sock is None:
            self._conn.connect()
        self._conn.sock.settimeout(self._network_timeout)
        self._conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._conn.request(method, path, body=body, headers=headers)
        resp = self._conn.getresponse()
        return resp.status, dict(resp.headers), resp.read()

    # -- raw-socket fast path ---------------------------------------------

    def _connect(self):
        self._sock = socket.create_connection(
            (self._host, self._port), timeout=self._connection_timeout
        )
        self._sock.settimeout(self._network_timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._buf = bytearray()

    def _read_more(self):
        chunk = self._sock.recv(65536)
        if not chunk:
            raise ConnectionError("connection closed by server")
        self._buf += chunk  # bytearray += is amortized in-place

    def _read_exact(self, n):
        if len(self._buf) >= n:
            out = bytes(self._buf[:n])
            del self._buf[:n]
            return out
        # large read: drain the buffer, then recv_into the remainder
        out = bytearray(n)
        have = len(self._buf)
        out[:have] = self._buf
        del self._buf[:]
        view = memoryview(out)
        while have < n:
            got = self._sock.recv_into(view[have:])
            if not got:
                raise ConnectionError("connection closed by server")
            have += got
        return bytes(out)

    def _read_line(self):
        start = 0
        while True:
            eol = self._buf.find(b"\r\n", start)
            if eol >= 0:
                line = bytes(self._buf[:eol])
                del self._buf[:eol + 2]
                return line
            start = max(0, len(self._buf) - 1)
            self._read_more()

    @staticmethod
    def _check_header(key, value):
        text = "{}{}".format(key, value)
        if "\r" in text or "\n" in text:
            raise ValueError(
                "invalid CR/LF in header {!r}".format(key))

    def request(self, method, path, body, headers):
        if self._conn is not None:
            return self._request_https(method, path, body, headers)
        if self._sock is None:
            self._connect()
        if "\r" in path or "\n" in path or " " in path:
            raise ValueError("invalid characters in request path")
        head = [
            "{} {} HTTP/1.1".format(method, path),
            "Host: {}:{}".format(self._host, self._port),
        ]
        for key, value in headers.items():
            self._check_header(key, value)
            head.append("{}: {}".format(key, value))
        request = "\r\n".join(head).encode("latin-1") + b"\r\n\r\n"
        if body and hasattr(self._sock, "sendmsg"):
            # writev without concatenating the (possibly large) body;
            # sendmsg may send partially, so advance views until drained
            views = [memoryview(request), memoryview(body)]
            while views:
                sent = self._sock.sendmsg(views)
                while views and sent >= len(views[0]):
                    sent -= len(views[0])
                    views.pop(0)
                if views and sent:
                    views[0] = views[0][sent:]
        elif body:
            # sendmsg is Unix-only; fall back to two sendalls (still no
            # concatenation copy of the body)
            self._sock.sendall(request)
            self._sock.sendall(body)
        else:
            self._sock.sendall(request)

        while True:
            status_line = self._read_line()
            parts = status_line.split(None, 2)
            status = int(parts[1])
            resp_headers = {}
            while True:
                line = self._read_line()
                if not line:
                    break
                key, _, value = line.partition(b":")
                resp_headers[key.decode("latin-1").strip()] = (
                    value.decode("latin-1").strip()
                )
            if 100 <= status < 200:
                # interim response (e.g. a solicited 100 Continue):
                # bodiless by definition; the real response follows on
                # the same connection
                continue
            break
        lowered = {k.lower(): v for k, v in resp_headers.items()}
        if status in (204, 304):
            resp_body = b""  # bodiless by status (RFC 9112 6.3)
        elif lowered.get("transfer-encoding", "").lower() == "chunked":
            pieces = []
            while True:
                size = int(self._read_line().split(b";")[0], 16)
                if size == 0:
                    while self._read_line():  # trailers until blank line
                        pass
                    break
                pieces.append(self._read_exact(size))
                self._read_exact(2)  # CRLF after each chunk
            resp_body = b"".join(pieces)
        elif "content-length" in lowered:
            resp_body = self._read_exact(int(lowered["content-length"]))
        else:  # no framing: read to close
            try:
                while True:
                    self._read_more()
            except ConnectionError:
                pass
            resp_body = bytes(self._buf)
            self._buf = bytearray()
            self.close()
        if lowered.get("connection", "").lower() == "close":
            self.close()
        return status, resp_headers, resp_body

    def close(self):
        if self._conn is not None:
            try:
                self._conn.close()
            except Exception:
                pass
        if self._sock is not None:
            try:
                self._sock.close()
            except Exception:
                pass
            self._sock = None
        self._buf = bytearray()


class InferenceServerClient:
    """Client to the HTTP/REST endpoints of an inference server.

    Parameters
    ----------
    url : str
        ``host:port`` of the server (no scheme), e.g. ``"localhost:8000"``.
    verbose : bool
        If True print request/response details.
    concurrency : int
        Number of pooled connections (and worker threads for async_infer).
    connection_timeout : float
        Connect timeout in seconds.
    network_timeout : float
        Read timeout in seconds.
    ssl : bool
        Use HTTPS.
    ssl_options : dict
        Optional keys ``keyfile``, ``certfile``, ``ca_certs``.
    insecure : bool
        If True skip certificate verification.
    ssl_context_factory : callable
        Factory returning an ``ssl.SSLContext`` (overrides ssl_options).
    retry_policy : tritonclient._auxiliary.RetryPolicy
        Opt-in retries: exponential backoff with jitter, honoring
        ``Retry-After``, retrying ONLY connection errors and typed
        overload rejections (429/503) — never timeouts, which may have
        executed server-side.  Default None = no retries (the
        historical behavior).
    """

    def __init__(
        self,
        url,
        verbose=False,
        concurrency=1,
        connection_timeout=60.0,
        network_timeout=60.0,
        max_greenlets=None,
        ssl=False,
        ssl_options=None,
        ssl_context_factory=None,
        insecure=False,
        retry_policy=None,
    ):
        # Set first so close()/__del__ are safe even if __init__ raises below.
        self._closed = True
        if url.startswith("http://") or url.startswith("https://"):
            raise_error("url should not include the scheme")
        scheme = "https" if ssl else "http"
        parsed = urlparse(scheme + "://" + url)
        self._host = parsed.hostname
        self._port = parsed.port or (443 if ssl else 80)
        self._base_path = parsed.path.rstrip("/")
        self._scheme = scheme
        self._verbose = verbose
        self._concurrency = max(1, concurrency)
        self._connection_timeout = connection_timeout
        self._network_timeout = network_timeout

        self._ssl_context = None
        if ssl:
            if ssl_context_factory is not None:
                self._ssl_context = ssl_context_factory()
            else:
                ctx = ssl_module.create_default_context()
                if ssl_options:
                    if "ca_certs" in ssl_options:
                        ctx.load_verify_locations(ssl_options["ca_certs"])
                    if "certfile" in ssl_options:
                        ctx.load_cert_chain(
                            ssl_options["certfile"],
                            ssl_options.get("keyfile"),
                        )
                if insecure:
                    ctx.check_hostname = False
                    ctx.verify_mode = ssl_module.CERT_NONE
                self._ssl_context = ctx

        self._retry_policy = retry_policy
        self._pool = queue.LifoQueue()
        for _ in range(self._concurrency):
            self._pool.put(None)  # lazily created
        self._executor = None
        self._executor_lock = threading.Lock()
        self._infer_stat = InferStat()
        self._closed = False

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self):
        return self

    def __exit__(self, type_, value, traceback):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            # interpreter shutdown: queue internals may already be torn
            # down (queue.Empty raises through a half-collected module)
            pass

    def close(self, _empty=queue.Empty):
        """Close the client: drain the pool and stop worker threads.

        ``queue.Empty`` is bound as a default so ``__del__`` during
        interpreter shutdown (module globals already torn down) still works.
        """
        if self._closed:
            return
        self._closed = True
        if self._executor is not None:
            self._executor.shutdown(wait=True)
        while True:
            try:
                conn = self._pool.get_nowait()
            except _empty:
                break
            if conn is not None:
                conn.close()

    # -- low-level transport ----------------------------------------------

    def _new_connection(self):
        return _PooledConnection(
            self._scheme,
            self._host,
            self._port,
            self._connection_timeout,
            self._network_timeout,
            self._ssl_context,
        )

    def _request(self, method, request_uri, body=None, headers=None,
                 query_params=None):
        """One logical request, with the opt-in retry policy applied.

        Only two failure classes ever retry (see RetryPolicy): the
        connection could not be ESTABLISHED (refused/unresolvable — the
        server provably never saw the request) and typed overload
        statuses (429/503 — the server shed the request before doing
        work).  Timeouts and mid-response drops propagate immediately:
        the server may have executed the request, and resending a
        non-idempotent infer would double-execute it.
        """
        policy = self._retry_policy
        if policy is None:
            return self._request_once(
                method, request_uri, body, headers, query_params
            )
        # the logical-call budget: no backoff sleep may extend past it,
        # so a large server Retry-After hint cannot park the caller
        # beyond its own deadline
        budget_deadline = (
            time.monotonic() + policy.max_total_s
            if policy.max_total_s is not None
            else None
        )

        def _remaining():
            if budget_deadline is None:
                return None
            return budget_deadline - time.monotonic()

        attempt = 0
        while True:
            try:
                status, resp_headers, resp_body = self._request_once(
                    method, request_uri, body, headers, query_params
                )
            except (ConnectionRefusedError, socket.gaierror) as e:
                # connect-phase failure only: a ConnectionError AFTER
                # the request was sent (reset mid-response) is NOT here
                # — the server may have executed it
                remaining = _remaining()
                if (
                    not policy.retry_connection_errors
                    or attempt + 1 >= policy.max_attempts
                    or (remaining is not None and remaining <= 0)
                ):
                    raise
                time.sleep(policy.backoff_s(attempt, None, remaining))
                attempt += 1
                continue
            remaining = _remaining()
            if (
                status in policy.retryable_statuses
                and attempt + 1 < policy.max_attempts
                and (remaining is None or remaining > 0)
            ):
                retry_after = {
                    k.lower(): v for k, v in resp_headers.items()
                }.get("retry-after")
                time.sleep(policy.backoff_s(attempt, retry_after, remaining))
                attempt += 1
                continue
            return status, resp_headers, resp_body

    def _request_once(self, method, request_uri, body=None, headers=None,
                      query_params=None):
        path = self._base_path + "/" + request_uri
        if query_params is not None:
            path = path + "?" + _get_query_string(query_params)
        if self._verbose:
            print(f"{method} {path}, headers {headers}")
        hdrs = dict(headers) if headers else {}
        if body is not None and "Content-Length" not in hdrs:
            hdrs["Content-Length"] = str(len(body))
        import http.client as _http_client

        conn = self._pool.get()
        try:
            fresh = conn is None
            if fresh:
                conn = self._new_connection()
            try:
                status, resp_headers, resp_body = conn.request(
                    method, path, body, hdrs
                )
            except (ConnectionError, OSError,
                    _http_client.HTTPException) as e:
                conn.close()
                # Retry exactly once, and only when the failure is a stale
                # keep-alive connection (pooled conn, not a timeout): a
                # timeout may mean the server already executed this —
                # resending a non-idempotent infer would double-execute it.
                if fresh or isinstance(e, socket.timeout):
                    raise
                conn = self._new_connection()
                try:
                    status, resp_headers, resp_body = conn.request(
                        method, path, body, hdrs
                    )
                except Exception:
                    conn.close()
                    raise
        except Exception:
            self._pool.put(None)
            raise
        else:
            self._pool.put(conn)
        if self._verbose:
            print(status, resp_headers)
        return status, resp_headers, resp_body

    def _get(self, request_uri, headers=None, query_params=None):
        return self._request("GET", request_uri, None, headers, query_params)

    def _post(self, request_uri, request_body, headers=None,
              query_params=None):
        return self._request(
            "POST", request_uri, request_body, headers, query_params
        )

    @staticmethod
    def _raise_if_error(status, response_body, response_headers=None):
        if status != 200:
            retry_after = None
            if response_headers:
                # carried onto the exception so retry/failover layers
                # (tritonclient._pool) can honor the server's cooldown
                retry_after = {
                    k.lower(): v for k, v in response_headers.items()
                }.get("retry-after")
            raise InferenceServerException(
                msg=_get_error_message(response_body),
                status=str(status),
                retry_after=retry_after,
            )

    def _get_json(self, request_uri, headers=None, query_params=None):
        status, resp_headers, body = self._get(
            request_uri, headers, query_params
        )
        self._raise_if_error(status, body, resp_headers)
        content = json.loads(body) if body else {}
        if self._verbose:
            print(content)
        return content

    def _post_json(self, request_uri, request=None, headers=None,
                   query_params=None):
        body = json.dumps(request).encode("utf-8") if request is not None else b""
        status, resp_headers, resp_body = self._post(
            request_uri, body, headers, query_params
        )
        self._raise_if_error(status, resp_body, resp_headers)
        content = json.loads(resp_body) if resp_body else {}
        if self._verbose:
            print(content)
        return content

    # -- health / metadata -------------------------------------------------

    def is_server_live(self, headers=None, query_params=None):
        """Contact the server's liveness endpoint; returns bool."""
        status, _, _ = self._get("v2/health/live", headers, query_params)
        return status == 200

    def is_server_ready(self, headers=None, query_params=None):
        """Contact the server's readiness endpoint; returns bool."""
        status, _, _ = self._get("v2/health/ready", headers, query_params)
        return status == 200

    def is_model_ready(self, model_name, model_version="", headers=None,
                       query_params=None):
        """Contact the model's readiness endpoint; returns bool."""
        if model_version:
            uri = "v2/models/{}/versions/{}/ready".format(
                quote(model_name), model_version
            )
        else:
            uri = "v2/models/{}/ready".format(quote(model_name))
        status, _, _ = self._get(uri, headers, query_params)
        return status == 200

    def get_server_metadata(self, headers=None, query_params=None):
        """Get server metadata as a dict."""
        return self._get_json("v2", headers, query_params)

    def get_model_metadata(self, model_name, model_version="", headers=None,
                           query_params=None):
        """Get model metadata as a dict."""
        if model_version:
            uri = "v2/models/{}/versions/{}".format(
                quote(model_name), model_version
            )
        else:
            uri = "v2/models/{}".format(quote(model_name))
        return self._get_json(uri, headers, query_params)

    def get_model_config(self, model_name, model_version="", headers=None,
                         query_params=None):
        """Get model configuration as a dict."""
        if model_version:
            uri = "v2/models/{}/versions/{}/config".format(
                quote(model_name), model_version
            )
        else:
            uri = "v2/models/{}/config".format(quote(model_name))
        return self._get_json(uri, headers, query_params)

    # -- repository control ------------------------------------------------

    def get_model_repository_index(self, headers=None, query_params=None):
        """Get the index of the model repository (list of dicts)."""
        return self._post_json(
            "v2/repository/index", None, headers, query_params
        )

    def load_model(self, model_name, headers=None, query_params=None,
                   config=None, files=None):
        """Request the server to load or reload the model.

        ``config`` is an optional JSON config string override; ``files`` maps
        file paths to base64 content for repository override (reference
        grpc_client.h:232-256 / http/_client.py load_model).
        """
        load_request = {}
        if config is not None or files is not None:
            load_request["parameters"] = {}
        if config is not None:
            load_request["parameters"]["config"] = config
        if files is not None:
            for path, content in files.items():
                load_request["parameters"][path] = base64.b64encode(
                    content
                ).decode("utf-8")
        self._post_json(
            "v2/repository/models/{}/load".format(quote(model_name)),
            load_request if load_request else None,
            headers,
            query_params,
        )

    def unload_model(self, model_name, headers=None, query_params=None,
                     unload_dependents=False):
        """Request the server to unload the model."""
        unload_request = {
            "parameters": {"unload_dependents": unload_dependents}
        }
        self._post_json(
            "v2/repository/models/{}/unload".format(quote(model_name)),
            unload_request,
            headers,
            query_params,
        )

    # -- statistics / trace / logging -------------------------------------

    def get_inference_statistics(self, model_name="", model_version="",
                                 headers=None, query_params=None):
        """Get per-model inference statistics as a dict."""
        if model_name:
            if model_version:
                uri = "v2/models/{}/versions/{}/stats".format(
                    quote(model_name), model_version
                )
            else:
                uri = "v2/models/{}/stats".format(quote(model_name))
        else:
            uri = "v2/models/stats"
        return self._get_json(uri, headers, query_params)

    def update_trace_settings(self, model_name=None, settings={},
                              headers=None, query_params=None):
        """Update trace settings (server-global or per-model)."""
        if model_name is not None and model_name != "":
            uri = "v2/models/{}/trace/setting".format(quote(model_name))
        else:
            uri = "v2/trace/setting"
        return self._post_json(uri, settings, headers, query_params)

    def get_trace_settings(self, model_name=None, headers=None,
                           query_params=None):
        """Get trace settings (server-global or per-model)."""
        if model_name is not None and model_name != "":
            uri = "v2/models/{}/trace/setting".format(quote(model_name))
        else:
            uri = "v2/trace/setting"
        return self._get_json(uri, headers, query_params)

    def update_log_settings(self, settings, headers=None, query_params=None):
        """Update the server's log settings."""
        return self._post_json("v2/logging", settings, headers, query_params)

    def get_log_settings(self, headers=None, query_params=None):
        """Get the server's log settings."""
        return self._get_json("v2/logging", headers, query_params)

    # -- shared memory -----------------------------------------------------

    def get_system_shared_memory_status(self, region_name="", headers=None,
                                        query_params=None):
        """Get the status of registered system shared-memory regions."""
        if region_name:
            uri = "v2/systemsharedmemory/region/{}/status".format(
                quote(region_name)
            )
        else:
            uri = "v2/systemsharedmemory/status"
        return self._get_json(uri, headers, query_params)

    def register_system_shared_memory(self, name, key, byte_size, offset=0,
                                      headers=None, query_params=None):
        """Register a system (POSIX) shared-memory region with the server."""
        register_request = {
            "key": key,
            "offset": offset,
            "byte_size": byte_size,
        }
        self._post_json(
            "v2/systemsharedmemory/region/{}/register".format(quote(name)),
            register_request,
            headers,
            query_params,
        )
        if self._verbose:
            print("Registered system shared memory with name '{}'".format(name))

    def unregister_system_shared_memory(self, name="", headers=None,
                                        query_params=None):
        """Unregister one (or all, if name empty) system shm regions."""
        if name:
            uri = "v2/systemsharedmemory/region/{}/unregister".format(
                quote(name)
            )
        else:
            uri = "v2/systemsharedmemory/unregister"
        self._post_json(uri, None, headers, query_params)
        if self._verbose:
            if name:
                print(
                    "Unregistered system shared memory with name '{}'".format(
                        name
                    )
                )
            else:
                print("Unregistered all system shared memory regions")

    def get_cuda_shared_memory_status(self, region_name="", headers=None,
                                      query_params=None):
        """Get the status of registered CUDA shared-memory regions."""
        if region_name:
            uri = "v2/cudasharedmemory/region/{}/status".format(
                quote(region_name)
            )
        else:
            uri = "v2/cudasharedmemory/status"
        return self._get_json(uri, headers, query_params)

    def register_cuda_shared_memory(self, name, raw_handle, device_id,
                                    byte_size, headers=None,
                                    query_params=None):
        """Register a CUDA shared-memory region; ``raw_handle`` is the
        base64-encoded serialized cudaIpcMemHandle_t."""
        register_request = {
            "raw_handle": {"b64": raw_handle.decode("utf-8")
                           if isinstance(raw_handle, bytes) else raw_handle},
            "device_id": device_id,
            "byte_size": byte_size,
        }
        self._post_json(
            "v2/cudasharedmemory/region/{}/register".format(quote(name)),
            register_request,
            headers,
            query_params,
        )
        if self._verbose:
            print("Registered cuda shared memory with name '{}'".format(name))

    def unregister_cuda_shared_memory(self, name="", headers=None,
                                      query_params=None):
        """Unregister one (or all, if name empty) CUDA shm regions."""
        if name:
            uri = "v2/cudasharedmemory/region/{}/unregister".format(quote(name))
        else:
            uri = "v2/cudasharedmemory/unregister"
        self._post_json(uri, None, headers, query_params)

    def get_xla_shared_memory_status(self, region_name="", headers=None,
                                     query_params=None):
        """Get the status of registered XLA/TPU shared-memory regions.

        TPU-native analogue of ``get_cuda_shared_memory_status`` (reference
        http_client.h:411-442)."""
        if region_name:
            uri = "v2/xlasharedmemory/region/{}/status".format(
                quote(region_name)
            )
        else:
            uri = "v2/xlasharedmemory/status"
        return self._get_json(uri, headers, query_params)

    def register_xla_shared_memory(self, name, raw_handle, device_ordinal,
                                   byte_size, headers=None, query_params=None):
        """Register an XLA/TPU-HBM shared-memory region with the server.

        ``raw_handle`` is the base64-encoded serialized XlaShmHandle produced
        by ``tritonclient.utils.xla_shared_memory.get_raw_handle``."""
        register_request = {
            "raw_handle": {"b64": raw_handle.decode("utf-8")
                           if isinstance(raw_handle, bytes) else raw_handle},
            "device_ordinal": device_ordinal,
            "byte_size": byte_size,
        }
        self._post_json(
            "v2/xlasharedmemory/region/{}/register".format(quote(name)),
            register_request,
            headers,
            query_params,
        )
        if self._verbose:
            print("Registered xla shared memory with name '{}'".format(name))

    def unregister_xla_shared_memory(self, name="", headers=None,
                                     query_params=None):
        """Unregister one (or all, if name empty) XLA/TPU shm regions."""
        if name:
            uri = "v2/xlasharedmemory/region/{}/unregister".format(quote(name))
        else:
            uri = "v2/xlasharedmemory/unregister"
        self._post_json(uri, None, headers, query_params)

    # -- inference ---------------------------------------------------------

    @staticmethod
    def generate_request_body(
        inputs,
        outputs=None,
        request_id="",
        sequence_id=0,
        sequence_start=False,
        sequence_end=False,
        priority=0,
        timeout=None,
        parameters=None,
    ):
        """Generate an inference request body without sending it (reference
        http/_client.py:1207-1260).  Returns (body_bytes, header_length)."""
        return _get_inference_request(
            inputs=inputs,
            request_id=request_id,
            outputs=outputs,
            sequence_id=sequence_id,
            sequence_start=sequence_start,
            sequence_end=sequence_end,
            priority=priority,
            timeout=timeout,
            custom_parameters=parameters,
        )

    @staticmethod
    def parse_response_body(response_body, verbose=False, header_length=None,
                            content_encoding=None):
        """Parse a raw inference response body into an InferResult."""
        return InferResult.from_response_body(
            response_body, verbose, header_length, content_encoding
        )

    def _infer_uri(self, model_name, model_version):
        if model_version:
            return "v2/models/{}/versions/{}/infer".format(
                quote(model_name), model_version
            )
        return "v2/models/{}/infer".format(quote(model_name))

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
        headers=None,
        query_params=None,
        request_compression_algorithm=None,
        response_compression_algorithm=None,
        parameters=None,
    ):
        """Run a synchronous inference; returns an InferResult.

        Mirrors reference http/_client.py:1315-1462 (binary-tensor protocol,
        optional gzip/deflate compression both ways).
        """
        timers = RequestTimers()
        timers.request_start()
        request_body, json_size = _get_inference_request(
            inputs=inputs,
            request_id=request_id,
            outputs=outputs,
            sequence_id=sequence_id,
            sequence_start=sequence_start,
            sequence_end=sequence_end,
            priority=priority,
            timeout=timeout,
            custom_parameters=parameters,
        )

        hdrs = dict(headers) if headers else {}
        if request_compression_algorithm == "gzip":
            hdrs["Content-Encoding"] = "gzip"
            request_body = _compress_request_body("gzip", request_body)
        elif request_compression_algorithm == "deflate":
            hdrs["Content-Encoding"] = "deflate"
            request_body = _compress_request_body("deflate", request_body)
        if response_compression_algorithm == "gzip":
            hdrs["Accept-Encoding"] = "gzip"
        elif response_compression_algorithm == "deflate":
            hdrs["Accept-Encoding"] = "deflate"
        if json_size is not None:
            hdrs["Inference-Header-Content-Length"] = str(json_size)
        hdrs.setdefault("Content-Type", "application/octet-stream")

        timers.send_start()
        try:
            status, resp_headers, response_body = self._post(
                self._infer_uri(model_name, model_version),
                request_body,
                hdrs,
                query_params,
            )
            timers.send_end()
            self._raise_if_error(status, response_body, resp_headers)
        except Exception:
            self._infer_stat.update(timers, success=False)
            raise

        header_length = resp_headers.get("Inference-Header-Content-Length")
        content_encoding = resp_headers.get("Content-Encoding")
        timers.recv_start()
        result = InferResult.from_response_body(
            response_body,
            self._verbose,
            int(header_length) if header_length is not None else None,
            content_encoding,
        )
        timers.recv_end()
        timers.request_end()
        self._infer_stat.update(timers, success=True)
        return result

    def generate_stream(
        self,
        model_name,
        inputs,
        model_version="",
        parameters=None,
        request_id="",
        headers=None,
        resume=True,
        max_reconnects=5,
        reconnect_backoff_s=0.05,
        read_timeout=600.0,
        on_reconnect=None,
        fallback_urls=None,
    ):
        """Stream a decoupled generation over ``/generate_stream`` SSE,
        yielding one dict per event (the KServe generate-response JSON:
        ``outputs`` plus, for resumable generations, ``parameters`` with
        ``generation_id`` and the 0-based token ``seq``).

        With ``resume=True`` (default) a connection dropped
        *mid-generation* transparently reconnects: the client re-POSTs
        the same body with the SSE-standard ``Last-Event-ID`` header
        (``<generation_id>/<seq>`` of the last event received), the
        server replays the missed tokens from its replay buffer and
        splices the live continuation — no duplicated or missing
        tokens.  Against a bare replica resume is same-endpoint only
        (generation replay state is replica-local); behind a fleet
        router the contract is **seq continuity, not endpoint
        identity** — so ``fallback_urls`` (``host:port`` peers, e.g.
        the warm-standby router or the supervisor's respawn address)
        makes each reconnect rotate through the target list: a
        connect-refused primary (router SIGKILLed) retries the resume
        against the peer under the same ``max_reconnects`` + backoff
        budget.  Up to ``max_reconnects`` reattempts with exponential
        backoff; ``on_reconnect(attempt, exc)`` is called before each
        one (perf tooling counts resumes through it).  In-band
        ``{"error": ...}`` events raise InferenceServerException
        without reconnecting — those are typed server-side failures
        (e.g. a quarantined slot), not transport faults.

        Typed-status handling across targets: 404 on a RESUME and
        429/503 anywhere before the terminal event are transitions
        (router restart, standby not yet promoted, momentary
        saturation) and ride the reconnect path; a 404 on the FIRST
        request stays terminal — the model/endpoint genuinely is not
        there.

        ``inputs`` is a dict name -> numpy array (serialized as JSON
        data — generation prompts are small); ``parameters`` are the
        request parameters (``eos_id``, ``generation_id``, ...).
        
        A model that generates by diffusion over blocks sends one event
        a finished block (outputs ``TOKEN``, ``LOGPROB``, ``POSITION``,
        ``UNMASK_PASS`` of the block's length; request parameters
        ``denoising_steps`` / ``confidence_threshold``); call it with
        ``resume=False``: it does not replay a dropped stream yet.
        """
        import http.client as _http_client

        import numpy as np

        from tritonclient.utils import np_to_triton_dtype

        def _input_json(name, arr):
            if isinstance(arr, dict) and "shared_memory_region" in arr:
                # a shared-memory reference (the zero-copy data plane):
                # the prompt ids live in a registered region; the wire
                # carries only this descriptor
                return {
                    "name": name,
                    "shape": list(arr["shape"]),
                    "datatype": arr["datatype"],
                    "parameters": {
                        "shared_memory_region":
                            arr["shared_memory_region"],
                        "shared_memory_byte_size":
                            arr["shared_memory_byte_size"],
                        "shared_memory_offset":
                            arr.get("shared_memory_offset", 0),
                    },
                }
            return {
                "name": name,
                "shape": list(np.asarray(arr).shape),
                "datatype": ("BYTES"
                             if np.asarray(arr).dtype == np.object_
                             else np_to_triton_dtype(
                                 np.asarray(arr).dtype)),
                "data": [
                    v.decode("utf-8") if isinstance(v, bytes) else v
                    for v in np.asarray(arr).reshape(-1).tolist()
                ],
            }

        body_json = {
            "inputs": [
                _input_json(name, arr) for name, arr in inputs.items()
            ],
        }
        if request_id:
            body_json["id"] = request_id
        if parameters:
            body_json["parameters"] = dict(parameters)
        body = json.dumps(body_json)
        uri = "{}/v2/models/{}{}/generate_stream".format(
            self._base_path, quote(model_name),
            "/versions/{}".format(model_version) if model_version else "",
        )

        # reconnect target rotation: the primary first, then each
        # fallback router in turn (attempt N dials targets[N % len]);
        # validated up front — a malformed entry silently dropped
        # would degrade the supposed HA rotation to no-failover with
        # no signal until the first real outage
        targets = [(self._host, self._port)]
        for fb in fallback_urls or ():
            fb_host, sep, fb_port = str(fb).rpartition(":")
            if not (sep and fb_host and fb_port.isdigit()):
                raise InferenceServerException(
                    "fallback_urls entries must be host:port strings "
                    "(got {!r})".format(fb))
            targets.append((fb_host, int(fb_port)))

        last_event_id = None
        last_seq = -1
        yielded_any = False
        attempt = 0
        while True:
            t_host, t_port = targets[attempt % len(targets)]
            conn = (
                _http_client.HTTPSConnection(
                    t_host, t_port, timeout=read_timeout,
                    context=self._ssl_context)
                if self._scheme == "https"
                else _http_client.HTTPConnection(
                    t_host, t_port, timeout=read_timeout)
            )
            dropped = None
            try:
                hdrs = dict(headers) if headers else {}
                hdrs["Content-Type"] = "application/json"
                if last_event_id is not None:
                    hdrs["Last-Event-ID"] = last_event_id
                try:
                    conn.request("POST", uri, body, hdrs)
                    resp = conn.getresponse()
                except (ConnectionError, socket.timeout, OSError,
                        _http_client.HTTPException) as e:
                    dropped = e
                    resp = None
                if resp is not None:
                    transition = (
                        resp.status == 404 and last_event_id is not None
                    ) or (
                        resp.status in (429, 503)
                        and (last_event_id is not None or not yielded_any)
                    )
                    if transition:
                        # a RESUME answered 404 (server does not — yet —
                        # know this generation) or a typed overload
                        # (429/503: a router's shed valve, a standby
                        # router awaiting promotion, a busy serving
                        # slot) — under a fleet these are transitions,
                        # not verdicts (router restart/takeover,
                        # handoff in progress, momentary saturation):
                        # ride the reconnect path (rotating through
                        # fallback targets) and let the retries bound
                        # it.  429/503 retry even on a FIRST request
                        # that delivered nothing — re-POSTing an
                        # admission that never started cannot duplicate
                        # tokens; a first-request 404 stays terminal
                        # (the model/endpoint genuinely is not there).
                        reason = (
                            "resume target does not know generation"
                            if resp.status == 404
                            else "generation target is overloaded or "
                                 "standby")
                        dropped = InferenceServerException(
                            "{}: {}".format(
                                reason, _get_error_message(resp.read())),
                            status=str(resp.status),
                        )
                        resp = None
                    elif resp.status != 200:
                        raise InferenceServerException(
                            "generate_stream failed: {}".format(
                                _get_error_message(resp.read())),
                            status=str(resp.status),
                        )
                if resp is not None:
                    event_id = None
                    try:
                        for line in resp:
                            line = line.strip()
                            if line.startswith(b"id: "):
                                event_id = line[4:].decode(
                                    "utf-8", errors="replace")
                                continue
                            if not line.startswith(b"data: "):
                                continue
                            event = json.loads(line[len(b"data: "):])
                            if "error" in event:
                                # typed server failure: terminal, never
                                # ridden out by reconnecting
                                raise InferenceServerException(
                                    event["error"])
                            if event.get("final"):
                                return  # in-band end: generation done
                            seq = (event.get("parameters") or {}).get(
                                "seq")
                            if seq is not None and seq <= last_seq:
                                event_id = None
                                continue  # replayed duplicate
                            if seq is not None:
                                last_seq = seq
                            if event_id is not None:
                                last_event_id = event_id
                                event_id = None
                            yielded_any = True
                            yield event
                        # the stream ended WITHOUT the in-band terminal
                        # event: a mid-generation connection drop (a
                        # premature chunked EOF is not reliably an
                        # exception in stdlib http.client)
                        dropped = ConnectionError(
                            "stream ended without terminal event")
                    except (ConnectionError, socket.timeout, OSError,
                            _http_client.HTTPException) as e:
                        dropped = e
            finally:
                conn.close()
            # reconnect path: the stream died mid-flight.  Resume is
            # only safe when the server issued SSE ids (a resumable,
            # scheduler-backed generation) OR nothing was delivered yet
            # (a fresh re-send cannot duplicate); re-running a
            # non-resumable generation after yielding tokens would
            # duplicate them (and re-execute server-side effects like
            # KV-cache parking), so that fails instead.
            attempt += 1
            if (not resume or attempt > max_reconnects
                    or (yielded_any and last_event_id is None)):
                reason = (
                    " (resume disabled)" if not resume
                    else " (generation is not resumable: the server sent"
                         " no event ids)"
                    if yielded_any and last_event_id is None
                    else ""
                )
                if isinstance(dropped, InferenceServerException):
                    # retries exhausted on a typed answer (e.g. the
                    # resume 404 every reattempt repeated): surface it
                    # with its status intact
                    raise dropped
                raise InferenceServerException(
                    "generate_stream connection lost{}: {}".format(
                        reason, dropped))
            if on_reconnect is not None:
                on_reconnect(attempt, dropped)
            time.sleep(min(reconnect_backoff_s * (2 ** (attempt - 1)), 2.0))

    def async_infer(
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
        headers=None,
        query_params=None,
        request_compression_algorithm=None,
        response_compression_algorithm=None,
        parameters=None,
    ):
        """Run inference on a worker thread; returns an InferAsyncRequest
        whose ``get_result()`` blocks for the InferResult (reference
        http/_client.py:1464-1600, gevent pool -> thread pool)."""
        with self._executor_lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=self._concurrency,
                    thread_name_prefix="tritonclient-http",
                )
        future = self._executor.submit(
            self.infer,
            model_name,
            inputs,
            model_version,
            outputs,
            request_id,
            sequence_id,
            sequence_start,
            sequence_end,
            priority,
            timeout,
            headers,
            query_params,
            request_compression_algorithm,
            response_compression_algorithm,
            parameters,
        )
        return InferAsyncRequest(future, self._verbose)

    def get_inference_stat(self):
        """Client-side accumulated InferStat for this client's requests."""
        return self._infer_stat
