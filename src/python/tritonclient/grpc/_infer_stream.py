"""Bidirectional streaming machinery (reference grpc/_infer_stream.py:35-179).

``_InferStream`` owns the ModelStreamInfer call of the callback (push)
API: requests are fed from a queue through ``_RequestIterator`` (the gRPC
request iterator), responses are drained by a daemon thread that invokes
the user callback with ``(InferResult | None, InferenceServerException |
None)`` — decoupled models may produce zero or many responses per request.

``_PulledStream`` owns the ModelStreamInfer call of ONE attempt of
``generate_stream`` (the pull API): the caller iterates, so the caller's
own thread reads the call and nothing stands between gRPC and it.  The
two share how a request is built and nothing of the read side: a push
API needs a thread to push from, a pull API has its caller's.
"""

import queue
import threading
import time

import grpc

from tritonclient.utils import InferenceServerException

from ._infer_result import InferResult
from ._utils import get_error_grpc


class _RequestIterator:
    """Iterator over enqueued ModelInferRequest protos; blocks until the
    stream is closed with a None sentinel."""

    def __init__(self):
        self._queue = queue.Queue()

    def put(self, request):
        self._queue.put(request)

    def __iter__(self):
        return self

    def __next__(self):
        request = self._queue.get()
        if request is None:
            raise StopIteration
        return request


class _InferStream:
    """One open ModelStreamInfer bidi stream."""

    def __init__(self, callback, verbose=False):
        self._callback = callback
        self._verbose = verbose
        self._request_iterator = _RequestIterator()
        self._response_iterator = None
        self._handler = None
        self._active = True

    def _init_handler(self, response_iterator):
        self._response_iterator = response_iterator
        self._handler = threading.Thread(
            target=self._process_response, daemon=True
        )
        self._handler.start()

    def _enqueue_request(self, request):
        if not self._active:
            raise InferenceServerException(
                "The stream is no longer in valid state, the error detail "
                "is reported through provided callback. A new stream should "
                "be started after stopping the current stream."
            )
        self._request_iterator.put(request)

    def _process_response(self):
        """[handler thread] deliver each stream response to the callback;
        a dead stream surfaces the error once and deactivates."""
        try:
            for response in self._response_iterator:
                if self._verbose:
                    print(response)
                if response.error_message:
                    self._callback(
                        None,
                        InferenceServerException(response.error_message),
                    )
                else:
                    self._callback(
                        InferResult(response.infer_response), None
                    )
        except grpc.RpcError as rpc_error:
            self._active = False
            if rpc_error.code() != grpc.StatusCode.CANCELLED:
                self._callback(None, get_error_grpc(rpc_error))
        except Exception as e:  # stream death must reach the user
            self._active = False
            self._callback(None, InferenceServerException(str(e)))

    def close(self, cancel_requests=False):
        """Close the stream: stop the request feed and join the reader."""
        if cancel_requests and self._response_iterator is not None:
            self._response_iterator.cancel()
        self._request_iterator.put(None)
        self._active = False
        if self._handler is not None:
            self._handler.join()
            self._handler = None


class _PulledStream:
    """One ModelStreamInfer call that carries one request and is read by
    whoever iterates this object, in that thread: no reader thread, no
    queue, no timed wait per response.

    ``read_timeout`` (seconds, None = wait for ever) bounds how long one
    ``next()`` may wait for a response.  Nothing on a response's path
    keeps that time: a watchdog, one a call, sleeps until the earliest
    moment the wait in progress could have lasted that long, and cancels
    the RPC if it is still the same wait.  A response costs it two
    stores, and a call that never waits ``read_timeout`` never wakes it.
    """

    def __init__(self, call, read_timeout=None, verbose=False):
        self._call = call
        self._verbose = verbose
        self._read_timeout = read_timeout
        self._waiting_since = None  # monotonic start of the next() in progress
        self._timed_out = False
        self._closed = threading.Event()
        self._watchdog = None
        if read_timeout is not None:
            self._watchdog = threading.Thread(
                target=self._watch, name="generate-stream-watchdog",
                daemon=True)
            self._watchdog.start()

    def _watch(self):
        sleep = self._read_timeout
        while not self._closed.wait(sleep):
            since = self._waiting_since
            # a wait that has not begun cannot be over before a whole
            # read_timeout from now
            sleep = self._read_timeout if since is None else (
                since + self._read_timeout - time.monotonic())
            if sleep <= 0:
                self._timed_out = True
                self._call.cancel()
                return

    def __iter__(self):
        return self

    def __next__(self):
        """The next ``ModelStreamInferResponse``; StopIteration when the
        server ended the call, ``grpc.RpcError`` when the transport did."""
        self._waiting_since = time.monotonic()
        try:
            response = next(self._call)
        except grpc.RpcError:
            if self._timed_out:
                raise InferenceServerException(
                    "generate_stream: no response within {}s".format(
                        self._read_timeout)) from None
            raise
        finally:
            self._waiting_since = None
        if self._verbose:
            print(response)
        return response

    def _enqueue_request(self, request):
        raise InferenceServerException(
            "the active stream belongs to a generate_stream call and "
            "carries that generation alone; async_stream_infer needs a "
            "stream opened with start_stream()"
        )

    def close(self, cancel_requests=False):
        """End the call (a pulled call has no request left to send, so
        closing it is cancelling it) and stop the watchdog."""
        self._call.cancel()
        self._closed.set()
        if self._watchdog is not None:
            self._watchdog.join()
            self._watchdog = None
