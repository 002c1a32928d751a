"""``generate_stream`` (sync gRPC) reads its call in the caller's thread.

The pull API has its caller's thread, so a streamed response crosses no
thread and no queue of the client on its way to the caller; the push
API (``start_stream(callback)``) needs a thread to push from and keeps
its reader.  Every scenario below drives the real client against the
in-process server over a real socket while a recorder notes what
``tritonclient``'s OWN code starts and reads (gRPC's channel thread and
the in-process server's threads are other modules' and are not
counted): no reader thread, at most one read-timeout watchdog a call,
no ``queue.Queue`` read, nothing left behind.
"""

import contextlib
import queue
import sys
import threading
import time

import numpy as np
import pytest

import tritonclient.grpc as grpcclient
from tpuserver import faults
from tpuserver.core import InferenceServer, InferRequest
from tpuserver.grpc_frontend import GrpcFrontend
from tpuserver.models import llama
from tpuserver.models.llama_serving import LlamaGenerateModel
from tritonclient.utils import InferenceServerException

CFG = llama.tiny(vocab=512)
MAX_SEQ = 64
PROMPT = np.array([3, 1, 4, 1, 5], dtype=np.int32)
BUDGET = 8
WATCHDOG = "generate-stream-watchdog"
# seconds a step where a scenario counts responses: a client that reads
# multi-token responses gets one a token only if no token waits behind
# another for the server's handler
PACE = 0.1


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def served():
    model = LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2)
    core = InferenceServer([model])
    frontend = GrpcFrontend(core, port=0).start()
    try:
        yield model, core, "127.0.0.1:{}".format(frontend.port)
    finally:
        frontend.stop()
        core.close()


@pytest.fixture(scope="module")
def reference(served):
    _, core, _ = served
    req = InferRequest("llama_generate", inputs={
        "PROMPT_IDS": PROMPT, "MAX_TOKENS": np.array([BUDGET], np.int32)})
    return [int(arr[0]) for resp in core.infer_stream(req)
            for spec, arr, _ in resp.outputs if spec["name"] == "TOKEN"]


def _inputs(max_tokens=BUDGET):
    p_in = grpcclient.InferInput("PROMPT_IDS", [len(PROMPT)], "INT32")
    p_in.set_data_from_numpy(PROMPT)
    m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
    m_in.set_data_from_numpy(np.array([max_tokens], dtype=np.int32))
    return [p_in, m_in]


class _Recorder:
    """What ``tritonclient``'s own code does while installed: every
    thread it starts and every ``queue.Queue.get`` it makes, told from
    everybody else's by the module of the calling frame."""

    def __init__(self):
        self.started = []    # threading.Thread objects
        self.queue_gets = 0

    @staticmethod
    def _from_client():
        return sys._getframe(2).f_globals.get(
            "__name__", "").startswith("tritonclient")

    @contextlib.contextmanager
    def installed(self):
        start, get = threading.Thread.start, queue.Queue.get
        recorder = self

        def recording_start(thread):
            if recorder._from_client():
                recorder.started.append(thread)
            return start(thread)

        def recording_get(q, *args, **kwargs):
            if recorder._from_client():
                recorder.queue_gets += 1
            return get(q, *args, **kwargs)

        threading.Thread.start, queue.Queue.get = recording_start, recording_get
        try:
            yield self
        finally:
            threading.Thread.start, queue.Queue.get = start, get

    def names(self):
        return [t.name for t in self.started]

    def alive(self):
        return [t.name for t in self.started if t.is_alive()]


def _whole_call(client, served, reference):
    """A whole generation: the reference's tokens, in order, once."""
    results = list(client.generate_stream("llama_generate", _inputs()))
    assert [int(r.as_numpy("TOKEN")[0]) for r in results] == reference
    assert [r.get_response().parameters["seq"].int64_param
            for r in results] == list(range(BUDGET))
    return {"watchdogs": 1}


def _no_read_timeout(client, served, reference):
    """``read_timeout=None`` waits for ever, so nothing watches: the
    call starts no thread at all."""
    results = list(client.generate_stream(
        "llama_generate", _inputs(), read_timeout=None))
    assert [int(r.as_numpy("TOKEN")[0]) for r in results] == reference
    return {"watchdogs": 0}


def _slot_is_held(client, served, reference):
    """A generation owns the client's one stream slot for as long as it
    lasts: the callback API and a second generation are refused, and
    the slot is free again once the generator ends."""
    stream = client.generate_stream("llama_generate", _inputs())
    first = next(stream)
    with pytest.raises(InferenceServerException,
                       match="cannot start another stream"):
        client.start_stream(lambda result, error: None)
    with pytest.raises(InferenceServerException,
                       match="stream already active"):
        next(client.generate_stream("llama_generate", _inputs()))
    with pytest.raises(InferenceServerException,
                       match="belongs to a generate_stream call"):
        client.async_stream_infer("llama_generate", _inputs())
    rest = list(stream)
    assert [int(r.as_numpy("TOKEN")[0])
            for r in [first] + rest] == reference
    assert client._stream is None
    return {"watchdogs": 1}


def _read_timeout(client, served, reference):
    """A server that falls silent mid-generation (connection open, no
    bytes, no error) costs the caller ``read_timeout`` and no more."""
    read_timeout = 0.5
    # a token a step, each sent before the next comes: a response a
    # token, so the second response is the second token
    faults.install("scheduler.step", mode="slow", delay=PACE)
    faults.install("grpc.stream_infer", mode="partition", skip=2)
    got = []
    t0 = time.monotonic()
    with pytest.raises(
            InferenceServerException,
            match=r"generate_stream: no response within 0\.5s"):
        for result in client.generate_stream(
                "llama_generate", _inputs(), read_timeout=read_timeout):
            got.append(int(result.as_numpy("TOKEN")[0]))
            t0 = time.monotonic()
    waited = time.monotonic() - t0
    faults.clear()
    assert got == reference[:2]
    assert read_timeout <= waited < read_timeout + 1.0, waited
    return {"watchdogs": 1}


def _early_close(client, served, reference):
    """Closing the generator after the first token cancels the RPC: the
    server sees the caller gone and retires the slot long before
    ``max_tokens``."""
    model = served[0]
    budget = MAX_SEQ - len(PROMPT) - 1
    faults.install("scheduler.step", mode="slow", delay=0.03)
    before = model.scheduler_stats()["tokens"]
    stream = client.generate_stream("llama_generate", _inputs(budget))
    next(stream)
    assert model.scheduler_stats()["live_streams"] == 1
    stream.close()
    assert client._stream is None
    deadline = time.monotonic() + 1.0   # the budget would take ~1.7 s
    while (model.scheduler_stats()["live_streams"]
           and time.monotonic() < deadline):
        time.sleep(0.01)
    stats = model.scheduler_stats()
    assert stats["live_streams"] == 0
    assert stats["tokens"] - before < budget // 2, stats
    return {"watchdogs": 1}


def _stream_killed(client, served, reference):
    """The transport dies mid-generation (``grpc.stream_infer``): the
    call reconnects with its resume token, and no ``seq`` is missing
    or doubled.  One watchdog an attempt, no reader in either."""
    faults.install("scheduler.step", mode="slow", delay=PACE)
    faults.install("grpc.stream_infer", mode="raise", times=1, skip=3)
    reconnects = []
    results = list(client.generate_stream(
        "llama_generate", _inputs(),
        on_reconnect=lambda attempt, error: reconnects.append(attempt)))
    assert reconnects == [1]
    assert [int(r.as_numpy("TOKEN")[0]) for r in results] == reference
    assert [r.get_response().parameters["seq"].int64_param
            for r in results] == list(range(BUDGET))
    return {"watchdogs": 2}


@pytest.mark.parametrize("scenario", [
    _whole_call, _no_read_timeout, _slot_is_held, _read_timeout,
    _early_close, _stream_killed,
], ids=lambda f: f.__name__.lstrip("_"))
def test_generate_stream_is_read_by_its_caller(scenario, served, reference):
    client = grpcclient.InferenceServerClient(served[2])
    try:
        with _Recorder().installed() as seen:
            expect = scenario(client, served, reference)
        # no reader: the only thread a call may start watches the
        # read timeout and sleeps until the deadline
        assert seen.names() == [WATCHDOG] * expect["watchdogs"], seen.names()
        assert seen.queue_gets == 0
        assert seen.alive() == []
        assert client._stream is None
    finally:
        client.close()


def test_start_stream_keeps_its_reader(served, reference):
    """The push API is the parent's: one reader thread a stream, the
    callback fired from it, joined by ``stop_stream``; and a generation
    is refused while that stream is open."""
    client = grpcclient.InferenceServerClient(served[2])
    got = queue.Queue()
    try:
        with _Recorder().installed() as seen:
            client.start_stream(
                lambda result, error: got.put(
                    (threading.current_thread(), result, error)))
            with pytest.raises(InferenceServerException,
                               match="stream already active"):
                next(client.generate_stream("llama_generate", _inputs()))
            client.async_stream_infer("llama_generate", _inputs())
            delivered = [got.get(timeout=30) for _ in range(BUDGET)]
            client.stop_stream(cancel_requests=True)
        assert len(seen.started) == 1 and seen.alive() == []
        reader = seen.started[0]
        assert reader.name != WATCHDOG
        assert all(thread is reader and error is None
                   for thread, _, error in delivered)
        assert [int(result.as_numpy("TOKEN")[0])
                for _, result, _ in delivered] == reference
    finally:
        client.close()


def test_watchdog_sleeps_through_a_call_that_never_waits_that_long():
    """The read timeout costs a response two stores and the watchdog
    nothing: it wakes only when a whole ``read_timeout`` has passed,
    and never cancels a caller that is busy between two reads."""
    from tritonclient.grpc._infer_stream import _PulledStream

    class Call:
        cancelled = False

        def __next__(self):
            return "response"

        def cancel(self):
            self.cancelled = True

    call = Call()
    stream = _PulledStream(call, read_timeout=0.2)
    waits = []
    wait = stream._closed.wait
    stream._closed.wait = lambda timeout: waits.append(timeout) or wait(timeout)
    try:
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.5:   # reads, then is busy: no wait
            assert next(stream) == "response"
            time.sleep(0.05)
        assert not call.cancelled
        assert 1 <= len(waits) <= 4, waits
    finally:
        stream.close()
    assert stream._watchdog is None
