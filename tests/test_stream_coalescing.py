"""A generation's waiting tokens leave in one gRPC response, for a client
that says it reads such responses.

The generation model here is the real ``LlamaGenerateModel`` with a
stand-in for its decode loop (``_Loop``): each generation gets a real
scheduler stream, whose queue the test fills with what it scripts —
bursts of tokens that wait together, put at once — so a backlog exists
exactly where the test says.  Everything else is the real path: the
scheduler's drain, the model's events, the core, the gRPC frontend's
handler, and ``tritonclient.grpc`` over a real socket.
"""

import threading
import time

import numpy as np
import pytest

import tritonclient.grpc as grpcclient
from tpuserver import faults
from tpuserver import scheduler as scheduler_mod
from tpuserver.core import (
    MERGEABLE_KEY,
    InferenceServer,
    InferRequest,
    InferResponse,
    merge_responses,
)
from tpuserver.grpc_frontend import GrpcFrontend, _merge_waiting
from tpuserver.metrics import parse_prometheus_text
from tpuserver.models import llama
from tpuserver.models.llama_serving import LlamaGenerateModel
from tritonclient.grpc import grpc_service_pb2 as pb
from tritonclient.grpc._utils import _get_inference_request

MODEL = "llama_generate"
COUNTERS = ("tpu_frontend_token_handoffs_total",
            "tpu_frontend_stream_emissions_total",
            "tpu_frontend_stream_responses_total")


def _token(i):
    return 100 + i, -0.125 * i


def _put_together(stream, events):
    """Put ``events`` on the stream's queue as one: a reader takes all
    of them or none."""
    q = stream.queue
    with q.mutex:
        q.queue.extend(events)
        q.unfinished_tasks += len(events)
        q.not_empty.notify()


class _Loop:
    """Stands in for the decode loop: a generation's tokens come in the
    scripted ``bursts`` (token numbers), the first already waiting when
    the generation is submitted, each later one put at once when its
    gate opens (``gates[i]`` before burst ``i + 1``; None: at once);
    the generation ends with its last burst.  A block model's burst is
    of blocks, one emission each."""

    def __init__(self, bursts, gates=None, blocks=False):
        self.bursts, self.blocks = bursts, blocks
        self.gates = gates or [None] * (len(bursts) - 1)
        self.history = []
        self.batched = []

    def _event(self, i):
        if self.blocks:
            toks = [4 * i + j for j in range(4)]
            block = (toks, [-0.5] * 4, toks, [0] * 4)
            return ("tok", (block, None), time.monotonic())
        return ("tok", _token(i), time.monotonic())

    def submit(self, prompt, max_tokens, batched=False, generation_id=None,
               **_):
        stream = scheduler_mod._Stream(np.asarray(prompt, np.int32),
                                       max_tokens, None, None, 0, None,
                                       generation_id=generation_id)
        self.batched.append(batched)
        self._put(stream, 0)

        def emitter():
            for n, gate in enumerate(self.gates, start=1):
                if gate is not None:
                    assert gate.wait(10), "the gate never opened"
                self._put(stream, n)

        threading.Thread(target=emitter, daemon=True).start()
        return scheduler_mod.DecodeScheduler._drain(stream, batched)

    def _put(self, stream, n):
        events = [self._event(i) for i in self.bursts[n]]
        self.history.extend(e[1] for e in events)
        if n == len(self.bursts) - 1:
            events.append(("done", None, None))
        _put_together(stream, events)

    def resume(self, generation_id, from_seq=0, deadline=None,
               batched=False):
        """What a finished generation's replay gives: its history from
        ``from_seq``, one list where the reader takes lists."""
        replay = list(self.history[from_seq:])
        self.batched.append(batched)
        return iter([replay] if batched else replay)

    def stats(self):
        return {}

    def close(self):
        pass


def _served(loop, cfg=None):
    model = LlamaGenerateModel(cfg=cfg or llama.tiny(vocab=512),
                               max_slots=2)
    model._params = object()     # nothing to load: the loop is a stand-in
    model._scheduler = loop
    core = InferenceServer([model])
    frontend = GrpcFrontend(core, port=0).start()
    return core, frontend, "127.0.0.1:{}".format(frontend.port)


@pytest.fixture
def serve():
    started = []

    def start(loop, cfg=None):
        core, frontend, url = _served(loop, cfg)
        started.append((core, frontend))
        return core, url

    faults.clear()
    yield start
    faults.clear()
    for core, frontend in started:
        frontend.stop()
        core.close()


def _inputs(n=8):
    ids = grpcclient.InferInput("PROMPT_IDS", [3], "INT32")
    ids.set_data_from_numpy(np.array([3, 1, 4], np.int32))
    budget = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
    budget.set_data_from_numpy(np.array([n], np.int32))
    return [ids, budget]


def _raw_responses(url, parameters):
    """Every response of one ``ModelStreamInfer`` call, the final one
    left out, read off the raw stub."""
    client = grpcclient.InferenceServerClient(url)
    try:
        request = _get_inference_request(
            model_name=MODEL, inputs=_inputs(), model_version="",
            request_id="", outputs=None,
            parameters=dict(parameters,
                            triton_enable_empty_final_response=True))
        out = []
        for r in client._stub.ModelStreamInfer(iter((request,))):
            assert not r.error_message, r.error_message
            resp = r.infer_response
            if not resp.parameters["triton_final_response"].bool_param:
                out.append(resp)
        return out
    finally:
        client.close()


def _tokens_of(resp):
    return np.frombuffer(resp.raw_output_contents[0], np.int32).tolist()


def _counters(core):
    families = parse_prometheus_text(core.metrics_text())
    return [next((v for _, labels, v in families.get(name, {}).get(
        "samples", ()) if labels == {"model": MODEL}), 0.0)
        for name in COUNTERS]


def test_a_backlog_leaves_in_one_response(serve):
    """Five tokens already waiting: one response, ``TOKEN`` of five,
    ``seq`` the first's, ``token_count`` 5; without the parameter, five
    responses of one."""
    _, url = serve(_Loop([list(range(5))]))
    (one,) = _raw_responses(url, {"multi_token_responses": True})
    assert _tokens_of(one) == [100 + i for i in range(5)]
    assert list(one.outputs[0].shape) == [5]
    assert one.parameters["seq"].int64_param == 0
    assert one.parameters["token_count"].int64_param == 5
    plain = _raw_responses(url, {})
    assert [_tokens_of(r) for r in plain] == [[100 + i] for i in range(5)]
    assert [r.parameters["seq"].int64_param for r in plain] == list(range(5))
    assert all("token_count" not in r.parameters for r in plain)


def test_generate_stream_yields_a_result_a_token(serve):
    loop = _Loop([list(range(5)), list(range(5, 8))])
    _, url = serve(loop)
    client = grpcclient.InferenceServerClient(url)
    try:
        results = list(client.generate_stream(MODEL, _inputs()))
    finally:
        client.close()
    assert loop.batched == [True]
    assert [int(r.as_numpy("TOKEN")[0]) for r in results] == [
        100 + i for i in range(8)]
    np.testing.assert_array_equal(
        [float(r.as_numpy("LOGPROB")[0]) for r in results],
        [-0.125 * i for i in range(8)])
    own = [r.get_response() for r in results]
    assert [r.parameters["seq"].int64_param for r in own] == list(range(8))
    assert all(list(r.outputs[0].shape) == [1] and
               "token_count" not in r.parameters for r in own)
    assert [list(r.get_output("TOKEN").shape) for r in results] == [[1]] * 8


def test_a_drop_inside_a_coalesced_response_resumes_exactly(serve):
    """The transport dies on the response that carries tokens 3-7 (all
    waiting together): the client resumes from seq 3, the replay comes
    as one response, and every token is yielded once, in order."""
    got_first = threading.Event()
    loop = _Loop([[0, 1, 2], [3, 4, 5, 6, 7]], gates=[got_first])
    _, url = serve(loop)
    faults.install("grpc.stream_infer", mode="raise", times=1, skip=1)
    reconnects = []
    client = grpcclient.InferenceServerClient(url)
    tokens, seqs = [], []
    try:
        for r in client.generate_stream(
                MODEL, _inputs(),
                parameters={"generation_id": "g-drop"},
                on_reconnect=lambda attempt, e: reconnects.append(attempt)):
            tokens.append(int(r.as_numpy("TOKEN")[0]))
            seqs.append(r.get_response().parameters["seq"].int64_param)
            got_first.set()
    finally:
        client.close()
    assert reconnects == [1]
    assert tokens == [100 + i for i in range(8)]
    assert seqs == list(range(8))
    assert loop.batched == [True, True]


def test_the_handoff_counters_count_every_token(serve):
    """``tpu_frontend_token_handoffs_total`` rises by exactly the tokens
    streamed, whatever the responses carried; emissions over responses
    is what a response carried."""
    gates = [threading.Event(), threading.Event()]
    core, url = serve(_Loop([list(range(6)), [6], [7]], gates=gates))
    before = _counters(core)
    client = grpcclient.InferenceServerClient(url)
    tokens = []
    try:
        for r in client.generate_stream(MODEL, _inputs()):
            tokens.append(r)
            if len(tokens) in (6, 7):
                gates[len(tokens) - 6].set()
    finally:
        client.close()
    handoffs, emissions, responses = (
        a - b for a, b in zip(_counters(core), before))
    assert len(tokens) == 8
    assert handoffs == emissions == 8
    assert responses == 3


def test_no_response_waits_while_a_token_is_available(serve):
    """Each token is put only once the client holds the one before: a
    handler that waited for a second token would never see it."""
    acks = [threading.Event() for _ in range(5)]
    _, url = serve(_Loop([[i] for i in range(6)], gates=acks))
    client = grpcclient.InferenceServerClient(url)
    t0 = time.monotonic()
    got = []
    try:
        for r in client.generate_stream(MODEL, _inputs(), read_timeout=5):
            got.append(int(r.as_numpy("TOKEN")[0]))
            if len(got) <= len(acks):
                acks[len(got) - 1].set()
    finally:
        client.close()
    assert got == [100 + i for i in range(6)]
    assert time.monotonic() - t0 < 5


def test_blocks_are_one_emission_a_response(serve):
    """A block configuration's blocks leave one a response even for a
    client that reads multi-token responses: a block is one emission."""
    loop = _Loop([[0, 1, 2]], blocks=True)
    core, url = serve(loop, llama.tiny_sdar(vocab=256))
    before = _counters(core)
    responses = _raw_responses(url, {"multi_token_responses": True})
    assert loop.batched == [False]
    assert [_tokens_of(r) for r in responses] == [
        [4 * i + j for j in range(4)] for i in range(3)]
    assert [r.parameters["seq"].int64_param for r in responses] == [0, 1, 2]
    _, emissions, count = (a - b for a, b in zip(_counters(core), before))
    assert emissions == count == 3


def test_the_shm_token_ring_is_never_coalesced():
    """A token a ring slot: with a ring the model's events carry one
    token each and are not mergeable, whatever the client reads."""
    model = LlamaGenerateModel(cfg=llama.tiny(vocab=512), max_slots=2)
    loop = _Loop([list(range(4))])
    model._scheduler = loop
    request = InferRequest(MODEL)
    request.multi_token = True
    written = []
    events = list(model._execute_scheduled(
        np.array([3, 1], np.int32), 4, None, request,
        ring_write=lambda seq, tok, lp: written.append(seq) or 8 * seq))
    assert loop.batched == [False]
    assert written == [0, 1, 2, 3]
    assert all(MERGEABLE_KEY not in e for e in events)


def _response(request_id, tokens, seq):
    resp = InferResponse(MODEL, "1", request_id, [(
        {"name": "TOKEN", "datatype": "INT32", "shape": [len(tokens)]},
        np.array(tokens, np.int32), None)], {"seq": seq})
    resp.emitted_at = [1.0] * len(tokens)
    resp.mergeable = True
    return resp


def test_waiting_responses_merge_by_request_in_order():
    """The handler's merge: each request's mergeable responses join its
    first still open, in order, until something else of that request
    comes; another request's never wait for them."""
    a, b = object(), object()
    err = pb.ModelStreamInferResponse(error_message="boom")
    waiting = [(None, _response("a", [1], 0), a),
               (None, _response("b", [7], 0), b),
               (None, _response("a", [2, 3], 1), a),
               (err, None, a),
               (None, _response("a", [4], 3), a),
               (None, _response("b", [8], 1), b)]
    sent = _merge_waiting(waiting)
    assert [(item is err, resp and resp.id) for item, resp in sent] == [
        (False, "a"), (False, "b"), (True, None), (False, "a")]
    first_a, first_b, _, last_a = (resp for _, resp in sent)
    assert first_a.outputs[0][1].tolist() == [1, 2, 3]
    assert first_a.parameters == {"seq": 0, "token_count": 3}
    assert first_a.emitted_at == [1.0] * 3
    assert first_b.outputs[0][1].tolist() == [7, 8]
    assert last_a.outputs[0][1].tolist() == [4]
    assert merge_responses([last_a]) is last_a
