"""Self-healing decode scheduler tests: supervised restart (watchdog +
budget), slot quarantine on the wire, and resumable generation streams
end-to-end over both frontends.

The acceptance bar (ISSUE 5):

(a) a NaN-poisoned slot fails with the typed error while co-batched
    streams complete token-identically (tests/test_continuous_batching
    proves the identity; here the wire mapping: HTTP 422 / gRPC
    INVALID_ARGUMENT);
(b) an injected loop death auto-restarts within the budget and
    in-flight streams complete identically (tests/test_chaos.py), a
    HUNG step restarts via the watchdog, and restart-budget exhaustion
    ends in unhealthy + drain;
(c) a client whose connection drops mid-generation transparently
    resumes (HTTP SSE via Last-Event-ID, gRPC via a resume token) with
    no duplicated or missing tokens.
"""

import json
import time

import numpy as np
import pytest

from tpuserver import faults
from tpuserver.core import InferenceServer, InferRequest, ServerError
from tpuserver.models import llama
from tpuserver.models.llama_serving import LlamaGenerateModel

pytestmark = pytest.mark.chaos

CFG = llama.tiny(vocab=512)
MAX_SEQ = 64
PROMPTS = [
    np.array([3, 1, 4, 1, 5], dtype=np.int32),
    np.array([9, 8, 7], dtype=np.int32),
    np.array([2, 7, 1, 8, 2, 8], dtype=np.int32),
]
BUDGETS = [8, 6, 7]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def heal_model():
    return LlamaGenerateModel(
        cfg=CFG, max_seq=MAX_SEQ, max_slots=2,
        max_restarts=64, restart_backoff_s=0.01)


@pytest.fixture(scope="module")
def heal_core(heal_model):
    return InferenceServer([heal_model])


@pytest.fixture(scope="module")
def reference_tokens(heal_core):
    return [
        _generate(heal_core, p, n) for p, n in zip(PROMPTS, BUDGETS)
    ]


def _generate(core, prompt, n_tokens, parameters=None):
    req = InferRequest(
        "llama_generate",
        inputs={
            "PROMPT_IDS": np.asarray(prompt, np.int32),
            "MAX_TOKENS": np.array([n_tokens], dtype=np.int32),
        },
        parameters=parameters or {},
    )
    return [
        int(arr[0])
        for resp in core.infer_stream(req)
        for spec, arr, _ in resp.outputs
        if spec["name"] == "TOKEN"
    ]


# -- quarantine on the wire --------------------------------------------------


def test_quarantine_maps_to_http_422_and_grpc_inband(
        heal_core, reference_tokens):
    """The typed SlotQuarantined reaches the wire: HTTP 422 on
    /generate, the quarantine message in-band on the decoupled gRPC
    stream — and the scheduler stays healthy (no restart burned)."""
    import http.client

    import tritonclient.grpc as grpcclient
    from tritonclient.utils import InferenceServerException

    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend

    _generate(heal_core, PROMPTS[1], 2)  # warm: slot 0 free
    restarts = heal_core._models["llama_generate"]._scheduler.stats()[
        "restarts"]
    http_f = HttpFrontend(heal_core, port=0).start()
    grpc_f = GrpcFrontend(heal_core, port=0).start()
    try:
        # poison slot 0 on the victim's first step: the request is the
        # only live stream, so it deterministically owns slot 0
        faults.install("scheduler.step", mode="nan", times=1, delay=0)
        body = json.dumps({
            "inputs": [
                {"name": "PROMPT_IDS", "datatype": "INT32",
                 "shape": [len(PROMPTS[0])], "data": PROMPTS[0].tolist()},
                {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
                 "data": [BUDGETS[0]]},
            ]
        })
        conn = http.client.HTTPConnection("127.0.0.1", http_f.port)
        try:
            conn.request("POST", "/v2/models/llama_generate/generate",
                         body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
            assert resp.status == 422, (resp.status, payload)
            assert b"quarantined" in payload
        finally:
            conn.close()
        # gRPC decoupled: the typed error arrives in-band on the stream
        faults.install("scheduler.step", mode="nan", times=1, delay=0)
        client = grpcclient.InferenceServerClient(
            "127.0.0.1:{}".format(grpc_f.port))
        try:
            p_in = grpcclient.InferInput(
                "PROMPT_IDS", [len(PROMPTS[0])], "INT32")
            p_in.set_data_from_numpy(PROMPTS[0])
            m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
            m_in.set_data_from_numpy(np.array([BUDGETS[0]], np.int32))
            with pytest.raises(InferenceServerException,
                               match="quarantined"):
                list(client.generate_stream(
                    "llama_generate", [p_in, m_in]))
        finally:
            client.close()
        # no restart was burned and later runs are untouched
        stats = heal_core._models["llama_generate"]._scheduler.stats()
        assert stats["restarts"] == restarts
        assert stats["quarantined"] >= 2
        assert heal_core.server_ready()
        assert _generate(
            heal_core, PROMPTS[0], BUDGETS[0]) == reference_tokens[0]
    finally:
        faults.clear("scheduler.step")
        grpc_f.stop()
        http_f.stop()


# -- watchdog + restart budget -----------------------------------------------


def test_watchdog_restarts_hung_step_and_stream_completes():
    """A step wedged past step_timeout_s is demoted (epoch bump) and the
    supervisor restarts the loop; the in-flight stream is re-admitted
    and completes token-identically while the zombie thread's late
    deliveries are dropped."""
    model = LlamaGenerateModel(
        cfg=CFG, max_seq=MAX_SEQ, max_slots=2,
        # generous deadline during warmup: the FIRST step's XLA compile
        # runs inside the heartbeat window and must not read as a hang
        # (docs: warm up before tightening step_timeout_s)
        step_timeout_s=30.0, max_restarts=8, restart_backoff_s=0.01)
    core = InferenceServer([model])
    try:
        reference = _generate(core, PROMPTS[0], BUDGETS[0])  # warm/compile
        model._scheduler._step_timeout_s = 0.5  # compiled: tighten
        faults.install("scheduler.step", mode="hang", times=1, delay=2.5,
                       skip=2)
        t0 = time.monotonic()
        tokens = _generate(core, PROMPTS[0], BUDGETS[0])
        elapsed = time.monotonic() - t0
        assert tokens == reference
        # the WATCHDOG unblocked the stream (hang stalls inside the
        # heartbeat window): completion must beat the hang's natural end
        assert elapsed < 2.5, elapsed
        stats = model._scheduler.stats()
        assert stats["restarts"] == 1
        assert model.healthy()
        # the zombie wakes (2.5s) and must not corrupt a later run
        time.sleep(2.0)
        assert _generate(core, PROMPTS[0], BUDGETS[0]) == reference
    finally:
        faults.clear("scheduler.step")
        core.close()


def test_restart_budget_exhaustion_trips_unhealthy_then_drains():
    """Repeated unattributable failures escalate to today's permanently-
    tripped behavior: streams fail typed, readiness flips false (pools
    rotate the replica out), submits are rejected, drain still works."""
    model = LlamaGenerateModel(
        cfg=CFG, max_seq=MAX_SEQ, max_slots=2,
        max_restarts=2, restart_backoff_s=0.01)
    core = InferenceServer([model])
    try:
        _generate(core, PROMPTS[1], 2)  # warm
        faults.install("scheduler.step", mode="raise", times=-1)
        with pytest.raises(ServerError) as exc:
            _generate(core, PROMPTS[0], BUDGETS[0])
        assert "restart budget exhausted" in str(exc.value)
        faults.clear("scheduler.step")
        stats = model._scheduler.stats()
        assert stats["tripped"] and not stats["healthy"]
        assert stats["restarts"] == 2
        assert not model.healthy()
        assert not core.server_ready()
        # tripped is sticky: new submits are rejected typed
        with pytest.raises(ServerError, match="tripped"):
            _generate(core, PROMPTS[1], 2)
        # ... and the replica still drains deterministically
        core.drain(timeout=5.0)
        assert core.server_state() == "stopped"
    finally:
        faults.clear("scheduler.step")
        core.close()


# -- scheduler-level resume --------------------------------------------------


def test_abandoned_stream_parks_and_resume_splices(heal_core, heal_model,
                                                   reference_tokens):
    """Disconnect mid-generation -> the stream parks in the replay
    buffer; resume(gen_id, from_seq) replays the missed tokens and
    splices the live continuation with no duplicates or gaps."""
    sched = heal_model._scheduler
    stream = sched.submit(PROMPTS[0], BUDGETS[0], generation_id="g-splice")
    got = [next(stream) for _ in range(3)]
    stream.close()  # consumer walks away after 3 tokens
    deadline = time.monotonic() + 5
    while ("g-splice" not in sched._replay
           and time.monotonic() < deadline):
        time.sleep(0.01)  # the cancel-reap parks it between steps
    assert "g-splice" in sched._replay
    # the reconnecting client saw only 2 of the 3 delivered tokens
    resumed = list(sched.resume("g-splice", from_seq=2))
    tokens = [t for t, _ in got[:2]] + [t for t, _ in resumed]
    assert tokens == reference_tokens[0]
    # the continuation ran to completion, so the id re-parked as a
    # COMPLETED entry: a later resume replays from the buffer alone
    assert [t for t, _ in sched.resume("g-splice", 0)] == (
        reference_tokens[0])
    # an interrupted entry, by contrast, is consumed exactly once
    from tpuserver.scheduler import UnknownGeneration

    with pytest.raises(UnknownGeneration):
        list(sched.resume("never-issued", 0))


def test_resume_carries_the_reconnects_fresh_deadline(
        heal_model, heal_core, reference_tokens):
    """The original request's deadline died with its connection: a
    reconnect with a fresh (or no) deadline must not be killed by the
    stale bound."""
    sched = heal_model._scheduler
    stream = sched.submit(PROMPTS[2], BUDGETS[2],
                          generation_id="g-deadline",
                          deadline=time.monotonic() + 1.0)
    got = [next(stream) for _ in range(2)]
    stream.close()
    deadline = time.monotonic() + 5
    while ("g-deadline" not in sched._replay
           and time.monotonic() < deadline):
        time.sleep(0.01)
    time.sleep(1.1)  # the ORIGINAL deadline is now expired
    resumed = list(sched.resume("g-deadline", from_seq=2, deadline=None))
    tokens = [t for t, _ in got] + [t for t, _ in resumed]
    assert tokens == reference_tokens[2]


def test_completed_generation_tail_replays(heal_model, heal_core,
                                           reference_tokens):
    """A generation that finished while the client was away replays its
    tail from the buffer (repeatedly, within the TTL)."""
    sched = heal_model._scheduler
    stream = sched.submit(PROMPTS[1], BUDGETS[1], generation_id="g-tail")
    full = [t for t, _ in stream]
    assert full == reference_tokens[1]
    for _ in range(2):  # completed tails replay more than once
        tail = [t for t, _ in sched.resume("g-tail", from_seq=4)]
        assert tail == reference_tokens[1][4:]


def test_replay_buffer_ttl_expires_entries():
    model = LlamaGenerateModel(
        cfg=CFG, max_seq=MAX_SEQ, max_slots=2, replay_ttl_s=0.05,
        restart_backoff_s=0.01)
    core = InferenceServer([model])
    try:
        _generate(core, PROMPTS[1], 2, {"generation_id": "g-ttl"})
        sched = model._scheduler
        time.sleep(0.2)
        from tpuserver.scheduler import UnknownGeneration

        with pytest.raises(UnknownGeneration, match="g-ttl"):
            list(sched.resume("g-ttl", 0))
        # through the core the miss is a typed 404
        with pytest.raises(ServerError) as exc:
            _generate(core, PROMPTS[1], 2,
                      {"resume_generation_id": "g-ttl",
                       "resume_from_seq": 0})
        assert exc.value.code == 404
    finally:
        core.close()


# -- client auto-resume end-to-end -------------------------------------------


def test_http_sse_client_resumes_across_injected_disconnect(
        heal_core, reference_tokens):
    """The HTTP client's generate_stream transparently reconnects with
    Last-Event-ID after a mid-stream connection drop: the server
    replays from the buffer and the client splices — no duplicated or
    missing tokens."""
    import tritonclient.http as httpclient

    from tpuserver.http_frontend import HttpFrontend

    frontend = HttpFrontend(heal_core, port=0).start()
    client = httpclient.InferenceServerClient(
        "127.0.0.1:{}".format(frontend.port))
    try:
        # sever the connection after the 3rd SSE event
        faults.install("http.generate_stream", mode="raise", times=1,
                       skip=3)
        reconnects = []
        tokens = []
        seqs = []
        for event in client.generate_stream(
                "llama_generate",
                {"PROMPT_IDS": PROMPTS[0],
                 "MAX_TOKENS": np.array([BUDGETS[0]], np.int32)},
                on_reconnect=lambda a, e: reconnects.append(a)):
            for out in event.get("outputs", []):
                if out["name"] == "TOKEN":
                    tokens.append(out["data"][0])
            seqs.append(event["parameters"]["seq"])
        assert tokens == reference_tokens[0]
        assert seqs == list(range(BUDGETS[0]))
        assert len(reconnects) == 1
    finally:
        faults.clear("http.generate_stream")
        client.close()
        frontend.stop()


def test_grpc_client_resumes_across_injected_stream_kill(
        heal_core, reference_tokens):
    """The gRPC client's generate_stream re-opens the bidi stream with a
    resume token after a stream-level failure and splices."""
    import tritonclient.grpc as grpcclient

    from tpuserver.grpc_frontend import GrpcFrontend

    frontend = GrpcFrontend(heal_core, port=0).start()
    client = grpcclient.InferenceServerClient(
        "127.0.0.1:{}".format(frontend.port))
    try:
        # a token a step, each sent before the next comes (a response a
        # token): the drop comes after the third token, mid-generation
        faults.install("scheduler.step", mode="slow", delay=0.03)
        faults.install("grpc.stream_infer", mode="raise", times=1, skip=3)
        p_in = grpcclient.InferInput("PROMPT_IDS", [len(PROMPTS[0])],
                                     "INT32")
        p_in.set_data_from_numpy(PROMPTS[0])
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([BUDGETS[0]], dtype=np.int32))
        reconnects = []
        tokens = []
        seqs = []
        for result in client.generate_stream(
                "llama_generate", [p_in, m_in],
                on_reconnect=lambda a, e: reconnects.append(a)):
            tokens.append(int(result.as_numpy("TOKEN")[0]))
            resp = result.get_response()
            seqs.append(resp.parameters["seq"].int64_param)
        assert tokens == reference_tokens[0]
        assert seqs == list(range(BUDGETS[0]))
        assert len(reconnects) == 1
    finally:
        faults.clear("grpc.stream_infer")
        faults.clear("scheduler.step")
        client.close()
        frontend.stop()


def test_clients_refuse_to_rerun_non_resumable_generations():
    """A drop mid-generation against a NON-resumable server (the
    max_slots=1 single-stream path issues no generation ids) must fail
    typed, never silently re-run the generation — a blind re-send
    after yielding tokens would duplicate them and re-execute
    server-side effects (KV parking)."""
    import tritonclient.grpc as grpcclient
    import tritonclient.http as httpclient
    from tritonclient.utils import InferenceServerException

    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend

    core = InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ)  # max_slots=1
    ])
    http_f = HttpFrontend(core, port=0).start()
    grpc_f = GrpcFrontend(core, port=0).start()
    hc = httpclient.InferenceServerClient(
        "127.0.0.1:{}".format(http_f.port))
    gc = grpcclient.InferenceServerClient(
        "127.0.0.1:{}".format(grpc_f.port))
    try:
        faults.install("http.generate_stream", mode="raise", times=1,
                       skip=2)
        n_tokens = 0
        with pytest.raises(InferenceServerException,
                           match="not resumable"):
            for event in hc.generate_stream(
                    "llama_generate",
                    {"PROMPT_IDS": PROMPTS[0],
                     "MAX_TOKENS": np.array([BUDGETS[0]], np.int32)}):
                n_tokens += 1
        assert 0 < n_tokens < BUDGETS[0]  # dropped mid-generation

        faults.install("grpc.stream_infer", mode="raise", times=1,
                       skip=2)
        p_in = grpcclient.InferInput(
            "PROMPT_IDS", [len(PROMPTS[0])], "INT32")
        p_in.set_data_from_numpy(PROMPTS[0])
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([BUDGETS[0]], np.int32))
        n_tokens = 0
        with pytest.raises(InferenceServerException,
                           match="not resumable"):
            for result in gc.generate_stream(
                    "llama_generate", [p_in, m_in]):
                n_tokens += 1
        assert 0 < n_tokens < BUDGETS[0]
        # a non-200 response surfaces as a typed error with its status
        # (regression: the error-message helper took one argument)
        with pytest.raises(InferenceServerException) as exc:
            list(hc.generate_stream(
                "no_such_model",
                {"PROMPT_IDS": PROMPTS[0],
                 "MAX_TOKENS": np.array([2], np.int32)}))
        assert exc.value.status() == "404", exc.value
    finally:
        faults.clear()
        hc.close()
        gc.close()
        grpc_f.stop()
        http_f.stop()
        core.close()


def test_pool_generate_stream_pins_one_endpoint(reference_tokens):
    """EndpointPool.generate_stream runs the whole generation (including
    any resume) against ONE replica: replay state is replica-local."""
    import tritonclient.http as httpclient

    from tpuserver.http_frontend import HttpFrontend

    models = [
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2,
                           restart_backoff_s=0.01)
        for _ in range(2)
    ]
    cores = [InferenceServer([m]) for m in models]
    frontends = [HttpFrontend(c, port=0).start() for c in cores]
    pool = httpclient.EndpointPool(
        ["127.0.0.1:{}".format(f.port) for f in frontends])
    try:
        tokens = []
        gen_ids = set()
        for event in pool.generate_stream(
                "llama_generate",
                {"PROMPT_IDS": PROMPTS[1],
                 "MAX_TOKENS": np.array([BUDGETS[1]], np.int32)}):
            for out in event.get("outputs", []):
                if out["name"] == "TOKEN":
                    tokens.append(out["data"][0])
            gen_ids.add(event["parameters"]["generation_id"])
        assert tokens == reference_tokens[1]
        assert len(gen_ids) == 1
        # exactly one replica served it (the other's scheduler was
        # never even built) — the pin in action
        built = [m._scheduler is not None for m in models]
        assert built.count(True) == 1
    finally:
        pool.close()
        for f in frontends:
            f.stop()
        for c in cores:
            c.close()


# -- fleet transitions (ISSUE 7 client gap) ----------------------------------


def test_http_resume_404_is_a_fleet_transition_not_a_verdict(
        heal_core, reference_tokens):
    """A resume attempt that lands on a server which does not know the
    generation id answers 404 — but behind a fleet router the backend
    set can change under one address mid-generation (router restart,
    handoff in progress), so the HTTP auto-resume helper retries the
    resume instead of dying typed: seq continuity is the contract, not
    endpoint identity.  A 404 on the FIRST request (no Last-Event-ID)
    stays terminal — that is pinned by
    test_clients_refuse_to_rerun_non_resumable_generations."""
    import tritonclient.http as httpclient

    from tpuserver.http_frontend import HttpFrontend

    stranger = InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2)
    ])
    frontend = HttpFrontend(heal_core, port=0).start()
    client = httpclient.InferenceServerClient(
        "127.0.0.1:{}".format(frontend.port))
    try:
        faults.install("http.generate_stream", mode="raise", times=1,
                       skip=3)
        attempts = []

        def on_reconnect(attempt, exc):
            attempts.append(str(exc))
            # reconnect 1 lands on a backend that has never seen the
            # generation (the fleet changed under the address) -> 404;
            # reconnect 2 finds the owning backend again
            frontend._httpd.core = (
                stranger if attempt == 1 else heal_core)

        tokens, seqs = [], []
        for event in client.generate_stream(
                "llama_generate",
                {"PROMPT_IDS": PROMPTS[2],
                 "MAX_TOKENS": np.array([BUDGETS[2]], np.int32)},
                on_reconnect=on_reconnect):
            for out in event.get("outputs", []):
                if out["name"] == "TOKEN":
                    tokens.append(out["data"][0])
            seqs.append(event["parameters"]["seq"])
        assert tokens == reference_tokens[2]
        assert seqs == list(range(BUDGETS[2]))
        assert len(attempts) == 2
        # the second reattempt was triggered by the typed resume 404,
        # not a transport fault — the new retryable classification
        assert "does not know generation" in attempts[1]
    finally:
        faults.clear("http.generate_stream")
        frontend._httpd.core = heal_core
        client.close()
        frontend.stop()
        stranger.close()


def test_grpc_resume_unknown_generation_retries_as_fleet_transition(
        heal_core, reference_tokens):
    """gRPC side of the same gap: the in-band unknown-generation answer
    to OUR resume request rides the reconnect path (bounded by
    max_reconnects) instead of raising terminally.  Other in-band
    errors (quarantine, deadline) stay terminal."""
    import tritonclient.grpc as grpcclient

    from tpuserver.grpc_frontend import GrpcFrontend

    stranger = InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2)
    ])
    frontend = GrpcFrontend(heal_core, port=0).start()
    client = grpcclient.InferenceServerClient(
        "127.0.0.1:{}".format(frontend.port))
    try:
        # a token a step, each sent before the next comes (a response a
        # token): the drop comes after the third token, mid-generation
        faults.install("scheduler.step", mode="slow", delay=0.03)
        faults.install("grpc.stream_infer", mode="raise", times=1, skip=3)
        attempts = []

        def on_reconnect(attempt, exc):
            attempts.append(str(exc))
            frontend._bridge._core = (
                stranger if attempt == 1 else heal_core)

        p_in = grpcclient.InferInput(
            "PROMPT_IDS", [len(PROMPTS[2])], "INT32")
        p_in.set_data_from_numpy(PROMPTS[2])
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([BUDGETS[2]], dtype=np.int32))
        tokens, seqs = [], []
        for result in client.generate_stream(
                "llama_generate", [p_in, m_in],
                on_reconnect=on_reconnect):
            tokens.append(int(result.as_numpy("TOKEN")[0]))
            resp = result.get_response()
            seqs.append(resp.parameters["seq"].int64_param)
        assert tokens == reference_tokens[2]
        assert seqs == list(range(BUDGETS[2]))
        assert len(attempts) == 2
        assert "unknown or expired generation id" in attempts[1]
    finally:
        faults.clear("grpc.stream_infer")
        faults.clear("scheduler.step")
        frontend._bridge._core = heal_core
        client.close()
        frontend.stop()
        stranger.close()
