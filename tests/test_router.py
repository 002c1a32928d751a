"""Fleet-router tests (ISSUE 7 acceptance).

The router makes N replicas look like one resilient KServe server for
PLAIN clients — no EndpointPool.  The bar:

(a) kill the home replica mid-generation and the stream completes
    THROUGH the router with token-identical, gap-free, duplicate-free
    output, without the client ever reconnecting (cross-replica
    handoff: greedy re-prefill of prompt + emitted history);
(b) a client that reconnects with Last-Event-ID routes home to the
    replica that owns the replay state (sticky resume);
(c) a draining replica rotates out BEFORE a request lands on it, and
    rotates back in after mark_ready;
(d) the router-level in-flight cap sheds with a typed 429 +
    Retry-After instead of queueing, and connect-phase failures fail
    over with zero user-visible errors;
(e) every replica exposes the cheap /v2/health/stats routing snapshot
    the prober polls (no per-model inference-statistics calls).

``tools/chaos_smoke.py --router`` soaks (a)-(d) against real replica
processes under SIGTERM/revive.
"""

import http.client as http_client
import json
import threading
import time

import numpy as np
import pytest

from tpuserver import faults
from tpuserver.core import InferenceServer
from tpuserver.http_frontend import HttpFrontend
from tpuserver.models import default_models, llama
from tpuserver.models.llama_serving import LlamaGenerateModel
from tpuserver.router import FleetRouter

pytestmark = pytest.mark.router

CFG = llama.tiny(vocab=512)
MAX_SEQ = 64
PROMPT = [3, 1, 4, 1, 5]
N_TOK = 8

STREAM_PATH = "/v2/models/llama_generate/generate_stream"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _make_replica(scope=None, with_llama=True):
    models = default_models()
    if with_llama:
        models.append(LlamaGenerateModel(
            cfg=CFG, max_seq=MAX_SEQ, max_slots=2,
            restart_backoff_s=0.01))
    core = InferenceServer(models, fault_scope=scope)
    frontend = HttpFrontend(core, port=0).start()
    return core, frontend


def _make_unresumable_replica(scope):
    """max_slots=1 = the pre-scheduler single-stream path: no stream
    ids on the wire, so routed streams are passthrough-only."""
    models = default_models()
    models.append(LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=1))
    core = InferenceServer(models, fault_scope=scope)
    frontend = HttpFrontend(core, port=0).start()
    return core, frontend


@pytest.fixture(scope="module")
def fleet():
    """Two llama replicas behind one router (probes at 10 Hz so drain
    rotation is visible within a test timeout)."""
    core_a, fe_a = _make_replica("router-a")
    core_b, fe_b = _make_replica("router-b")
    backends = ["127.0.0.1:{}".format(fe_a.port),
                "127.0.0.1:{}".format(fe_b.port)]
    router = FleetRouter(backends, probe_interval_s=0.1,
                         gen_ttl_s=30.0).start()
    yield {
        "router": router,
        "backends": backends,
        "cores": (core_a, core_b),
        "frontends": (fe_a, fe_b),
        "scopes": ("router-a", "router-b"),
    }
    router.stop()
    fe_a.stop()
    fe_b.stop()
    core_a.close()
    core_b.close()


@pytest.fixture(scope="module")
def reference_tokens(fleet):
    """Greedy decode is deterministic and both replicas share weights:
    one replica's direct answer is the fleet-wide truth every routed /
    handed-off stream must reproduce byte-for-byte."""
    import tritonclient.http as httpclient

    client = httpclient.InferenceServerClient(fleet["backends"][0])
    try:
        return _stream_tokens(client)
    finally:
        client.close()


def _stream_tokens(client, parameters=None, on_reconnect=None):
    tokens = []
    for event in client.generate_stream(
            "llama_generate",
            {"PROMPT_IDS": np.array(PROMPT, np.int32),
             "MAX_TOKENS": np.array([N_TOK], np.int32)},
            parameters=parameters, on_reconnect=on_reconnect):
        for out in event.get("outputs", []):
            if out["name"] == "TOKEN":
                tokens.append(int(out["data"][0]))
    return tokens


def _stream_body(gen_id=None):
    body = {
        "inputs": [
            {"name": "PROMPT_IDS", "shape": [len(PROMPT)],
             "datatype": "INT32", "data": PROMPT},
            {"name": "MAX_TOKENS", "shape": [1], "datatype": "INT32",
             "data": [N_TOK]},
        ],
    }
    if gen_id is not None:
        body["parameters"] = {"generation_id": gen_id}
    return json.dumps(body)


def _open_stream(url, body, last_event_id=None):
    host, _, port = url.rpartition(":")
    conn = http_client.HTTPConnection(host, int(port), timeout=30)
    headers = {"Content-Type": "application/json"}
    if last_event_id is not None:
        headers["Last-Event-ID"] = last_event_id
    conn.request("POST", STREAM_PATH, body=body, headers=headers)
    return conn, conn.getresponse()


def _read_events(resp, limit=None):
    """``(payloads, finished)``: data events until the in-band final
    marker (or ``limit`` events)."""
    events = []
    for raw in resp:
        line = raw.strip()
        if not line.startswith(b"data: "):
            continue
        payload = json.loads(line[len(b"data: "):])
        if payload.get("final"):
            return events, True
        assert "error" not in payload, payload
        events.append(payload)
        if limit is not None and len(events) >= limit:
            return events, False
    return events, False


def _tokens_of(events):
    return [int(out["data"][0]) for ev in events
            for out in ev.get("outputs", [])
            if out["name"] == "TOKEN"]


def _get_json(url, path):
    host, _, port = url.rpartition(":")
    conn = http_client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _wait_until(predicate, timeout_s=5.0, interval_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


# -- health/load snapshot (satellite: routing signal) -------------------------


def test_replica_health_stats_snapshot_shape_and_bounds(fleet,
                                                        reference_tokens):
    """/v2/health/stats is the cheap machine-readable routing signal:
    lifecycle + in-flight bounds + each model's scheduler counters with
    their capacity bounds — and NOT the per-model inference-statistics
    verb (the prober polls this at sub-second cadence fleet-wide)."""
    status, snap = _get_json(fleet["backends"][0], "/v2/health/stats")
    assert status == 200
    assert snap["state"] == "ready" and snap["ready"] is True
    assert snap["inflight"] >= 0
    if snap["max_inflight"] is not None:  # None = uncapped server
        assert snap["inflight"] <= snap["max_inflight"]
    assert "llama_generate" in snap["models"]
    sched = snap["models"]["llama_generate"]
    # reference_tokens ran a generation on replica A: its scheduler
    # stats must be live, with count <= bound (the utilization signal)
    assert sched is not None
    assert 0 <= sched["live_streams"] <= sched["max_slots"]
    assert 0 <= sched["pending"] <= sched["max_pending"]
    for key in ("tripped", "restarts", "replay_entries", "draining",
                "healthy"):
        assert key in sched
    # schedulerless models report None, not a stats blob — the snapshot
    # stays O(models), never O(inference history)
    assert snap["models"]["simple"] is None
    # cheap enough to poll: 50 snapshots well under a second apiece
    t0 = time.monotonic()
    for _ in range(50):
        _get_json(fleet["backends"][0], "/v2/health/stats")
    assert time.monotonic() - t0 < 10.0


def test_router_surface_matches_replica(fleet):
    """The router speaks the replica's own surface (live/ready/stats)
    plus /router/stats, so routers stack and pools can probe them."""
    router_url = fleet["router"].url
    status, snap = _get_json(router_url, "/v2/health/stats")
    assert status == 200
    assert snap["ready"] is True and snap["router"] is True
    status, stats = _get_json(router_url, "/router/stats")
    assert status == 200
    assert {r["url"] for r in stats["replicas"]} == set(fleet["backends"])
    for rep in stats["replicas"]:
        assert rep["eligible"] is True
    assert stats["shed"] >= 0 and stats["inflight"] >= 0
    host, _, port = router_url.rpartition(":")
    conn = http_client.HTTPConnection(host, int(port), timeout=10)
    try:
        conn.request("GET", "/v2/health/ready")
        assert conn.getresponse().status == 200
    finally:
        conn.close()


# -- routing ------------------------------------------------------------------


def test_unary_routes_through_router(fleet):
    import tritonclient.http as httpclient

    client = httpclient.InferenceServerClient(fleet["router"].url)
    try:
        assert client.is_server_live()
        assert client.is_server_ready()
        in0 = httpclient.InferInput("INPUT0", [16], "INT32")
        in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
        in1 = httpclient.InferInput("INPUT1", [16], "INT32")
        in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
        result = client.infer("simple", [in0, in1])
        np.testing.assert_array_equal(
            result.as_numpy("OUTPUT0"),
            np.arange(16, dtype=np.int32) + 1)
    finally:
        client.close()


def test_least_loaded_spreads_concurrent_requests(fleet):
    """With one replica occupied, the next request routes to the other:
    the probe load score plus the router's own in-flight accounting."""
    import tritonclient.http as httpclient

    router = fleet["router"]
    before = {r["url"]: r["requests"] for r in router.stats()["replicas"]}
    client = httpclient.InferenceServerClient(router.url)
    slow_done = threading.Event()

    def slow():
        c = httpclient.InferenceServerClient(router.url)
        try:
            in0 = httpclient.InferInput("INPUT0", [4], "INT32")
            in0.set_data_from_numpy(np.arange(4, dtype=np.int32))
            d = httpclient.InferInput("DELAY_US", [1], "UINT32")
            d.set_data_from_numpy(np.array([400000], dtype=np.uint32))
            c.infer("delayed_identity", [in0, d])
        finally:
            c.close()
            slow_done.set()

    t = threading.Thread(target=slow, daemon=True)
    t.start()
    try:
        # identify the busy replica by its REQUEST counter (bumped the
        # instant the router dials it) rather than the load score: the
        # prober's load contribution can be stale — the previous
        # test's request caught mid-flight by a probe reads as load on
        # the wrong replica for up to a probe interval
        assert _wait_until(lambda: any(
            r["requests"] == before[r["url"]] + 1
            for r in router.stats()["replicas"]))
        busy = next(r["url"] for r in router.stats()["replicas"]
                    if r["requests"] == before[r["url"]] + 1)
        # and let any stale probe load on the OTHER replica settle to
        # zero before routing the probe request, or the least-loaded
        # pick below would be comparing ghosts
        assert _wait_until(lambda: all(
            r["url"] == busy or r["load"] <= 0
            for r in router.stats()["replicas"]))
        in0 = httpclient.InferInput("INPUT0", [16], "INT32")
        in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
        in1 = httpclient.InferInput("INPUT1", [16], "INT32")
        in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
        client.infer("simple", [in0, in1])
        after = {r["url"]: r["requests"]
                 for r in router.stats()["replicas"]}
        other = next(u for u in after if u != busy)
        assert after[other] == before[other] + 1
    finally:
        t.join(timeout=10)
        client.close()
    assert slow_done.is_set()


def test_drain_rotates_replica_out_before_requests_land(fleet):
    """begin_drain flips the replica's own readiness; the prober folds
    it into eligibility so requests stop landing there BEFORE one
    fails — and mark_ready rotates it back in (ops undrain)."""
    import tritonclient.http as httpclient

    router = fleet["router"]
    core_a = fleet["cores"][0]
    url_a, url_b = fleet["backends"]
    core_a.begin_drain()
    try:
        assert _wait_until(lambda: not next(
            r["eligible"] for r in router.stats()["replicas"]
            if r["url"] == url_a))
        before_a = next(r["requests"] for r in router.stats()["replicas"]
                        if r["url"] == url_a)
        client = httpclient.InferenceServerClient(router.url)
        try:
            in0 = httpclient.InferInput("INPUT0", [16], "INT32")
            in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
            in1 = httpclient.InferInput("INPUT1", [16], "INT32")
            in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
            for _ in range(6):
                client.infer("simple", [in0, in1])  # zero errors
        finally:
            client.close()
        after_a = next(r["requests"] for r in router.stats()["replicas"]
                       if r["url"] == url_a)
        assert after_a == before_a  # drained replica saw none of them
    finally:
        core_a.mark_ready()
    assert _wait_until(lambda: next(
        r["eligible"] for r in router.stats()["replicas"]
        if r["url"] == url_a))


# -- streaming: handoff + sticky resume --------------------------------------


def test_home_replica_death_mid_generation_hands_off(fleet,
                                                     reference_tokens):
    """THE acceptance case: the serving replica's connection dies
    mid-generation (times=1 on each scope: whichever replica is home
    drops the stream after 3 events); the router re-admits
    prompt + emitted history on the other replica and the client sees
    one continuous, token-identical, gap-free, duplicate-free stream —
    it never reconnects, never learns a handoff happened."""
    import tritonclient.http as httpclient

    router = fleet["router"]
    for scope in fleet["scopes"]:
        faults.install("http.generate_stream", mode="raise", times=1,
                       skip=3, scope=scope)
    handoffs_before = router.stats()["handoffs"]
    reconnects = []
    client = httpclient.InferenceServerClient(router.url)
    try:
        tokens = _stream_tokens(
            client, parameters={"generation_id": "t-handoff"},
            on_reconnect=lambda a, e: reconnects.append(a))
    finally:
        client.close()
    assert tokens == reference_tokens
    assert reconnects == []  # the handoff is invisible to the client
    assert router.stats()["handoffs"] > handoffs_before


def test_sticky_resume_routes_home_and_replays_gap(fleet,
                                                   reference_tokens):
    """A client that drops and reconnects with Last-Event-ID gets the
    gap replayed from the router's buffer and the continuation spliced
    from the generation's home replica — same id, continuous seqs."""
    router = fleet["router"]
    resumed_before = router.stats()["resumed_streams"]
    body = _stream_body("t-sticky")
    conn, resp = _open_stream(router.url, body)
    try:
        head, finished = _read_events(resp, limit=3)
        assert not finished and len(head) == 3
    finally:
        conn.close()  # the client vanishes mid-stream
    home = router.generation_snapshot("t-sticky")["home"]
    assert home in fleet["backends"]
    last_seq = head[-1]["parameters"]["seq"]
    assert last_seq == 2
    conn, resp = _open_stream(
        router.url, body, last_event_id="t-sticky/{}".format(last_seq))
    try:
        tail, finished = _read_events(resp)
        assert finished
    finally:
        conn.close()
    assert _tokens_of(head) + _tokens_of(tail) == reference_tokens
    seqs = [ev["parameters"]["seq"] for ev in head + tail]
    assert seqs == list(range(N_TOK))
    assert router.stats()["resumed_streams"] > resumed_before
    # stickiness: the resume did not migrate a live home
    assert router.generation_snapshot("t-sticky")["home"] == home


def test_duplicate_generation_id_is_typed_400(fleet):
    """A fresh submit reusing a known generation_id must NOT clobber
    the existing record's replay buffer and home mapping — it gets a
    typed 400 (resume, don't resubmit)."""
    url = fleet["router"].url
    conn, resp = _open_stream(url, _stream_body(gen_id="dup-id"))
    try:
        assert resp.status == 200
        events, finished = _read_events(resp)
        assert finished
        first_tokens = _tokens_of(events)
        assert len(first_tokens) == N_TOK
    finally:
        conn.close()
    conn, resp = _open_stream(url, _stream_body(gen_id="dup-id"))
    try:
        assert resp.status == 400
        assert "already in use" in json.loads(resp.read())["error"]
    finally:
        conn.close()
    # the original record survived the rejected duplicate: its replay
    # buffer still answers a sticky resume with the same tokens
    conn, resp = _open_stream(url, _stream_body(),
                              last_event_id="dup-id/-1")
    try:
        assert resp.status == 200
        events, finished = _read_events(resp)
        assert finished
        assert _tokens_of(events) == first_tokens
    finally:
        conn.close()


def test_resume_of_unknown_generation_is_typed_404(fleet):
    """Neither the router nor any replica knows the id: the fleet-wide
    answer is the replicas' own typed 404, not a router-invented
    shape."""
    conn, resp = _open_stream(fleet["router"].url, _stream_body(),
                              last_event_id="never-issued/4")
    try:
        assert resp.status == 404
        assert "generation" in json.loads(resp.read())["error"]
    finally:
        conn.close()


# -- shedding + failover ------------------------------------------------------


def test_router_inflight_cap_sheds_typed_429(fleet):
    """Past max_inflight the router answers 429 + Retry-After without
    forwarding — the shed is a router-level valve, not a replica
    error."""
    import tritonclient.http as httpclient
    from tritonclient.utils import InferenceServerException

    capped = FleetRouter(fleet["backends"], probe_interval_s=60.0,
                         max_inflight=1).start()
    try:
        slow_started = threading.Event()
        done = []

        def slow():
            c = httpclient.InferenceServerClient(capped.url)
            try:
                in0 = httpclient.InferInput("INPUT0", [4], "INT32")
                in0.set_data_from_numpy(np.arange(4, dtype=np.int32))
                d = httpclient.InferInput("DELAY_US", [1], "UINT32")
                d.set_data_from_numpy(
                    np.array([500000], dtype=np.uint32))
                slow_started.set()
                c.infer("delayed_identity", [in0, d])
                done.append(True)
            finally:
                c.close()

        t = threading.Thread(target=slow, daemon=True)
        t.start()
        assert slow_started.wait(5)
        assert _wait_until(lambda: capped.stats()["inflight"] >= 1)
        client = httpclient.InferenceServerClient(capped.url)
        try:
            in0 = httpclient.InferInput("INPUT0", [16], "INT32")
            in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
            in1 = httpclient.InferInput("INPUT1", [16], "INT32")
            in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
            with pytest.raises(InferenceServerException) as exc:
                client.infer("simple", [in0, in1])
            assert "429" in str(exc.value.status())
            assert "in-flight request cap" in str(exc.value)
            t.join(timeout=10)
            assert done == [True]  # the in-flight request was untouched
            # capacity freed (the router gives the slot back after it
            # wrote the response): the next request goes through
            assert _wait_until(lambda: capped.stats()["inflight"] == 0)
            client.infer("simple", [in0, in1])
        finally:
            client.close()
        assert capped.stats()["shed"] >= 1
    finally:
        capped.stop()


def test_connect_failure_fails_over_with_zero_user_errors(fleet):
    """A replica that dies between probe rounds: requests routed to it
    hit connection-refused and silently fail over to a live replica
    under the FAILURE_CONNECT classification."""
    import tritonclient.http as httpclient

    core_a, fe_a = _make_replica(with_llama=False)
    core_b, fe_b = _make_replica(with_llama=False)
    router = FleetRouter(
        ["127.0.0.1:{}".format(fe_a.port),
         "127.0.0.1:{}".format(fe_b.port)],
        probe_interval_s=60.0,  # the prober must NOT save us here
    ).start()
    try:
        # replica A dies right after the initial probe marked it
        # eligible: the router still believes in it
        fe_a.stop()
        core_a.close()
        client = httpclient.InferenceServerClient(router.url)
        try:
            in0 = httpclient.InferInput("INPUT0", [16], "INT32")
            in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
            in1 = httpclient.InferInput("INPUT1", [16], "INT32")
            in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
            for _ in range(4):
                result = client.infer("simple", [in0, in1])
                np.testing.assert_array_equal(
                    result.as_numpy("OUTPUT0"),
                    np.arange(16, dtype=np.int32) + 1)
        finally:
            client.close()
        stats = router.stats()
        assert stats["failovers"] >= 1
        dead = next(r for r in stats["replicas"]
                    if r["url"].endswith(str(fe_a.port)))
        assert dead["eligible"] is False  # rotated out on first failure
    finally:
        router.stop()
        fe_b.stop()
        core_b.close()


# -- review hardening: passthrough duplication, blind re-POST, markers --------


def test_unresumable_stream_sever_fails_typed_without_duplicates():
    """A max_slots=1 llama puts no stream ids on the wire, so the
    router relays it passthrough (no replay buffer, no handoff).  When
    its connection dies AFTER tokens reached the client, re-sending the
    admission elsewhere would duplicate them: the router must fail the
    stream in-band and typed instead."""
    core_a, fe_a = _make_unresumable_replica("router-unres-a")
    core_b, fe_b = _make_unresumable_replica("router-unres-b")
    for scope in ("router-unres-a", "router-unres-b"):
        faults.install("http.generate_stream", mode="raise", times=1,
                       skip=2, scope=scope)
    router = FleetRouter(
        ["127.0.0.1:{}".format(fe_a.port),
         "127.0.0.1:{}".format(fe_b.port)],
        probe_interval_s=0.1).start()
    try:
        conn, resp = _open_stream(router.url, _stream_body())
        try:
            assert resp.status == 200
            tokens, error = [], None
            for raw in resp:
                line = raw.strip()
                if not line.startswith(b"data: "):
                    continue
                payload = json.loads(line[len(b"data: "):])
                if payload.get("final"):
                    break
                if "error" in payload:
                    error = payload["error"]
                    break
                tokens.extend(int(out["data"][0])
                              for out in payload.get("outputs", [])
                              if out["name"] == "TOKEN")
        finally:
            conn.close()
        # the sever landed after 2 relayed events: typed in-band
        # failure, and the 2 delivered tokens were never re-sent
        assert error is not None and "not handoff-capable" in error
        assert len(tokens) == 2
    finally:
        router.stop()
        fe_a.stop()
        fe_b.stop()
        core_a.close()
        core_b.close()


def test_reused_id_with_no_relayed_events_is_superseded(fleet,
                                                       reference_tokens):
    """The plain client's reconnect after a drop-before-first-token
    blind-re-POSTs the same admission (it has no Last-Event-ID): a
    registered predecessor that never relayed an event must be
    superseded — like the scheduler supersedes a reused id's parked
    record — not answered 400 until the TTL."""
    from tpuserver.router import _Generation

    router = fleet["router"]
    prior = _Generation("t-blind-repost", STREAM_PATH,
                        json.loads(_stream_body("t-blind-repost")))
    assert router.register_generation(prior, if_absent=True)
    conn, resp = _open_stream(router.url, _stream_body("t-blind-repost"))
    try:
        assert resp.status == 200
        events, finished = _read_events(resp)
        assert finished
        assert _tokens_of(events) == reference_tokens
    finally:
        conn.close()


def test_handoff_marks_id_lines_and_marked_resume_strips(fleet,
                                                         reference_tokens):
    """Post-handoff events mark their SSE id line with the handoff
    epoch (``gen~offset/seq``) because router seqs no longer equal the
    serving replica's numbering.  A live router strips the marker and
    resumes from its own buffer; the payload seqs stay continuous."""
    router = fleet["router"]
    for scope in fleet["scopes"]:
        faults.install("http.generate_stream", mode="raise", times=1,
                       skip=3, scope=scope)
    conn, resp = _open_stream(router.url, _stream_body("t-marked"))
    ids = []
    try:
        assert resp.status == 200
        events = []
        for raw in resp:
            line = raw.strip()
            if line.startswith(b"id: "):
                ids.append(line[4:].decode("utf-8"))
                continue
            if not line.startswith(b"data: "):
                continue
            payload = json.loads(line[len(b"data: "):])
            if payload.get("final"):
                break
            assert "error" not in payload, payload
            events.append(payload)
    finally:
        conn.close()
    assert _tokens_of(events) == reference_tokens
    assert [ev["parameters"]["seq"] for ev in events] == list(range(N_TOK))
    marked = [i for i in ids if i.startswith("t-marked~")]
    assert marked, ids  # the handoff epoch is visible on the wire
    assert ids[0] == "t-marked/0"  # pre-handoff events stay bare
    # a reconnect presenting the marked id resumes against the LIVE
    # router: the marker strips to the registry id and the completed
    # generation answers with its terminal event
    conn, resp = _open_stream(router.url, _stream_body(),
                              last_event_id=ids[-1])
    try:
        assert resp.status == 200
        tail, finished = _read_events(resp)
        assert finished and tail == []
    finally:
        conn.close()


# -- dynamic membership (ISSUE 9) ---------------------------------------------


def test_probe_jitter_spreads_phases():
    """Per-replica prober phases are deterministic, inside one probe
    interval, and SPREAD across it — a fleet-wide restart (supervisor
    scale-up, rolling restart) can never synchronize its probers into
    storms against just-booted replicas."""
    from tpuserver.router import _probe_phase

    urls = ["127.0.0.1:{}".format(8000 + i) for i in range(16)]
    phases = [_probe_phase(u, 1.0) for u in urls]
    assert all(0.0 <= p < 1.0 for p in phases)
    assert len(set(phases)) == 16  # distinct per replica
    assert max(phases) - min(phases) > 0.25  # genuinely staggered
    # deterministic (restart-stable) and interval-proportional
    assert _probe_phase(urls[0], 1.0) == phases[0]
    assert _probe_phase(urls[0], 4.0) == pytest.approx(4.0 * phases[0])


def test_add_replica_while_request_in_flight(fleet):
    """Membership grows live through /router/replicas: a slow request
    in flight during the add is untouched, the attempt budget it
    snapshotted stays coherent, and the new replica starts serving."""
    import tritonclient.http as httpclient

    url_a, url_b = fleet["backends"]
    router = FleetRouter([url_a], probe_interval_s=0.1).start()
    try:
        done = []

        def slow():
            c = httpclient.InferenceServerClient(router.url)
            try:
                in0 = httpclient.InferInput("INPUT0", [4], "INT32")
                in0.set_data_from_numpy(np.arange(4, dtype=np.int32))
                d = httpclient.InferInput("DELAY_US", [1], "UINT32")
                d.set_data_from_numpy(np.array([300000], dtype=np.uint32))
                c.infer("delayed_identity", [in0, d])
                done.append(True)
            finally:
                c.close()

        t = threading.Thread(target=slow, daemon=True)
        t.start()
        assert _wait_until(lambda: router.stats()["inflight"] >= 1)
        host, _, port = router.url.rpartition(":")
        conn = http_client.HTTPConnection(host, int(port), timeout=10)
        try:
            conn.request(
                "POST", "/router/replicas",
                body=json.dumps({"action": "add", "url": url_b}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            body = json.loads(resp.read())
        finally:
            conn.close()
        assert {r["url"] for r in body["replicas"]} == {url_a, url_b}
        t.join(timeout=10)
        assert done == [True]  # the in-flight request never noticed
        # the joined replica takes traffic: load url_a and check the
        # next request lands on url_b
        assert _wait_until(lambda: next(
            r["eligible"] for r in router.stats()["replicas"]
            if r["url"] == url_b))
        before_b = next(r["requests"] for r in router.stats()["replicas"]
                        if r["url"] == url_b)
        t2 = threading.Thread(target=slow, daemon=True)
        t2.start()
        try:
            assert _wait_until(lambda: any(
                r["load"] > 0 for r in router.stats()["replicas"]))
            client = httpclient.InferenceServerClient(router.url)
            try:
                in0 = httpclient.InferInput("INPUT0", [16], "INT32")
                in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
                in1 = httpclient.InferInput("INPUT1", [16], "INT32")
                in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
                client.infer("simple", [in0, in1])
            finally:
                client.close()
        finally:
            t2.join(timeout=10)
        after_b = next(r["requests"] for r in router.stats()["replicas"]
                       if r["url"] == url_b)
        assert after_b >= before_b + 1
    finally:
        router.stop()


def test_remove_home_replica_hands_off_capable_stream(fleet,
                                                      reference_tokens):
    """Removing the home replica of a live sticky generation: the
    resume NEVER dials the removed address — a handoff-capable stream
    re-admits prompt + history on a remaining replica and completes
    token-identical with continuous seqs."""
    router = FleetRouter(fleet["backends"], probe_interval_s=0.1,
                         gen_ttl_s=30.0).start()
    try:
        # the home's decode loop stalls before its seventh step (five
        # tokens delivered) until the replica has left the fleet: the
        # router cannot have the whole answer buffered by then
        faults.install("scheduler.step", mode="partition", skip=6)
        body = _stream_body("t-member-remove")
        conn, resp = _open_stream(router.url, body)
        try:
            head, finished = _read_events(resp, limit=3)
            assert not finished and len(head) == 3
        finally:
            conn.close()
        home = router.generation_snapshot("t-member-remove")["home"]
        assert home in fleet["backends"]
        handoffs_before = router.stats()["handoffs"]
        router.remove_replica(home)
        faults.clear("scheduler.step")
        snap = router.generation_snapshot("t-member-remove")
        assert snap["home"] is None and snap["home_lost"] is True
        conn, resp = _open_stream(
            router.url, body, last_event_id="t-member-remove/2")
        try:
            tail, finished = _read_events(resp)
            assert finished
        finally:
            conn.close()
        assert _tokens_of(head) + _tokens_of(tail) == reference_tokens
        seqs = [ev["parameters"]["seq"] for ev in head + tail]
        assert seqs == list(range(N_TOK))
        assert router.stats()["handoffs"] > handoffs_before
        new_home = router.generation_snapshot("t-member-remove")["home"]
        assert new_home in fleet["backends"] and new_home != home
    finally:
        router.stop()


def test_remove_home_replica_is_typed_404_when_not_handoff_capable(fleet):
    """The other half of removal semantics: a generation that cannot be
    reconstructed elsewhere (no PROMPT_IDS contract) answers resumes
    with a typed 404 after its home leaves — never a dial of the dead
    address, never a silent token gap."""
    from tpuserver.router import _Generation

    url_b = fleet["backends"][1]
    router = FleetRouter(fleet["backends"], probe_interval_s=60.0).start()
    try:
        gen = _Generation("t-removed-404", STREAM_PATH, {"inputs": []})
        assert router.register_generation(gen, if_absent=True)
        gen.record_event(0, {"outputs": []})  # relayed, no TOKEN
        gen.set_home(url_b)
        router.remove_replica(url_b)
        conn, resp = _open_stream(router.url, _stream_body(),
                                  last_event_id="t-removed-404/0")
        try:
            assert resp.status == 404
            err = json.loads(resp.read())["error"]
            assert "removed from the fleet" in err
            assert "not handoff-capable" in err
        finally:
            conn.close()
    finally:
        router.stop()


def test_remove_then_readd_same_url_resets_replica_state(fleet):
    """Remove-then-re-add of the same url is a FRESH membership entry:
    no request/failure-counter or eligibility carryover from the
    previous incarnation."""
    import tritonclient.http as httpclient

    url_a, url_b = fleet["backends"]
    router = FleetRouter(fleet["backends"], probe_interval_s=0.1).start()
    try:
        client = httpclient.InferenceServerClient(router.url)
        try:
            in0 = httpclient.InferInput("INPUT0", [16], "INT32")
            in0.set_data_from_numpy(np.arange(16, dtype=np.int32))
            in1 = httpclient.InferInput("INPUT1", [16], "INT32")
            in1.set_data_from_numpy(np.ones(16, dtype=np.int32))
            # accrue routing state on url_b's incarnation (deterministic
            # white-box: sequential routed requests tie-break to url_a)
            rep_b = router.replica_by_url(url_b)
            rep_b.begin_request()
            rep_b.end_request()
            rep_b.note_typed_failure()
            old = next(r for r in router.stats()["replicas"]
                       if r["url"] == url_b)
            assert old["requests"] >= 1 and old["failures"] >= 1
            router.remove_replica(url_b)
            assert {r["url"] for r in router.stats()["replicas"]} == {
                url_a}
            # re-add: a fresh _Replica, probed on entry
            router.add_replica(url_b)
            fresh = next(r for r in router.stats()["replicas"]
                         if r["url"] == url_b)
            assert fresh["requests"] == 0 and fresh["failures"] == 0
            assert fresh["eligible"] is True  # sync probe saw it ready
            client.infer("simple", [in0, in1])  # and it serves
            # prober bookkeeping stays bounded under membership churn:
            # the re-add pruned exited prober threads instead of
            # accumulating one entry per historical membership
            assert len(router._probers) <= 3
        finally:
            client.close()
        # duplicate add and unknown remove are typed 400s on the wire
        host, _, port = router.url.rpartition(":")
        for payload, needle in (
                ({"action": "add", "url": url_b}, "already a member"),
                ({"action": "remove", "url": "1.2.3.4:1"}, "not a member"),
                ({"action": "recycle", "url": url_b}, "action"),
        ):
            conn = http_client.HTTPConnection(host, int(port), timeout=10)
            try:
                conn.request("POST", "/router/replicas",
                             body=json.dumps(payload),
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 400
                assert needle in json.loads(resp.read())["error"]
            finally:
                conn.close()
    finally:
        router.stop()


def test_marked_resume_on_fresh_router_fails_typed_404(fleet):
    """A RESTARTED router (empty registry) cannot reconstruct the
    seq offset a handoff introduced: a handoff-marked resume must fail
    typed instead of forwarding a misaligned replay point that could
    silently gap or duplicate tokens."""
    fresh = FleetRouter(fleet["backends"], probe_interval_s=60.0).start()
    try:
        conn, resp = _open_stream(fresh.url, _stream_body(),
                                  last_event_id="t-anything~3/5")
        try:
            assert resp.status == 404
            assert "handed off" in json.loads(resp.read())["error"]
        finally:
            conn.close()
    finally:
        fresh.stop()


# -- prefix-affinity routing (paged KV fleet tier, ISSUE 11) -----------------
#
# These run against tests/fleet_stub.py processes (pure stdlib, ~100ms
# boot, a minimal SSE generate surface) per the tier-1 runtime budget:
# the routing DECISION under test lives entirely in the router.

import os as _os
import subprocess as _subprocess
import sys as _sys

from fleet_stub import free_port as _free_port  # noqa: E402
from fleet_stub import wait_ready as _stub_wait_ready  # noqa: E402
from http.server import (  # noqa: E402
    BaseHTTPRequestHandler as _BaseHTTPRequestHandler,
    ThreadingHTTPServer as _ThreadingHTTPServer,
)

_STUB = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                      "fleet_stub.py")
_STUB_STREAM_PATH = "/v2/models/stub/generate_stream"


def _stub_generations(port):
    conn = http_client.HTTPConnection("127.0.0.1", port, timeout=5)
    try:
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode("utf-8")
    finally:
        conn.close()
    for line in text.splitlines():
        if line.startswith("stub_generations_total "):
            return int(float(line.split()[1]))
    return 0


def _stub_set_state(port, **state):
    conn = http_client.HTTPConnection("127.0.0.1", port, timeout=5)
    try:
        conn.request("POST", "/stub/state", body=json.dumps(state),
                     headers={"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
    finally:
        conn.close()


def _stub_generate(router_url, prompt, n_tokens=4):
    host, _, port = router_url.rpartition(":")
    body = json.dumps({"inputs": [
        {"name": "PROMPT_IDS", "datatype": "INT32",
         "shape": [len(prompt)], "data": list(prompt)},
        {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
         "data": [n_tokens]},
    ]})
    conn = http_client.HTTPConnection(host, int(port), timeout=30)
    tokens = []
    try:
        conn.request("POST", _STUB_STREAM_PATH, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        for raw in resp:
            line = raw.rstrip(b"\r\n")
            if not line.startswith(b"data: "):
                continue
            payload = json.loads(line[len(b"data: "):])
            if payload.get("final"):
                break
            assert "error" not in payload, payload
            for out in payload.get("outputs", []):
                if out["name"] == "TOKEN":
                    tokens.append(int(out["data"][0]))
    finally:
        conn.close()
    return tokens


@pytest.fixture
def stub_fleet():
    ports = [_free_port(), _free_port()]
    procs = [
        _subprocess.Popen([_sys.executable, _STUB, "--port", str(p)])
        for p in ports
    ]
    try:
        for p in ports:
            assert _stub_wait_ready(p), "stub replica never became ready"
        yield ports
    finally:
        for proc in procs:
            proc.kill()
        for proc in procs:
            proc.wait(timeout=10)


def test_prefix_affinity_routes_siblings_to_warm_replica(stub_fleet):
    """Sibling generations sharing a prompt prefix all land on ONE
    replica (whose radix cache is warm) instead of spreading
    least-loaded — and the router counts the decisions the bonus
    swung."""
    ports = stub_fleet
    urls = ["127.0.0.1:{}".format(p) for p in ports]
    router = FleetRouter(urls, probe_interval_s=0.1,
                         affinity_bonus=2.0).start()
    prompt = list(range(1, 20))
    try:
        for _ in range(6):
            tokens = _stub_generate(router.url, prompt)
            assert len(tokens) == 4
        counts = [_stub_generations(p) for p in ports]
        # every sibling converged on the first pick's replica
        assert sorted(counts) == [0, 6], counts
        stats = router.stats()
        # the first admission had no affinity entry; the other five
        # were steered by the bonus
        assert stats["affinity_routed"] == 5
        assert stats["affinity_entries"] == 1
    finally:
        router.stop()


def test_prefix_affinity_never_overrides_eligibility(stub_fleet):
    """A draining/ineligible warm replica loses its affinity traffic:
    the bonus is a score tweak among ELIGIBLE replicas, never a
    health/drain override."""
    ports = stub_fleet
    urls = ["127.0.0.1:{}".format(p) for p in ports]
    router = FleetRouter(urls, probe_interval_s=0.05,
                         affinity_bonus=2.0).start()
    prompt = list(range(30, 50))
    try:
        assert len(_stub_generate(router.url, prompt)) == 4
        counts = [_stub_generations(p) for p in ports]
        warm = counts.index(1)
        cold = 1 - warm
        _stub_set_state(ports[warm], ready=False)
        deadline = time.monotonic() + 5.0
        warm_url = urls[warm]
        while time.monotonic() < deadline:
            snap = [r for r in router.stats()["replicas"]
                    if r["url"] == warm_url][0]
            if not snap["eligible"]:
                break
            time.sleep(0.02)
        else:
            pytest.fail("drained stub never rotated out")
        assert len(_stub_generate(router.url, prompt)) == 4
        assert _stub_generations(ports[cold]) >= 1
        # the prefix re-homed: once the old home revives, siblings
        # keep going to the NEW home (last-writer-wins map)
        _stub_set_state(ports[warm], ready=True)
        cold_before = _stub_generations(ports[cold])
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            snap = [r for r in router.stats()["replicas"]
                    if r["url"] == warm_url][0]
            if snap["eligible"]:
                break
            time.sleep(0.02)
        assert len(_stub_generate(router.url, prompt)) == 4
        assert _stub_generations(ports[cold]) == cold_before + 1
    finally:
        router.stop()


# -- tail-latency defense (ISSUE 13) ------------------------------------------
#
# Gray-failure ejection, hedged unary requests, and deadline-budget
# propagation.  The ejection-policy tests drive the router CORE
# directly (an unstarted FleetRouter: replicas are optimistic-eligible
# and no prober threads spin) feeding the latency digests by hand, so
# the decision logic is pinned clock-free; the wire-level tests use
# tiny in-test stdlib replicas — no jax, per the tier-1 budget.
# tools/chaos_smoke.py --gray soaks the full arc against stub replica
# processes.


class _MiniHandler(_BaseHTTPRequestHandler):
    disable_nagle_algorithm = True  # multi-write responses vs Nagle

    def log_message(self, *a):
        pass

    def _reply(self):
        spec = self.server.spec
        length = int(self.headers.get("Content-Length") or 0)
        body = self.rfile.read(length) if length else b""
        if self.path.startswith("/v2/health"):
            payload = json.dumps({
                "state": "ready", "ready": True, "inflight": 0,
                "models": {}}).encode("utf-8")
            self.send_response(200)
        else:
            spec["requests"].append(body)
            if spec["delay_s"]:
                time.sleep(spec["delay_s"])
            payload = json.dumps(
                {"served_by": self.server.server_address[1],
                 "error": "mini overload"}
                if spec["status"] >= 400 else
                {"served_by": self.server.server_address[1]}
            ).encode("utf-8")
            self.send_response(spec["status"])
            if spec["status"] == 503:
                self.send_header("Retry-After", "1")
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    do_GET = do_POST = _reply


@pytest.fixture
def mini_replicas():
    """Factory for tiny in-test HTTP replicas with a controllable
    delay/status; yields (make, urls-so-far) and tears them down."""
    servers = []

    def make(delay_s=0.0, status=200):
        server = _ThreadingHTTPServer(("127.0.0.1", 0), _MiniHandler)
        server.daemon_threads = True
        server.spec = {"delay_s": delay_s, "status": status,
                       "requests": []}
        thread = threading.Thread(target=server.serve_forever,
                                  daemon=True)
        thread.start()
        servers.append((server, thread))
        return ("127.0.0.1:{}".format(server.server_address[1]),
                server.spec)

    yield make
    for server, thread in servers:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)


@pytest.fixture
def make_router():
    """Unstarted FleetRouters for the policy/wire tests (no prober
    threads; the pre-bound admin socket still needs closing — stop()
    would block on a server loop that never ran)."""
    routers = []

    def make(backends, **kwargs):
        router = FleetRouter(backends, **kwargs)
        routers.append(router)
        return router

    yield make
    for router in routers:
        router._httpd.server_close()


def _feed(router, url, verb, value, n):
    rep = router.replica_by_url(url)
    for _ in range(n):
        rep.note_latency(verb, value)


def _status_of(router, url):
    return [r for r in router.stats()["replicas"]
            if r["url"] == url][0]


def test_gray_outlier_soft_ejects_counts_and_readmits(make_router):
    """The ejection core: a replica whose recent p90 is >3x the fleet
    median soft-ejects (counted, visible in /router/stats and the
    metrics families), stays HEALTH-eligible the whole time (gray is
    not down), is routed around except for the probe fraction, and
    re-admits once post-ejection samples come in under the bar."""
    router = make_router(["127.0.0.1:11", "127.0.0.1:12", "127.0.0.1:13"],
                         outlier_min_samples=4, probe_fraction=0.25,
                         digest_window=8)
    _feed(router, "127.0.0.1:11", "infer", 1.0, 8)   # the outlier
    _feed(router, "127.0.0.1:12", "infer", 0.01, 8)
    _feed(router, "127.0.0.1:13", "infer", 0.01, 8)
    router._evaluate_ejections(force=True)
    row = _status_of(router, "127.0.0.1:11")
    assert row["status"] == "soft-ejected" and row["ejected"]
    assert row["eligible"], "ejection must not leak into health"
    stats = router.stats()
    assert stats["ejections"] == 1
    # routed around except every 4th pick (probe_fraction=1/4)
    picked = [router.pick_replica().url for _ in range(8)]
    assert picked.count("127.0.0.1:11") == 2, picked
    # the exposition distinguishes the gray state per replica, and the
    # ejection counter is a first-class family
    text = router.metrics_text()
    assert 'tpu_router_replica_state{replica="127.0.0.1:11",' \
        'state="soft-ejected"} 1' in text
    assert "tpu_router_ejections_total 1" in text
    assert 'tpu_router_replica_p90_seconds{replica="127.0.0.1:12",' \
        'verb="infer"}' in text
    # ejection reset the digest: fresh (fast) probe samples re-admit
    assert _status_of(router, "127.0.0.1:11")["digest"] == {}
    _feed(router, "127.0.0.1:11", "infer", 0.01, 4)
    router._evaluate_ejections(force=True)
    row = _status_of(router, "127.0.0.1:11")
    assert row["status"] == "ok" and not row["ejected"]
    # re-admission is not a second ejection event
    assert router.stats()["ejections"] == 1


def test_ejection_defers_at_min_eligible_and_health_dominates(
        make_router):
    """Two pins: (a) an outlier is NOT ejected when ejection would
    leave fewer than min_eligible healthy replicas — the fleet
    degrades to slow, never to unavailable; (b) an ineligible
    (draining/unreachable) replica is never gray-ejected — health
    verdicts dominate, and its status stays diagnosable."""
    router = make_router(["127.0.0.1:21", "127.0.0.1:22"],
                         outlier_min_samples=4, min_eligible=2)
    _feed(router, "127.0.0.1:21", "infer", 1.0, 8)
    _feed(router, "127.0.0.1:22", "infer", 0.01, 8)
    router._evaluate_ejections(force=True)
    row = _status_of(router, "127.0.0.1:21")
    assert row["status"] == "ok" and not row["ejected"]
    assert router.stats()["ejections"] == 0
    # (b) health dominance: the outlier goes unreachable — its status
    # reports the HEALTH verdict, and no ejection ever applies
    router.replica_by_url("127.0.0.1:21").mark_unreachable()
    router._evaluate_ejections(force=True)
    row = _status_of(router, "127.0.0.1:21")
    assert row["status"] == "unreachable" and not row["ejected"]


def test_ejection_needs_a_differential_signal(make_router):
    """One replica alone (or one with samples) is its own median: no
    ejection without >= 2 replicas of digest coverage — a uniformly
    slow fleet is load, not a gray failure."""
    router = make_router(["127.0.0.1:31", "127.0.0.1:32"],
                         outlier_min_samples=4)
    _feed(router, "127.0.0.1:31", "infer", 1.0, 8)
    router._evaluate_ejections(force=True)
    assert _status_of(router, "127.0.0.1:31")["status"] == "ok"
    # both slow: still no outlier (the median IS the fleet)
    _feed(router, "127.0.0.1:32", "infer", 1.0, 8)
    router._evaluate_ejections(force=True)
    assert router.stats()["ejections"] == 0


def test_hedge_first_response_wins_loser_never_double_counted(
        mini_replicas, make_router):
    """Router-tier hedging: an idempotent unary attempt still pending
    after the hedge delay races a duplicate on the next-ranked
    replica; the fast replica's answer is relayed, the outcome counts
    once under tpu_router_hedges_total{outcome=won}, and the loser's
    latency sample never enters any digest."""
    slow_url, _slow_spec = mini_replicas(delay_s=0.6)
    fast_url, _fast_spec = mini_replicas(delay_s=0.0)
    router = make_router([slow_url, fast_url], hedge_delay_s=0.05,
                         read_timeout_s=5.0)
    status, headers, body = router.forward_unary(
        "POST", "/v2/models/stub/infer", b"{}",
        {"Content-Type": "application/json"})
    assert status == 200
    assert json.loads(body)["served_by"] == int(fast_url.rsplit(":")[-1])
    stats = router.stats()
    assert stats["hedges"] == 1
    assert stats["hedges_by_outcome"]["won"] == 1
    # the winner's sample recorded, the loser's excluded — even after
    # the loser's connection drains in the background
    assert _status_of(router, fast_url)["digest"]["infer"]["samples"] == 1
    time.sleep(0.8)
    assert _status_of(router, slow_url)["digest"] == {}
    text = router.metrics_text()
    assert 'tpu_router_hedges_total{outcome="won"} 1' in text


def test_hedge_primary_win_counts_lost_or_cancelled(mini_replicas,
                                                      make_router):
    """When the primary answers after the hedge fired, the hedge is
    abandoned and counted (lost if it completed, cancelled if still
    in flight) — never relayed, never double-answered."""
    primary_url, _spec = mini_replicas(delay_s=0.15)
    backup_url, backup_spec = mini_replicas(delay_s=3.0)
    router = make_router([primary_url, backup_url], hedge_delay_s=0.05,
                         read_timeout_s=5.0)
    status, _headers, body = router.forward_unary(
        "POST", "/v2/models/stub/infer", b"{}", {})
    assert status == 200
    assert json.loads(body)["served_by"] == int(
        primary_url.rsplit(":")[-1])
    outcomes = router.stats()["hedges_by_outcome"]
    assert outcomes["lost"] + outcomes["cancelled"] == 1, outcomes
    assert outcomes["won"] == 0
    # the hedge really fired: the backup saw the duplicate request
    assert len(backup_spec["requests"]) == 1


def test_streams_and_broadcasts_never_hedge(mini_replicas, make_router):
    """Hedging is unary-idempotent only: a generate_stream POST and a
    broadcast mutation must never produce a duplicate in-flight
    attempt, whatever the hedge knobs say."""
    a_url, a_spec = mini_replicas(delay_s=0.2)
    b_url, b_spec = mini_replicas(delay_s=0.2)
    router = make_router([a_url, b_url], hedge_delay_s=0.01,
                         read_timeout_s=5.0)
    # a broadcast goes to EVERY replica once — one request each, no
    # hedge accounting
    router.forward_broadcast(
        "POST", "/v2/systemsharedmemory/region/r/register", b"{}", {})
    assert len(a_spec["requests"]) == 1 and len(b_spec["requests"]) == 1
    assert router.stats()["hedges"] == 0
    # a non-hedgeable POST (not the infer verb) never hedges even when
    # slow
    router.forward_unary("POST", "/v2/repository/index", b"{}", {})
    assert router.stats()["hedges"] == 0


def test_deadline_budget_shrinks_across_failover(mini_replicas,
                                                   make_router):
    """Deadline-budget propagation, wire-pinned: the first attempt
    burns most of the request's ``timeout`` budget (slow typed-
    overload answer), and the SECOND replica receives the request
    with the timeout parameter rewritten to the remaining budget —
    not the original."""
    slow_url, slow_spec = mini_replicas(delay_s=0.3, status=503)
    ok_url, ok_spec = mini_replicas()
    router = make_router([slow_url, ok_url], read_timeout_s=5.0)
    body = json.dumps({"parameters": {"timeout": 500000}}).encode()
    status, _headers, _body = router.forward_unary(
        "POST", "/v2/models/stub/infer", body,
        {"Content-Type": "application/json"})
    assert status == 200
    first = json.loads(slow_spec["requests"][0])
    second = json.loads(ok_spec["requests"][0])
    # the first attempt carries (approximately) the full 500ms budget,
    # the second only what the slow 503 left over
    assert first["parameters"]["timeout"] > 400000
    assert 0 < second["parameters"]["timeout"] < 250000
    assert second["parameters"]["timeout"] < first["parameters"]["timeout"]


def test_deadline_propagation_reaches_replica_expiry_path(
        mini_replicas, make_router, fleet):
    """End-to-end: a router-relayed request whose first attempt burned
    most of its budget reaches the REAL replica with the shrunk
    timeout and dies on the replica's own deadline-expiry path (504).
    The control leg proves the same request succeeds on the full
    budget — only the propagated shrink makes it expire."""
    slow_url, _spec = mini_replicas(delay_s=0.45, status=503)
    real_url = fleet["backends"][0]
    # control: full budget straight at the real replica through a
    # router with no budget burned — DELAY_US=80ms fits 500ms easily
    request = {
        "inputs": [
            {"name": "INPUT0", "shape": [4], "datatype": "INT32",
             "data": [1, 2, 3, 4]},
            {"name": "DELAY_US", "shape": [1], "datatype": "UINT32",
             "data": [80000]},
        ],
        "parameters": {"timeout": 500000},
    }
    body = json.dumps(request).encode()
    control = make_router([real_url], read_timeout_s=5.0)
    status, _h, _b = control.forward_unary(
        "POST", "/v2/models/delayed_identity/infer", body,
        {"Content-Type": "application/json"})
    assert status == 200
    # the pin: the slow 503 burns ~450ms of the 500ms budget, the
    # failover lands on the real replica with ~50ms — the 80ms compute
    # crosses the PROPAGATED deadline: the client gets a typed 504,
    # and the REPLICA's own deadline-expiry path fires on the shrunk
    # budget (its 504 error counter moves — without the rewrite the
    # 80ms compute would sit comfortably inside the original 500ms)
    def replica_504s():
        host, _, port = real_url.rpartition(":")
        conn = http_client.HTTPConnection(host, int(port), timeout=5)
        try:
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode("utf-8")
        finally:
            conn.close()
        return sum(
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("tpu_request_errors_total")
            and 'code="504"' in line)

    before_504 = replica_504s()
    router = make_router([slow_url, real_url], read_timeout_s=5.0)
    status, _h, resp_body = router.forward_unary(
        "POST", "/v2/models/delayed_identity/infer", body,
        {"Content-Type": "application/json"})
    assert status == 504, resp_body
    assert b"deadline" in resp_body.lower()
    assert _wait_until(lambda: replica_504s() == before_504 + 1)


def test_ejected_probe_is_shadowed_and_measures_the_gray_replica(
        mini_replicas, make_router):
    """A probe routed to a soft-ejected replica launches an immediate
    backup on a healthy one: the client sees the healthy latency (the
    probe fraction never reappears in fleet p99) while the probe's own
    service time still lands in the ejected replica's digest — the
    sample re-admission is judged on."""
    gray_url, gray_spec = mini_replicas(delay_s=0.4)
    ok_url, _ok_spec = mini_replicas()
    router = make_router([gray_url, ok_url], probe_fraction=1.0,
                         read_timeout_s=5.0)
    router.replica_by_url(gray_url).soft_eject()
    t0 = time.monotonic()
    status, _headers, body = router.forward_unary(
        "POST", "/v2/models/stub/infer", b"{}", {})
    elapsed = time.monotonic() - t0
    assert status == 200
    assert json.loads(body)["served_by"] == int(ok_url.rsplit(":")[-1])
    assert elapsed < 0.3, "probe slowness leaked to the client"
    # the gray replica WAS probed with real traffic, and its sample
    # lands once the abandoned connection drains (its handler thread
    # may not have recorded the request yet when the backup returns)
    assert _wait_until(
        lambda: len(gray_spec["requests"]) == 1, timeout_s=2.0)
    assert _wait_until(
        lambda: _status_of(router, gray_url)["digest"].get(
            "infer", {}).get("samples") == 1, timeout_s=2.0)
    # probes are not hedges: the outcome counters stay untouched
    assert router.stats()["hedges"] == 0
