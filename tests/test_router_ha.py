"""Router high availability (ISSUE 15 acceptance).

The front tier becomes as survivable as the fleet behind it: the
router's resume-critical state (sticky bindings, handoff offset
rebases, relayed-seq watermarks, the relayed-event tail) is
crash-durable in an append-only journal, a warm standby tails it and
promotes on a takeover signal, and the fleet supervisor heals router
PROCESSES under the same drain-first restart-budgeted policy replicas
get.  The bar:

(a) journal round-trip: length-prefixed + checksummed records,
    TTL-aligned segment rotation, incremental follower tailing;
(b) a torn/corrupt final record (crash mid-write) truncates — never
    fatal, every complete record before it recovers;
(c) THE acceptance case: SIGKILL the active router mid-generation and
    the client reconnects (same port on respawn, or the standby via
    ``fallback_urls``) to a resumed stream that is token-identical and
    gap-free vs an uninterrupted run — INCLUDING the handoff-marked
    (``gen~offset/seq``) resume PR 7 had to answer with a typed 404,
    which now succeeds via journal recovery;
(d) a standby sheds typed 503 until promoted, then serves
    journal-recovered resumes; promotion counts takeovers;
(e) SIGTERM drains the router process: in-flight streams finish, the
    journal flushes clean (no torn tail), the process exits 0;
(f) the hot relay path stays enqueue-only — journaling adds ZERO lock
    acquisitions to the event path (AST-pinned);
(g) ``tools/chaos_smoke.py --router-kill`` exits 0.

Replicas here are ``tests/fleet_stub.py`` processes (stdlib-only,
continuation-consistent autoregressive tokens — the greedy-determinism
stand-in), so the whole file fits the tier-1 runtime budget.
"""

import ast
import http.client
import inspect
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from fleet_stub import free_port, wait_ready  # noqa: E402

from tpuserver.journal import (  # noqa: E402
    JournalFollower,
    JournalWriter,
    read_journal,
)
from tpuserver.router import FleetRouter, _Generation  # noqa: E402

pytestmark = pytest.mark.router

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
STUB = os.path.join(HERE, "fleet_stub.py")
ROUTER_CLI = os.path.join(REPO, "tools", "router.py")
STREAM_PATH = "/v2/models/stub/generate_stream"
PROMPT = [5, 7, 9]


# -- plumbing ----------------------------------------------------------------


def _spawn_stubs(n):
    ports = [free_port() for _ in range(n)]
    procs = [
        subprocess.Popen([sys.executable, STUB, "--port", str(p)])
        for p in ports
    ]
    for p in ports:
        assert wait_ready(p), "stub replica never became ready"
    return ports, procs


def _kill_all(procs):
    for proc in procs:
        try:
            proc.kill()
        except OSError:
            pass
    for proc in procs:
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass


def _gen_body(gid, n_tokens, delay_ms=0):
    return json.dumps({"inputs": [
        {"name": "PROMPT_IDS", "datatype": "INT32",
         "shape": [len(PROMPT)], "data": PROMPT},
        {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
         "data": [n_tokens]},
    ], "parameters": {"generation_id": gid,
                      "token_delay_ms": delay_ms}}).encode("utf-8")


def _stream(port, body, last_event_id=None, stop_after=None,
            on_event=None, timeout=30):
    """Raw SSE consumption: ``(events[(id_line, payload)], final)``.
    ``stop_after`` abandons the connection mid-stream (the client-drop
    shape resume tests need)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    headers = {"Content-Type": "application/json"}
    if last_event_id is not None:
        headers["Last-Event-ID"] = last_event_id
    conn.request("POST", STREAM_PATH, body, headers)
    resp = conn.getresponse()
    assert resp.status == 200, (resp.status, resp.read())
    events, final, id_line = [], False, None
    try:
        for raw in resp:
            line = raw.rstrip(b"\r\n")
            if line.startswith(b"id: "):
                id_line = line[4:].decode("utf-8")
                continue
            if not line.startswith(b"data: "):
                continue
            payload = json.loads(line[len(b"data: "):])
            if payload.get("final"):
                final = True
                break
            assert "error" not in payload, payload
            events.append((id_line, payload))
            if on_event is not None:
                on_event(len(events))
            if stop_after is not None and len(events) >= stop_after:
                break
    finally:
        conn.close()
    return events, final


def _tokens(events):
    return [e[1]["outputs"][0]["data"][0] for e in events]


def _seqs(events):
    return [e[1]["parameters"]["seq"] for e in events]


# -- (a)/(b): the journal itself ---------------------------------------------


def test_journal_roundtrip_rotation_and_follower(tmp_path):
    d = str(tmp_path / "j")
    writer = JournalWriter(d, rotate_interval_s=0.15,
                           flush_interval_s=0.01)
    follower = JournalFollower(d)
    try:
        for i in range(5):
            writer.append({"t": "ev", "seq": i})
        assert writer.flush(), "flush never drained"
        records, truncated = read_journal(d)
        assert [r["seq"] for r in records] == list(range(5))
        assert truncated == 0
        stats = writer.stats()
        assert stats["records"] == 5
        assert stats["bytes"] > 0
        assert stats["fsyncs"] >= 1
        # the follower sees exactly the same records, incrementally
        assert [r["seq"] for r in follower.poll()] == list(range(5))
        assert follower.poll() == []
        # rotation: records written after the interval land in a new
        # segment, and the follower crosses segments seamlessly
        time.sleep(0.2)
        writer.append({"t": "ev", "seq": 5})
        assert writer.flush()
        assert len([n for n in os.listdir(d)
                    if n.startswith("seg-")]) >= 2
        assert [r["seq"] for r in follower.poll()] == [5]
    finally:
        writer.close()


def test_journal_torn_tail_is_truncated_never_fatal(tmp_path):
    d = str(tmp_path / "j")
    writer = JournalWriter(d, rotate_interval_s=60.0,
                           flush_interval_s=0.01)
    for i in range(4):
        writer.append({"t": "ev", "seq": i})
    assert writer.flush()
    writer.close()
    seg = sorted(n for n in os.listdir(d) if n.startswith("seg-"))[-1]
    path = os.path.join(d, seg)
    with open(path, "rb") as fh:
        clean = fh.read()
    # a torn final record: a length prefix promising more bytes than
    # were ever written (the classic crash-mid-write shape)
    with open(path, "ab") as fh:
        fh.write(b"\x40\x00\x00\x00\x01\x02torn")
    records, truncated = read_journal(d)
    assert [r["seq"] for r in records] == list(range(4))
    assert truncated == 1
    # a checksum-corrupt record mid-frame truncates the same way
    with open(path, "wb") as fh:
        fh.write(clean[:-3] + b"XYZ")  # corrupt the last record's body
    records, truncated = read_journal(d)
    assert [r["seq"] for r in records] == list(range(3))
    assert truncated == 1
    # an empty/missing directory is a clean first boot, not an error
    assert read_journal(str(tmp_path / "fresh")) == ([], 0)


def test_recovered_generation_tail_semantics():
    """Unit pins for the recovered-tail arithmetic: a resume before
    the retained tail is unavailable (typed 404 upstream), and
    fast_forward is a recovered-only affordance."""
    live = _Generation("g", STREAM_PATH, {})
    live.apply_event(0, "g", {"outputs": []})
    assert live.fast_forward(5) is False  # live watermarks never trail
    rec = _Generation.from_journal("g", STREAM_PATH, {})
    # records 0..4 aged out with their segment; 5..6 retained
    rec.apply_event(5, "g", {"outputs": []})
    rec.apply_event(6, "g", {"outputs": []})
    blocks, _completed, next_seq, available = rec.replay_from(2)
    assert not available
    blocks, _completed, next_seq, available = rec.replay_from(5)
    assert available and len(blocks) == 2 and next_seq == 7
    # the crash lost the flush window past 6; the client is at 9
    assert rec.fast_forward(9) is True
    assert rec.replay_from(9) == ([], False, 9, True)


# -- (c): restarted-router marked resume (the previously-404 case) -----------


def test_restarted_router_serves_marked_resume_from_journal(tmp_path):
    """Mid-generation replica SIGKILL forces a cross-replica handoff
    (events gain the ``gen~offset/seq`` epoch marker); the router then
    dies and a RESTARTED router — same journal — serves the marked
    resume token-identically.  Without ``journal=`` this exact resume
    is the typed 404 of PR 7's hardening note (iv)."""
    ports, procs = _spawn_stubs(2)
    urls = ["127.0.0.1:{}".format(p) for p in ports]
    jdir = str(tmp_path / "journal")
    router2 = None
    try:
        # the uninterrupted reference, straight off a stub
        ref_events, final = _stream(ports[0], _gen_body("ref", 12))
        assert final
        reference = _tokens(ref_events)

        router1 = FleetRouter(urls, journal=jdir, probe_interval_s=0.1,
                              journal_flush_s=0.005).start()
        killed = []

        def kill_home_at_three(n):
            if n == 3 and not killed:
                home = router1.generation_snapshot("hagen")["home"]
                victim = procs[urls.index(home)]
                victim.send_signal(signal.SIGKILL)
                killed.append(home)

        events, _ = _stream(router1.port, _gen_body("hagen", 12, 40),
                            stop_after=8, on_event=kill_home_at_three)
        assert killed, "the home replica was never identified"
        assert _tokens(events) == reference[:8]
        last_id = events[-1][0]
        assert "~" in last_id, (
            "expected a handoff-marked id line, got " + last_id)
        time.sleep(0.2)  # the relay notices the dropped client; flush
        router1.stop()

        # the restart: recovery replays the journal, the marked resume
        # (previously typed-404) splices token-identically
        router2 = FleetRouter(urls, journal=jdir,
                              probe_interval_s=0.1).start()
        assert router2.stats()["recovered_generations"] >= 1
        events2, final2 = _stream(router2.port, _gen_body("hagen", 12),
                                  last_event_id=last_id)
        assert final2
        assert _tokens(events) + _tokens(events2) == reference
        assert _seqs(events2) == list(range(8, 12))
        # and the epoch-mismatch guard stays honest: an epoch NEWER
        # than any the journal recorded is unreconstructable — typed
        conn = http.client.HTTPConnection("127.0.0.1", router2.port,
                                          timeout=10)
        try:
            conn.request("POST", STREAM_PATH, _gen_body("hagen", 12),
                         {"Content-Type": "application/json",
                          "Last-Event-ID": "hagen~99/100"})
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 404, (resp.status, body)
            assert b"handed off" in body
        finally:
            conn.close()
    finally:
        if router2 is not None:
            router2.stop()
        _kill_all(procs)


# -- (d): warm standby + promotion -------------------------------------------


def test_standby_sheds_typed_503_then_promotes_and_serves_resume(
        tmp_path):
    ports, procs = _spawn_stubs(2)
    urls = ["127.0.0.1:{}".format(p) for p in ports]
    jdir = str(tmp_path / "journal")
    active = standby = None
    try:
        active = FleetRouter(urls, journal=jdir, probe_interval_s=0.1,
                             journal_flush_s=0.005).start()
        standby = FleetRouter(urls, journal=jdir, standby=True,
                              probe_interval_s=0.1).start()
        # the standby sheds /v2 typed-503 and reports itself not-ready
        conn = http.client.HTTPConnection("127.0.0.1", standby.port,
                                          timeout=10)
        try:
            conn.request("POST", STREAM_PATH, _gen_body("x", 4),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 503, (resp.status, body)
            assert b"standby" in body
            assert resp.headers.get("Retry-After") == "1"
        finally:
            conn.close()
        assert standby.health_snapshot()["state"] == "standby"
        assert standby.health_snapshot()["ready"] is False

        ref_events, _ = _stream(ports[0], _gen_body("ref", 10))
        reference = _tokens(ref_events)
        events, _ = _stream(active.port, _gen_body("sgen", 10, 20),
                            stop_after=4)
        last_id = events[-1][0]
        time.sleep(0.3)  # standby tails the journal
        active.stop()  # the active is GONE before promotion

        # promotion over the admin surface (the supervisor's signal)
        conn = http.client.HTTPConnection("127.0.0.1", standby.port,
                                          timeout=10)
        try:
            conn.request("POST", "/router/promote", b"{}",
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert json.loads(resp.read())["promoted"] is True
        finally:
            conn.close()
        stats = standby.stats()
        assert stats["takeovers"] == 1
        assert stats["recovered_generations"] >= 1
        assert standby.rejecting() is None

        events2, final2 = _stream(standby.port, _gen_body("sgen", 10),
                                  last_event_id=last_id)
        assert final2
        assert _tokens(events) + _tokens(events2) == reference
        assert _seqs(events2) == list(range(4, 10))
    finally:
        for r in (active, standby):
            if r is not None:
                r.stop()
        _kill_all(procs)


# -- (c) at process level: supervised SIGKILL takeover -----------------------


def test_sigkill_active_router_supervised_takeover_token_identical():
    """THE acceptance case, end to end: a FleetSupervisor owns stub
    replicas AND active+standby router processes; the ACTIVE router is
    SIGKILLed mid-generation; the client (carrying both router urls
    via ``fallback_urls``) reconnects to the promoted standby and the
    resumed stream is token-identical and gap-free vs an uninterrupted
    run."""
    import numpy as np
    import tritonclient.http as httpclient

    from tpuserver.fleet import FleetSupervisor

    command = [sys.executable, STUB, "--port", "{port}",
               "--scope", "{scope}"]
    router_command = [
        sys.executable, ROUTER_CLI, "--backends", "{backends}",
        "--port", "{port}", "--journal", "{journal}",
        "--probe-interval", "0.1",
    ]
    supervisor = FleetSupervisor(
        command, replicas=2, min_replicas=2, max_replicas=2,
        probe_interval_s=0.1, probe_timeout_s=2.0,
        start_timeout_s=30.0, drain_grace_s=3.0,
        restart_backoff_s=0.05, scope_prefix="ha-stub-",
        router_command=router_command, router_standby=True,
        env={"PYTHONPATH": os.path.join(REPO, "src", "python")},
    ).start()
    try:
        assert supervisor.wait_ready(timeout_s=30)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            routers = supervisor.stats().get("routers", [])
            if routers and all(r["state"] == "up" for r in routers):
                break
            time.sleep(0.1)
        else:
            pytest.fail("router processes never came up")
        urls = supervisor.router_urls()
        assert len(urls) == 2

        def run_stream(client, fallback):
            tokens, seqs = [], []
            for event in client.generate_stream(
                    "stub",
                    {"PROMPT_IDS": np.array(PROMPT, np.int32),
                     "MAX_TOKENS": np.array([14], np.int32)},
                    parameters={"token_delay_ms": 50},
                    fallback_urls=fallback, max_reconnects=10):
                for out in event.get("outputs", []):
                    if out["name"] == "TOKEN":
                        tokens.append(int(out["data"][0]))
                params = event.get("parameters") or {}
                if "seq" in params:
                    seqs.append(params["seq"])
            return tokens, seqs

        client = httpclient.InferenceServerClient(urls[0])
        try:
            reference, _ = run_stream(client, [])
            result = {}

            def worker():
                result["tokens"], result["seqs"] = run_stream(
                    client, urls[1:])

            thread = threading.Thread(target=worker, daemon=True)
            thread.start()
            time.sleep(0.3)  # a few 50ms-cadence tokens in flight
            active = [r for r in supervisor.stats()["routers"]
                      if r["role"] == "active"][0]
            os.kill(active["pid"], signal.SIGKILL)
            thread.join(timeout=60)
            assert not thread.is_alive(), "stream never terminated"
        finally:
            client.close()
        assert result["tokens"] == reference
        assert result["seqs"] == list(range(14))
        stats = supervisor.stats()
        assert stats["router_takeovers"] >= 1
        # the promoted router rebuilt the stream from the journal
        rstats = supervisor.router.stats()
        assert rstats.get("takeovers", 0) >= 1
        assert rstats.get("recovered_generations", 0) >= 1
    finally:
        supervisor.stop()


# -- (e): SIGTERM drain ------------------------------------------------------


def test_router_sigterm_drain_finishes_streams_and_flushes_journal(
        tmp_path):
    ports, procs = _spawn_stubs(1)
    jdir = str(tmp_path / "journal")
    router_port = free_port()
    router_proc = subprocess.Popen(
        [sys.executable, ROUTER_CLI, "--backends",
         "127.0.0.1:{}".format(ports[0]), "--port", str(router_port),
         "--journal", jdir, "--probe-interval", "0.1",
         "--drain-timeout", "15"],
        env=dict(os.environ,
                 PYTHONPATH=os.path.join(REPO, "src", "python")))
    try:
        assert wait_ready(router_port), "router never became ready"
        ref_events, _ = _stream(ports[0], _gen_body("ref", 10))
        reference = _tokens(ref_events)

        result = {}
        signalled = threading.Event()

        def mid_generation(n_events):
            # the reader holds its first event and has not read the
            # final one: the stream is admitted and still open
            if n_events == 1:
                router_proc.send_signal(signal.SIGTERM)
                signalled.set()

        def worker():
            events, final = _stream(router_port,
                                    _gen_body("dgen", 10, 50),
                                    on_event=mid_generation)
            result["tokens"] = _tokens(events)
            result["final"] = final

        thread = threading.Thread(target=worker, daemon=True)
        thread.start()
        assert signalled.wait(timeout=30), "the stream never started"
        # draining = stop admitting: once the router says it drains, a
        # fresh request sheds typed 503 (or the process already exited
        # and refuses the connection)
        try:
            draining, until = False, time.monotonic() + 30
            while not draining:
                assert time.monotonic() < until, "router never drained"
                conn = http.client.HTTPConnection(
                    "127.0.0.1", router_port, timeout=5)
                conn.request("GET", "/router/stats")
                draining = json.loads(
                    conn.getresponse().read())["draining"]
                conn.close()
            conn = http.client.HTTPConnection(
                "127.0.0.1", router_port, timeout=5)
            conn.request("POST", STREAM_PATH, _gen_body("late", 4),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 503, resp.status
            conn.close()
        except (OSError, http.client.HTTPException):
            pass
        thread.join(timeout=30)
        assert not thread.is_alive(), "in-flight stream never finished"
        # drain-first: the in-flight stream COMPLETED through the
        # SIGTERM'd router
        assert result["final"] is True
        assert result["tokens"] == reference
        assert router_proc.wait(timeout=30) == 0
        # the flushed journal is clean (no torn tail) and terminal
        records, truncated = read_journal(jdir)
        assert truncated == 0
        kinds = {}
        for rec in records:
            kinds.setdefault(rec.get("gen"), set()).add(rec.get("t"))
        dgen = [g for g in kinds if kinds[g] >= {"bind", "ev", "fin"}]
        assert dgen, kinds
    finally:
        if router_proc.poll() is None:
            router_proc.kill()
            router_proc.wait(timeout=10)
        _kill_all(procs)


# -- client-side: multi-router-url resume ------------------------------------


def test_http_client_fallback_urls_rotate_on_connect_refused():
    """A dead primary router (connect-refused) rotates the reconnect
    to the fallback url — fresh streams and resumes both ride it."""
    import numpy as np
    import tritonclient.http as httpclient

    ports, procs = _spawn_stubs(1)
    dead = free_port()  # nothing listens here
    client = httpclient.InferenceServerClient(
        "127.0.0.1:{}".format(dead))
    try:
        tokens = []
        for event in client.generate_stream(
                "stub",
                {"PROMPT_IDS": np.array(PROMPT, np.int32),
                 "MAX_TOKENS": np.array([6], np.int32)},
                fallback_urls=["127.0.0.1:{}".format(ports[0])],
                max_reconnects=4, reconnect_backoff_s=0.01):
            for out in event.get("outputs", []):
                if out["name"] == "TOKEN":
                    tokens.append(int(out["data"][0]))
        assert len(tokens) == 6
    finally:
        client.close()
        _kill_all(procs)


def test_grpc_client_fallback_urls_rotate_on_connect_refused():
    """The gRPC auto-resume helper rotates too: a dead primary
    re-binds the channel to the fallback url on reconnect (secure
    channels refuse the option up front)."""
    import numpy as np
    import grpc  # noqa: F401 — environment gate
    import tritonclient.grpc as grpcclient
    from tritonclient.utils import InferenceServerException

    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    core = InferenceServer([LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=64, max_slots=2,
        restart_backoff_s=0.01)])
    frontend = GrpcFrontend(core, port=0).start()
    dead = free_port()
    client = grpcclient.InferenceServerClient(
        "127.0.0.1:{}".format(dead))
    try:
        p_in = grpcclient.InferInput("PROMPT_IDS", [len(PROMPT)],
                                     "INT32")
        p_in.set_data_from_numpy(np.array(PROMPT, np.int32))
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([4], np.int32))
        tokens = [
            int(result.as_numpy("TOKEN")[0])
            for result in client.generate_stream(
                "llama_generate", [p_in, m_in],
                fallback_urls=["127.0.0.1:{}".format(frontend.port)],
                max_reconnects=4, reconnect_backoff_s=0.01)
        ]
        assert len(tokens) == 4
        # the rotation must not outlive the call: the client is bound
        # back to its primary url (a sticky rebind would silently
        # point a pool's breaker accounting at the wrong endpoint)
        assert client._url == "127.0.0.1:{}".format(dead)
        with pytest.raises(InferenceServerException,
                           match="host:port"):
            list(client.generate_stream(
                "llama_generate", [p_in, m_in],
                fallback_urls=["not-a-url"]))
    finally:
        client.close()
        frontend.stop()
        core.close()


def test_pool_generate_stream_seeds_peer_fallback_urls():
    """EndpointPool.generate_stream hands the pinned client the OTHER
    endpoints as ``fallback_urls`` (and an explicit caller override
    wins) — the connect-refused resume escape hatch."""
    import tritonclient.http as httpclient

    seen = {}

    class _FakeClient:
        def __init__(self, url):
            self.url = url

        def generate_stream(self, *args, **kwargs):
            seen["kwargs"] = kwargs
            yield {"outputs": []}

        def is_server_ready(self):
            return True

        def close(self):
            pass

    pool = httpclient.EndpointPool(
        ["127.0.0.1:1", "127.0.0.1:2"],
        client_factory=lambda url: _FakeClient(url))
    try:
        list(pool.generate_stream("m", {}))
        assert seen["kwargs"]["fallback_urls"] in (
            ["127.0.0.1:1"], ["127.0.0.1:2"])
        list(pool.generate_stream("m", {}, fallback_urls=()))
        assert seen["kwargs"]["fallback_urls"] == ()
    finally:
        pool.close()

    # secure channels never get auto-injected fallbacks: the gRPC
    # client refuses rotation on them with a typed error, so a secure
    # pool must keep the plain same-endpoint pin working
    class _SecureFake(_FakeClient):
        _secure = True

    pool = httpclient.EndpointPool(
        ["127.0.0.1:1", "127.0.0.1:2"],
        client_factory=lambda url: _SecureFake(url))
    try:
        seen.clear()
        list(pool.generate_stream("m", {}))
        assert "fallback_urls" not in seen["kwargs"]
    finally:
        pool.close()


# -- (f): the hot relay path stays enqueue-only (lint pin) -------------------


def test_relay_hot_path_is_enqueue_only():
    """Durability must not tax the token path: ``JournalWriter.append``
    performs no lock acquisition and no I/O (one deque append), and
    ``_Generation.record_event`` acquires nothing beyond the
    ``self._lock`` the relay already held before journaling existed."""
    import tpuserver.journal as journal_mod
    import tpuserver.router as router_mod

    def with_items(func):
        tree = ast.parse(inspect.getsource(func).lstrip())
        fn = tree.body[0]
        return [node for node in ast.walk(fn)
                if isinstance(node, ast.With)], fn

    withs, fn = with_items(journal_mod.JournalWriter.append)
    assert withs == [], "JournalWriter.append must be lock-free"
    banned = {"open", "fsync", "flush", "write", "dumps", "pack"}
    calls = {node.func.attr if isinstance(node.func, ast.Attribute)
             else getattr(node.func, "id", None)
             for node in ast.walk(fn) if isinstance(node, ast.Call)}
    assert not (calls & banned), (
        "JournalWriter.append must only enqueue, found calls: "
        "{}".format(sorted(calls & banned)))

    withs, _fn = with_items(router_mod._Generation.record_event)
    locks = set()
    for node in withs:
        for item in node.items:
            expr = item.context_expr
            assert isinstance(expr, ast.Attribute), ast.dump(expr)
            locks.add(expr.attr)
    assert locks == {"_lock"}, (
        "record_event may hold only the generation's own _lock; "
        "journaling must stay enqueue-only (got {})".format(locks))


# -- (g): the soak ------------------------------------------------------------


def test_chaos_smoke_router_kill_cli_exits_zero():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_smoke.py"),
         "--router-kill", "--cycles", "2", "--soak", "2"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, timeout=240)
    assert proc.returncode == 0, proc.stdout.decode()
    assert b"router-kill chaos smoke OK" in proc.stdout
