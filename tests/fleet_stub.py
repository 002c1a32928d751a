#!/usr/bin/env python3
"""Stub replica for fleet-supervisor tests: a pure-stdlib process that
speaks just enough of the replica health surface to be supervised.

Boots in ~100ms (no jax import), serves ``/v2/health/stats`` with an
injectable scheduler-utilization snapshot, and honors the drain-first
contract: SIGTERM flips the snapshot to ``draining``, appends a
``drain`` marker line to ``--marker`` (how tests prove a planned
restart SIGTERMed before any SIGKILL), and exits cleanly after
``--drain-s``.

Control surface (what tests poke):

    POST /stub/state {"pending": 16}         # scheduler counters
    POST /stub/state {"tripped": true}       # alive-but-tripped
    POST /stub/state {"wedged": true}        # stop answering probes
    POST /stub/state {"infer_delay_ms": 200} # gray failure: slow, not
                                             # dead (probes still 200)
    POST /stub/state {"sever_streams": 2}    # abruptly drop the next 2
                                             # live generation streams
                                             # mid-token (no terminal
                                             # event; replay state kept
                                             # so clients resume)
    POST /stub/state {"partition_ms": 300}   # half-open partition: ONE
                                             # live stream stalls that
                                             # long with the connection
                                             # open (reads hang, no
                                             # error — the faults.py
                                             # 'partition' shape)

``--ttl S`` makes the process exit nonzero after S seconds — the
always-crashing replica that exhausts a restart budget.

The stub also speaks just enough of the KServe inference surface for
the distributed perf_analyzer coordinator's tier-1 tests (N real
worker processes driving N stub replicas, zero jax imports): model
``stub`` (INPUT0 FP32[8] -> OUTPUT0 FP32[1]) with metadata / config /
stats / infer plus a ``/metrics`` Prometheus exposition whose
``stub_requests_total`` counter moves with served inferences
(``--infer-delay-ms`` pins a synthetic latency floor).

``/v2/models/stub/generate_stream`` emulates the scheduler-backed
resumable SSE contract closely enough for router-HA tier-1 tests:

- tokens are **autoregressive and continuation-consistent** —
  ``next_token(fed) = (sum(fed)*31 + len(fed)) % 100`` over every fed
  id (prompt + emitted history) — so the router's cross-replica
  handoff re-prefill (``prompt + history``, shrunk ``MAX_TOKENS``)
  continues token-identically, exactly like greedy llama decode;
- each generation parks a replica-local replay record keyed by its
  ``generation_id``: a reconnect with ``Last-Event-ID: <gid>/<seq>``
  replays the gap and splices the live continuation, an unknown gid
  answers the typed 404 the real scheduler would;
- ``parameters.token_delay_ms`` stretches token cadence so kill tests
  can land a SIGKILL provably mid-generation.

Model ``stubgen`` is the same generation machinery behind
generation-shaped KServe metadata (``PROMPT_IDS`` INT32[-1] +
``MAX_TOKENS`` INT32[1] -> ``TOKEN`` INT32[-1]) so the distributed
perf_analyzer's ``--generation`` pool builder can drive a stub fleet;
``/metrics`` additionally exposes ``tpu_prefix_cache_hits_total`` /
``tpu_prefix_cache_misses_total`` moved by longest-seen-prefix
matching over generation prompts, giving chaos-campaign proof runs a
real fleet prefix-hit%% column without jax replicas.
"""

import argparse
import json
import os
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


def free_port():
    """An OS-assigned free localhost port — the one spawn-a-stub
    helper every stub-fleet test shares (import it; don't copy it)."""
    import socket

    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]
    finally:
        sock.close()


def wait_ready(port, timeout_s=20.0):
    """Poll a just-spawned stub's ``/v2/health/ready`` until it
    answers 200 (or the timeout passes)."""
    import http.client

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=2)
        try:
            conn.request("GET", "/v2/health/ready")
            if conn.getresponse().status == 200:
                return True
        except OSError:
            pass
        finally:
            conn.close()
        time.sleep(0.05)
    return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--scope", default="stub")
    ap.add_argument("--role", default="",
                    help="phase role advertised in /v2/health/stats "
                         "(prefill/decode; empty = fused) — what "
                         "role-aware supervisor/router tests partition "
                         "stub fleets with")
    ap.add_argument("--spawn-nonce", default="",
                    help="spawn identity nonce echoed in "
                         "/v2/health/stats (the supervisor-adoption "
                         "contract fleet HA tests pin)")
    ap.add_argument("--drain-s", type=float, default=0.1)
    ap.add_argument("--marker", default="")
    ap.add_argument("--ttl", type=float, default=0.0,
                    help="exit 1 after this many seconds (0 = never)")
    ap.add_argument("--never-ready", action="store_true",
                    help="answer probes but report ready=false forever "
                         "(a start that never completes)")
    ap.add_argument("--infer-delay-ms", type=float, default=0.0,
                    help="synthetic latency floor per /infer request")
    ap.add_argument("--infer-jitter-ms", type=float, default=0.0,
                    help="deterministic pseudo-random extra latency in "
                         "[0, this) per /infer, from an LCG seeded by "
                         "the port — the stdlib twin of the faults.py "
                         "'jitter' mode, so gray-failure tier-1 tests "
                         "get realistic latency spread without jax "
                         "replicas")
    args = ap.parse_args()

    lock = threading.Lock()
    state = {"state": "starting" if args.never_ready else "ready",
             "ready": not args.never_ready, "wedged": False,
             # runtime-adjustable latency (POST /stub/state): how gray
             # tests make ONE replica of a stub fleet slow mid-soak
             # (the process keeps answering probes — that is the gray
             # shape) and then recover it
             "infer_delay_ms": args.infer_delay_ms,
             "infer_jitter_ms": args.infer_jitter_ms,
             # one-shot chaos-campaign controls (POST /stub/state):
             # a sever budget (next N live streams get dropped with no
             # terminal event) and a half-open partition (ONE live
             # stream stalls with its connection open)
             "sever_streams": 0,
             "partition_ms": 0.0}
    # glibc LCG constants over 2^31 — matches tpuserver.faults' jitter
    # mode so stub soaks replay exactly run to run
    lcg = {"state": (args.port * 2654435761) % (1 << 31)}

    def next_jitter_ms():
        with lock:
            jitter = state["infer_jitter_ms"]
            if jitter <= 0:
                return 0.0
            lcg["state"] = (1103515245 * lcg["state"] + 12345) % (1 << 31)
            return jitter * lcg["state"] / (1 << 31)
    model = {
        "live_streams": 0, "pending": 0, "max_slots": 4,
        "max_pending": 16, "tripped": False, "draining": False,
        "closed": False, "healthy": True, "restarts": 0,
        "quarantined": 0, "replay_entries": 0,
    }

    served = {"count": 0, "ns": 0, "gen": 0}
    # longest-seen-prefix accounting over generation prompts: the stub
    # twin of the radix prefix cache's hit/miss token counters, so a
    # fleet /metrics view (and a perf proof run's prefix-hit%% column)
    # has real numbers to aggregate.  "seen" holds every prefix tuple
    # of every admitted prompt
    prefix = {"seen": set(), "hits": 0, "misses": 0}
    # replica-local generation replay state: gid -> {"fed": [ids the
    # virtual model consumed], "emitted": [tokens], "target": int,
    # "delay_ms": float, "done": bool} — what makes Last-Event-ID
    # resume and token-identical handoff continuations possible
    gens = {}
    # stub twin of the server's KV-export registry: gid -> {"claimed",
    # "position"}; populated when a kv_phase=prefill generation
    # finishes, one-shot claimed by the first descriptor fetch (second
    # fetch answers the typed 409), released/404 after drop — the
    # lifetime edges disagg router tests exercise without jax
    kvx = {}

    def next_token(fed):
        # deterministic autoregressive "model": the next token depends
        # only on everything fed so far, so re-prefilling
        # prompt+history anywhere continues the identical stream.
        # Prime modulus + a position-squared term keep the sequence
        # varied (a plain sum%100 collapses to a fixed point: the
        # emitted token's contribution can cancel mod 100)
        return (sum(fed) * 31 + len(fed) * len(fed) * 7 + 13) % 101

    def snapshot():
        with lock:
            snap = {
                "state": state["state"],
                "ready": state["ready"] and not model["tripped"],
                "inflight": 0,
                "max_inflight": None,
                "pid": os.getpid(),
                "role": args.role or None,
                "models": {"stub": dict(model),
                           "stubgen": dict(model)},
            }
            if args.spawn_nonce:
                snap["spawn_nonce"] = args.spawn_nonce
            return snap

    STUB_METADATA = {
        "name": "stub", "versions": ["1"], "platform": "stub",
        "inputs": [
            {"name": "INPUT0", "datatype": "FP32", "shape": [8]}],
        "outputs": [
            {"name": "OUTPUT0", "datatype": "FP32", "shape": [1]}],
    }
    STUB_CONFIG = {
        "name": "stub", "platform": "stub", "max_batch_size": 0,
        "input": [{"name": "INPUT0", "data_type": "TYPE_FP32",
                   "dims": [8]}],
        "output": [{"name": "OUTPUT0", "data_type": "TYPE_FP32",
                    "dims": [1]}],
    }
    # the generation-shaped alias: same replay/resume machinery as
    # /v2/models/stub/generate_stream, but with the dynamic-prompt
    # metadata perf_analyzer's --generation pool builder synthesizes
    # against (PROMPT_IDS gets --prompt-len ids, MAX_TOKENS is pinned)
    STUBGEN_METADATA = {
        "name": "stubgen", "versions": ["1"], "platform": "stub",
        "inputs": [
            {"name": "PROMPT_IDS", "datatype": "INT32", "shape": [-1]},
            {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1]}],
        "outputs": [
            {"name": "TOKEN", "datatype": "INT32", "shape": [-1]}],
    }
    STUBGEN_CONFIG = {
        "name": "stubgen", "platform": "stub", "max_batch_size": 0,
        "input": [{"name": "PROMPT_IDS", "data_type": "TYPE_INT32",
                   "dims": [-1]},
                  {"name": "MAX_TOKENS", "data_type": "TYPE_INT32",
                   "dims": [1]}],
        "output": [{"name": "TOKEN", "data_type": "TYPE_INT32",
                    "dims": [-1]}],
    }

    def model_statistics():
        with lock:
            count, ns = served["count"], served["ns"]
        buckets = {
            key: {"count": count, "ns": ns if key == "success" else 0}
            for key in ("success", "queue", "compute_input",
                        "compute_infer", "compute_output")
        }
        buckets["fail"] = {"count": 0, "ns": 0}
        return {"model_stats": [{
            "name": "stub", "version": "1", "last_inference": 0,
            "inference_count": count, "execution_count": count,
            "inference_stats": buckets, "batch_stats": [],
        }]}

    def metrics_text():
        with lock:
            count = served["count"]
            gens = served["gen"]
            hits, misses = prefix["hits"], prefix["misses"]
        return (
            "# HELP stub_requests_total Inferences served by this "
            "stub replica.\n"
            "# TYPE stub_requests_total counter\n"
            "stub_requests_total {}\n"
            "# HELP stub_generations_total Generation streams served "
            "by this stub replica.\n"
            "# TYPE stub_generations_total counter\n"
            "stub_generations_total {}\n"
            "# HELP tpu_prefix_cache_hits_total Prompt tokens served "
            "from the (stub) prefix cache.\n"
            "# TYPE tpu_prefix_cache_hits_total counter\n"
            "tpu_prefix_cache_hits_total {}\n"
            "# HELP tpu_prefix_cache_misses_total Prompt tokens "
            "prefilled cold by the (stub) prefix cache.\n"
            "# TYPE tpu_prefix_cache_misses_total counter\n"
            "tpu_prefix_cache_misses_total {}\n".format(
                count, gens, hits, misses))

    class Handler(BaseHTTPRequestHandler):
        # the stub answers with several small writes (status, headers,
        # body); Nagle + delayed-ACK turns those into occasional
        # ~40-200ms stalls that would drown the latency signals the
        # gray-failure tests measure
        disable_nagle_algorithm = True

        def log_message(self, *a):
            pass

        def _json(self, obj, code=200):
            body = json.dumps(obj).encode("utf-8")
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            with lock:
                wedged = state["wedged"]
            if wedged:
                time.sleep(60)  # probe times out: the wedge signal
                return
            if self.path == "/v2/health/stats":
                return self._json(snapshot())
            if self.path == "/v2/health/live":
                return self._json({})
            if self.path == "/v2/health/ready":
                with lock:
                    ready = state["ready"]
                return self._json({}, 200 if ready else 503)
            if self.path == "/v2/models/stub":
                return self._json(STUB_METADATA)
            if self.path == "/v2/models/stub/config":
                return self._json(STUB_CONFIG)
            if self.path == "/v2/models/stubgen":
                return self._json(STUBGEN_METADATA)
            if self.path == "/v2/models/stubgen/config":
                return self._json(STUBGEN_CONFIG)
            if self.path in ("/v2/models/stats", "/v2/models/stub/stats",
                             "/v2/models/stubgen/stats"):
                return self._json(model_statistics())
            if self.path.startswith("/v2/kvexport/"):
                from urllib.parse import unquote

                gid = unquote(self.path[len("/v2/kvexport/"):])
                with lock:
                    entry = kvx.get(gid)
                    if entry is None:
                        pass  # typed 404 below, outside the lock
                    elif entry["claimed"]:
                        entry = "claimed"
                    else:
                        entry["claimed"] = True
                        position = entry["position"]
                if entry is None:
                    return self._json(
                        {"error": "no kv export for generation "
                                  "'{}'".format(gid)}, 404)
                if entry == "claimed":
                    return self._json(
                        {"error": "kv export for generation '{}' was "
                                  "already claimed".format(gid)}, 409)
                # shaped like InferenceServer.kv_export_descriptor;
                # the raw handle is a placeholder (a stub has no
                # device pages) — the decode stub ignores kv_attach
                # and recomputes, which lands on the identical stream
                return self._json({
                    "generation_id": gid,
                    "name": "kvexport/" + gid,
                    "raw_handle": "c3R1Yi1rdi1leHBvcnQ=",
                    "position": position,
                    "shape": [1, 1, 1, 1],
                    "dtype": "bfloat16",
                    "byte_size": 4096,
                    "device_ordinal": 0,
                })
            if self.path == "/metrics":
                body = metrics_text().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            self._json({"error": "unknown: " + self.path}, 404)

        def _emit_event(self, gid, seq, token, model_name="stub"):
            payload = {
                "model_name": model_name,
                "outputs": [{"name": "TOKEN", "datatype": "INT32",
                             "shape": [1], "data": [int(token)]}],
                "parameters": {"generation_id": gid, "seq": seq},
            }
            self.wfile.write(
                "id: {}/{}\n".format(gid, seq).encode("ascii")
                + b"data: " + json.dumps(payload).encode("ascii")
                + b"\n\n")

        def _generate_stream(self, body, model_name="stub"):
            """The scheduler-backed SSE generate contract, stub-sized:
            TOKEN events with generation_id/seq parameters, the
            explicit terminal event, Last-Event-ID resume from a
            replica-local replay record, and continuation-consistent
            autoregressive tokens (handoff re-prefill lands on the
            identical stream)."""
            try:
                request = json.loads(body or b"{}")
                inputs = {t.get("name"): t.get("data") or []
                          for t in request.get("inputs") or []}
                prompt = [int(v) for v in inputs.get(
                    "PROMPT_IDS") or [0]]
                max_tokens = int((inputs.get("MAX_TOKENS") or [4])[0])
                params = request.get("parameters") or {}
                gid = str(params.get("generation_id") or "")
                delay_ms = float(params.get("token_delay_ms") or 0.0)
                kv_prefill = params.get("kv_phase") == "prefill"
            except (TypeError, ValueError):
                return self._json(
                    {"error": "malformed generate request"}, 400)
            from_seq = 0
            resuming = False
            last_id = self.headers.get("Last-Event-ID") or ""
            if last_id:
                rid, sep, seq = last_id.rpartition("/")
                if sep and rid:
                    resuming = True
                    gid = rid
                    try:
                        from_seq = int(seq) + 1
                    except ValueError:
                        from_seq = 0
            with lock:
                if not resuming and not gid:
                    # anonymous fresh admission: assign a unique gid
                    # (scheduler parity — the real server mints one),
                    # so N concurrent perf streams never supersede
                    # each other's replay records
                    served["gidseq"] = served.get("gidseq", 0) + 1
                    gid = "stubgen-{}".format(served["gidseq"])
                entry = gens.get(gid)
                if resuming:
                    if entry is None:
                        pass  # typed 404 below, outside the lock
                else:
                    # fresh admission (a handoff re-admission reusing
                    # the id supersedes, scheduler-parity): the fed
                    # sequence IS the replay/continuation state
                    entry = gens[gid] = {
                        "fed": list(prompt), "emitted": [],
                        "target": max_tokens, "delay_ms": delay_ms,
                        "done": False,
                    }
                    served["gen"] += 1
                    # longest-seen-prefix hit/miss accounting (token
                    # units, like the real radix cache's counters)
                    t = tuple(prompt)
                    best = 0
                    for i in range(len(t), 0, -1):
                        if t[:i] in prefix["seen"]:
                            best = i
                            break
                    prefix["hits"] += best
                    prefix["misses"] += len(t) - best
                    for i in range(1, len(t) + 1):
                        prefix["seen"].add(t[:i])
            if resuming and entry is None:
                return self._json(
                    {"error": "unknown or expired generation id "
                              "'{}'".format(gid)}, 404)
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            try:
                while True:
                    sever = False
                    stall_ms = 0.0
                    with lock:
                        emitted = list(entry["emitted"])
                        done = entry["done"]
                        delay = entry["delay_ms"]
                        if from_seq > 0:
                            # one-shot chaos controls land only MID-
                            # stream (at least one event already out on
                            # THIS connection): a sever drops it with
                            # no terminal event (replay state stays for
                            # the client's resume); a partition stalls
                            # it with the connection open — the
                            # half-open shape (reads hang, no error)
                            if state["sever_streams"] > 0:
                                state["sever_streams"] -= 1
                                sever = True
                            elif state["partition_ms"] > 0:
                                stall_ms = state["partition_ms"]
                                state["partition_ms"] = 0.0
                    if sever:
                        self.close_connection = True
                        return
                    if stall_ms > 0:
                        time.sleep(stall_ms / 1000.0)
                    # replay the requester's gap, then splice live
                    while from_seq < len(emitted):
                        self._emit_event(
                            gid, from_seq, emitted[from_seq],
                            model_name)
                        from_seq += 1
                    if done:
                        break
                    with lock:
                        if len(entry["emitted"]) >= entry["target"]:
                            entry["done"] = True
                            continue
                        token = next_token(entry["fed"])
                        entry["fed"].append(token)
                        entry["emitted"].append(token)
                    if delay > 0:
                        time.sleep(delay / 1000.0)
                if kv_prefill:
                    # the prefill leg finished: publish the export the
                    # router's KV transfer will claim (position = every
                    # id the virtual model consumed, scheduler-parity)
                    with lock:
                        kvx.setdefault(gid, {
                            "claimed": False,
                            "position": len(entry["fed"]),
                        })
                self.wfile.write(b'data: {"final": true}\n\n')
            except (BrokenPipeError, ConnectionResetError, OSError):
                # requester hung up mid-stream (a severed router
                # relay): the replay record stays for its resume
                pass
            self.close_connection = True

        def do_POST(self):
            length = int(self.headers.get("Content-Length") or 0)
            body = self.rfile.read(length) if length else b""
            if self.path == "/v2/models/stub/infer":
                t0 = time.perf_counter()
                with lock:
                    delay_ms = state["infer_delay_ms"]
                delay_ms += next_jitter_ms()
                if delay_ms > 0:
                    time.sleep(delay_ms / 1000.0)
                with lock:
                    served["count"] += 1
                    served["ns"] += int(
                        (time.perf_counter() - t0) * 1e9)
                return self._json({
                    "model_name": "stub", "model_version": "1",
                    "outputs": [{"name": "OUTPUT0", "datatype": "FP32",
                                 "shape": [1], "data": [0.0]}],
                })
            if self.path == "/v2/models/stub/generate_stream":
                return self._generate_stream(body)
            if self.path == "/v2/models/stubgen/generate_stream":
                return self._generate_stream(body, "stubgen")
            if (self.path.startswith("/v2/kvexport/")
                    and self.path.endswith("/release")):
                from urllib.parse import unquote

                gid = unquote(
                    self.path[len("/v2/kvexport/"):-len("/release")])
                with lock:
                    kvx.pop(gid, None)  # idempotent, like the server
                return self._json({})
            if self.path != "/stub/state":
                return self._json({"error": "unknown: " + self.path}, 404)
            update = json.loads(body or b"{}")
            with lock:
                for key, val in update.items():
                    if key in model:
                        model[key] = val
                    else:
                        state[key] = val
            self._json(snapshot())

    httpd = ThreadingHTTPServer(("127.0.0.1", args.port), Handler)
    httpd.daemon_threads = True

    def on_sigterm(signum, frame):
        with lock:
            state["state"] = "draining"
            state["ready"] = False
        if args.marker:
            with open(args.marker, "a") as fh:
                fh.write("drain\n")
        # drain window, then a clean exit (what install_sigterm_drain
        # does on a real replica, compressed)
        threading.Timer(args.drain_s, lambda: os._exit(0)).start()

    signal.signal(signal.SIGTERM, on_sigterm)
    if args.ttl > 0:
        threading.Timer(args.ttl, lambda: os._exit(1)).start()
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    print("stub replica [{}] on 127.0.0.1:{} pid {}".format(
        args.scope, args.port, os.getpid()), flush=True)
    while True:
        time.sleep(3600)


if __name__ == "__main__":
    sys.exit(main())
