#!/usr/bin/env python3
"""Chaos smoke soak: injected failures against an in-process server,
nonzero exit on any resilience-invariant violation.

Runs rounds of concurrent generations on the continuous-batching
scheduler while cycling fault injections (decode-step raise, host-
transfer raise, admit raise, slow step + mid-generation deadline), and
finishes with a transient-overload phase through the real HTTP frontend
ridden out by the client retry policy.  After every round it asserts
the invariants PR 2 promises:

  1. every request reaches a terminal outcome (tokens or a typed error
     — never a hang);
  2. zero leaked slots/streams (the scheduler's live registry empties);
  3. the decode loop stays healthy (recovery, not watchdog trip);
  4. a clean request after the chaos produces greedy tokens IDENTICAL
     to the pre-chaos reference (the donated cache was rebuilt right).

Usage:
    python tools/chaos_smoke.py [--rounds N] [--slots K] [--budget T]
    python tools/chaos_smoke.py --pool [--cycles N] [--soak M]
    python tools/chaos_smoke.py --kill-loop [--rounds N]
    python tools/chaos_smoke.py --shm [--rounds N]
    python tools/chaos_smoke.py --router [--cycles N] [--soak M]
    python tools/chaos_smoke.py --fleet [--cycles N] [--soak M]
    python tools/chaos_smoke.py --gray [--cycles N] [--soak M]
    python tools/chaos_smoke.py --router-kill [--cycles N] [--soak M]
    python tools/chaos_smoke.py --disagg [--cycles N] [--soak M]
    python tools/chaos_smoke.py --supervisor [--cycles N] [--soak M]

``--kill-loop`` soaks the supervised-restart layer: every round kills
the decode loop mid-traffic (injected step failure = loop death) while
concurrent generations are in flight, and asserts the supervisor
auto-restarted with ZERO lost or corrupted streams — every request
completes with tokens identical to the fault-free reference, restart
counters rise accordingly, and the scheduler never trips.

``--shm`` soaks the shared-memory data plane (ISSUE 12): concurrent
token-ring generations with the decode loop killed mid-traffic every
round, plus a disconnect -> park-export -> attach-resume cycle.
Invariants: rings token-identical to the fault-free reference after
healing, ``xla_shm_status`` consistent (no stale ``kvexport/*``), and
teardown leaves zero leaked regions.

``--router`` soaks the server-side fleet tier (ISSUE 7): PLAIN clients
stream generations through a FleetRouter over two llama replicas while
every cycle (a) SIGTERM-drains and revives one replica mid-traffic and
(b) severs live upstream streams mid-generation (scoped fault = the
serving replica's connection dying).  Invariants: ZERO user-visible
errors, every stream's tokens identical to the fault-free reference
with gap-free duplicate-free seqs (the router's cross-replica handoff
and failover absorb every fault), the drained replica rotates out
before requests land on it and rotates back in after revival, and no
replica leaks streams.

``--fleet`` soaks the full supervised tier (ISSUE 9): real replica
server PROCESSES under a FleetSupervisor + FleetRouter, with a random
replica SIGKILLed (not SIGTERM — no drain, no warning) mid-traffic
every cycle.  Invariants: ZERO user-visible errors, every stream's
tokens identical to the fault-free reference with gap-free
duplicate-free seqs (the router's handoff absorbs the kill), and the
supervisor restores the fleet to its target replica count — with live
router membership — before the next cycle.

``--gray`` soaks the tail-latency defense (ISSUE 13): a FleetRouter
over stdlib stub replicas with one replica turned GRAY — alive to
every health probe, two orders of magnitude slower to serve — each
cycle.  Invariants: the router soft-ejects it on the latency
differential alone, fleet p99 returns to within 2x of the healthy
baseline while the fault is still active, zero user-visible errors,
and the replica re-admits itself via probe traffic once it recovers.

``--router-kill`` soaks router HA (ISSUE 15): a supervised stub fleet
fronted by ACTIVE + STANDBY router processes sharing one crash
journal, with the ACTIVE router SIGKILLed mid-traffic every cycle.
Invariants: the supervisor promotes the standby (takeover counter
moves) and respawns the casualty as the new standby, clients carrying
both router urls see ZERO user-visible errors, every stream —
including the ones severed by the kill — completes token-identical
with gap-free seqs via journal-recovered resume state, and the
promoted router's ``recovered_generations`` counter moves.

``--disagg`` soaks disaggregated prefill/decode serving (ISSUE 16): a
role fleet (one PREFILL + one DECODE stub replica under a
FleetSupervisor) serves phase-split generations while the PREFILL
replica is SIGKILLed mid-handoff every cycle — the window where its
token has relayed but the KV descriptor claim / decode leg is still
in flight.  Invariants: ZERO user-visible errors (every orphaned
split degrades to the fused path), every stream token-identical to
the fault-free reference with gap-free seqs, the supervisor heals the
prefill pool back to target WITH its role, and the healed replica
rejoins the split plane (``tpu_disagg_splits_total`` resumes moving).

``--supervisor`` soaks supervisor crash durability (ISSUE 18): a REAL
``tools/fleet.py`` supervisor process (stub replicas, a supervised
router process, ``--manifest`` + ``--heartbeat-file``) is SIGKILLed
mid-traffic every cycle while clients stream through the router
process.  Invariants: ZERO user-visible errors while the fleet runs
UNSUPERVISED and across the successor's adoption, the successor
ADOPTS every survivor from the manifest (heartbeat ``adoptions``
moves; every replica keeps its pid AND restart count — no
double-spawn, no budget burn), the port-collision probe sees each
replica port still served by the SAME pid, and the kernel-released
flock lets the successor take the manifest without ``--takeover``.

``--pool`` soaks the multi-replica client layer instead: an
EndpointPool over two in-process HTTP servers with one replica
SIGTERM-drained (PR 2 ``install_sigterm_drain``) and revived on a
cycle.  Invariants: no pool request may fail with a NON-TYPED error
(raw socket errors must be classified/failed-over), the pool sees zero
failures at all while a healthy sibling exists, and the drained
replica's breaker/health recovers after each revival.

CI wiring: run under JAX_PLATFORMS=cpu; exits 0 only if every invariant
held.
"""

import argparse
import os
import sys
import threading
import time

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "python"),
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from tpuserver import chaoslib  # noqa: E402
from tpuserver import faults  # noqa: E402
from tpuserver.core import (  # noqa: E402
    DeadlineExceeded,
    InferenceServer,
    InferRequest,
    ServerError,
)
from tpuserver.models import llama  # noqa: E402
from tpuserver.models.llama_serving import LlamaGenerateModel  # noqa: E402

PROMPTS = [
    np.array([3, 1, 4, 1, 5], dtype=np.int32),
    np.array([9, 8, 7], dtype=np.int32),
    np.array([2, 7, 1, 8, 2, 8], dtype=np.int32),
    np.array([1, 2, 3, 4], dtype=np.int32),
]

# long enough to span a full KV page (page_size 16): repeated streams
# of this prompt exercise the radix prefix cache, whose fleet-view
# counters the router/fleet soaks assert stay monotonic and keep
# MOVING (cold caches re-warm) across SIGKILL healing
SHARED_PROMPT = np.array(
    [7, 3, 11, 4, 9, 2, 6, 13, 5, 1, 8, 12, 10, 14, 15, 7,
     9, 4, 2, 11, 6, 3, 13, 5], dtype=np.int32)

FAULT_CYCLE = [
    ("scheduler.step", "raise", 1, 0.0),
    ("scheduler.fetch", "raise", 1, 0.0),
    ("scheduler.admit", "raise", 1, 0.0),
    ("scheduler.step", "sleep", -1, 0.02),  # + deadline pressure
]

_failures = []


def fail(msg):
    _failures.append(msg)
    print("INVARIANT VIOLATED: {}".format(msg), file=sys.stderr)


#: Every mode's assertions run on the shared invariant library
#: (``tpuserver.chaoslib``); this recorder's sink IS the historical
#: ``fail()`` above, so the ``INVARIANT VIOLATED:`` stderr line, the
#: ``_failures`` count, and the exit code stay byte-identical to the
#: pre-extraction CLI.
RECORDER = chaoslib.InvariantRecorder(sink=lambda v: fail(v.message))


class RouterMetricsCheck(chaoslib.MetricsMonotonicityCheck):
    """Per-cycle telemetry invariant for the router/fleet soaks
    (ISSUE 10), now the shared :class:`chaoslib.MetricsMonotonicityCheck`
    wired to this CLI's recorder: ``GET /metrics`` on the router must
    stay scrapeable under chaos, and its cumulative families must
    NEVER decrease or vanish across cycles — the fleet-aggregated view
    must survive replica restarts and membership churn without
    resetting.  ``prefix_hits`` (PR 11) holds the last scraped
    fleet-wide hit total so phases can assert a respawned replica's
    cold radix cache RE-WARMS."""

    def __init__(self, router_url, context, require_prefix=False):
        super().__init__(router_url, context, RECORDER,
                         require_prefix=require_prefix)


def drive_shared_streams(url, context, cycle, shared_ref, budget, n=2):
    """A burst of the page-spanning ``SHARED_PROMPT`` through a router
    at ``url``: back-to-back siblings exercise the radix prefix cache
    (and prefix-affinity routing), and a replica whose scheduler was
    rebuilt this cycle re-warms its cold cache here — with zero
    user-visible errors and token-identical output.  Shared by the
    ``--router`` and ``--fleet`` soaks."""
    import tritonclient.http as httpclient

    client = httpclient.InferenceServerClient(url)
    try:
        for _ in range(n):
            tokens = []
            try:
                for event in client.generate_stream(
                        "llama_generate",
                        {"PROMPT_IDS": SHARED_PROMPT,
                         "MAX_TOKENS": np.array([budget], np.int32)}):
                    for out in event.get("outputs", []):
                        if out["name"] == "TOKEN":
                            tokens.append(int(out["data"][0]))
            except Exception as e:  # noqa: BLE001 — the invariant
                fail("{} cycle {}: shared-prefix stream error "
                     "({}: {})".format(context, cycle,
                                       type(e).__name__, e))
                continue
            chaoslib.check_token_identity(
                RECORDER, shared_ref, tokens,
                context="{} cycle {}".format(context, cycle),
                message="{} cycle {}: shared-prefix tokens diverged: "
                        "{} != {}".format(context, cycle, tokens,
                                          shared_ref))
    finally:
        client.close()


def assert_prefix_rewarmed(metrics_check, hits_before, cycle):
    """The fleet-aggregated hit counter must have MOVED since the last
    cycle's scrape: a healed replica's cold radix cache re-warmed."""
    if (hits_before is not None
            and metrics_check.prefix_hits is not None
            and metrics_check.prefix_hits <= hits_before):
        fail("{} cycle {}: prefix cache did not re-warm (fleet hits "
             "stuck at {})".format(
                 metrics_check.context, cycle, hits_before))


def generate(core, prompt, n_tokens, parameters=None):
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


def wait_no_leaks(model, where, timeout=10.0):
    drained, stats = chaoslib.wait_stream_drain(
        model._scheduler.stats, timeout_s=timeout)
    if drained:
        return True
    fail("{}: leaked streams {}".format(where, stats))
    return False


def chaos_round(core, model, reference, budget, rnd):
    name, mode, times, delay = FAULT_CYCLE[rnd % len(FAULT_CYCLE)]
    faults.install(name, mode=mode, times=times, delay=delay)
    outcomes = [None] * len(PROMPTS)

    def worker(i):
        params = None
        if mode == "sleep":
            # slow-step round doubles as the deadline probe: this
            # request must expire mid-generation with a typed 504
            params = {"timeout": 300_000} if i == 0 else None
        try:
            outcomes[i] = ("ok", generate(
                core, PROMPTS[i], budget, params))
        except DeadlineExceeded:
            outcomes[i] = ("deadline", None)
        except ServerError as e:
            outcomes[i] = ("err", e)

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(len(PROMPTS))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    faults.clear(name)

    for i, outcome in enumerate(outcomes):
        if outcome is None:
            fail("round {} ({}:{}): request {} never terminated".format(
                rnd, name, mode, i))
        elif outcome[0] == "ok":
            # a request that claims success must be token-exact
            chaoslib.check_token_identity(
                RECORDER, reference[i], outcome[1],
                context="round {}".format(rnd),
                message="round {} ({}:{}): request {} tokens diverged: "
                        "{} != {}".format(
                            rnd, name, mode, i, outcome[1],
                            reference[i]))
    if mode == "sleep" and outcomes[0] is not None:
        if outcomes[0][0] not in ("deadline", "ok"):
            fail("round {} deadline probe got {} instead of a typed "
                 "DeadlineExceeded".format(rnd, outcomes[0][0]))

    wait_no_leaks(model, "round {}".format(rnd))
    if not model.healthy():
        fail("round {} ({}:{}): scheduler watchdog tripped".format(
            rnd, name, mode))
    # recovery bar: a clean run right after the chaos is token-identical
    clean = generate(core, PROMPTS[0], budget)
    chaoslib.check_token_identity(
        RECORDER, reference[0], clean,
        context="round {}".format(rnd),
        message="round {} ({}:{}): post-chaos tokens diverged: "
                "{} != {}".format(rnd, name, mode, clean, reference[0]))
    kinds = [o[0] if o else "hang" for o in outcomes]
    print("round {:2d} fault={}:{} outcomes={} live={}".format(
        rnd, name, mode, kinds, model._scheduler.stats()["live_streams"]))


def overload_phase(core_model_cls):
    """Transient overload through the real HTTP frontend: plain client
    sees 429 + Retry-After; retry-policy client succeeds."""
    import tritonclient.http as httpclient
    from tritonclient.utils import InferenceServerException

    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models.simple import SimpleModel

    core = InferenceServer([SimpleModel()])
    frontend = HttpFrontend(core, port=0).start()
    try:
        data = np.arange(16, dtype=np.int32).reshape(1, 16)
        inputs = [
            httpclient.InferInput("INPUT0", [1, 16], "INT32"),
            httpclient.InferInput("INPUT1", [1, 16], "INT32"),
        ]
        inputs[0].set_data_from_numpy(data)
        inputs[1].set_data_from_numpy(data)
        core.set_max_inflight(0)
        plain = httpclient.InferenceServerClient(
            "127.0.0.1:{}".format(frontend.port))
        try:
            plain.infer("simple", inputs)
            fail("overload: shed request unexpectedly succeeded")
        except InferenceServerException as e:
            if e.status() != "429":
                fail("overload: expected 429, got {}".format(e.status()))
        finally:
            plain.close()
        timer = threading.Timer(0.3, core.set_max_inflight, args=(None,))
        timer.start()
        retrying = httpclient.InferenceServerClient(
            "127.0.0.1:{}".format(frontend.port),
            retry_policy=httpclient.RetryPolicy(
                max_attempts=8, initial_backoff_s=0.1, max_backoff_s=0.5,
            ),
        )
        try:
            result = retrying.infer("simple", inputs)
            if not np.array_equal(result.as_numpy("OUTPUT0"), data + data):
                fail("overload: retried result wrong")
            print("overload phase: shed typed 429, retry client rode "
                  "it out")
        except InferenceServerException as e:
            fail("overload: retry client failed: {}".format(e))
        finally:
            timer.cancel()
            retrying.close()
    finally:
        frontend.stop()
    _ = core_model_cls


def pool_phase(cycles, soak):
    """Multi-replica soak: pool traffic rides out SIGTERM drains of one
    replica; exits nonzero on any non-typed failure (raw socket errors
    leaking through classification) or any failed request at all while
    the healthy sibling is up."""
    import signal

    import numpy as np
    import tritonclient.http as httpclient
    from tritonclient.utils import InferenceServerException

    from tpuserver.core import InferenceServer, install_sigterm_drain
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models.simple import SimpleModel

    cores = [
        InferenceServer([SimpleModel()], fault_scope=scope)
        for scope in ("pool-a", "pool-b")
    ]
    frontends = [HttpFrontend(core, port=0).start() for core in cores]
    urls = ["127.0.0.1:{}".format(f.port) for f in frontends]
    previous = install_sigterm_drain(cores[1], drain_timeout=5.0)
    pool = httpclient.EndpointPool(
        urls,
        retry_policy=httpclient.RetryPolicy(
            max_attempts=6, initial_backoff_s=0.02, max_backoff_s=0.2),
        breaker_threshold=2,
        breaker_cooldown_s=0.1,
        health_interval_s=0.05,
    )
    data = np.arange(16, dtype=np.int32).reshape(1, 16)

    def make_inputs():
        inputs = [
            httpclient.InferInput("INPUT0", [1, 16], "INT32"),
            httpclient.InferInput("INPUT1", [1, 16], "INT32"),
        ]
        inputs[0].set_data_from_numpy(data)
        inputs[1].set_data_from_numpy(data)
        return inputs

    def replica_b():
        return [e for e in pool.stats()["endpoints"]
                if e["url"] == urls[1]][0]

    try:
        for cycle in range(cycles):
            outcomes = {"ok": 0, "typed": 0, "untyped": 0}

            def worker(n):
                for i in range(n):
                    try:
                        result = pool.infer("simple", make_inputs())
                        if not np.array_equal(
                            result.as_numpy("OUTPUT0"), data + data
                        ):
                            fail("pool cycle: wrong result")
                        outcomes["ok"] += 1
                    except InferenceServerException as e:
                        outcomes["typed"] += 1
                        fail("pool cycle {}: typed failure leaked "
                             "through failover: {}".format(cycle, e))
                    except Exception as e:  # noqa: BLE001 — the invariant
                        outcomes["untyped"] += 1
                        fail("pool cycle {}: NON-TYPED failure {}: "
                             "{}".format(cycle, type(e).__name__, e))

            threads = [
                threading.Thread(target=worker, args=(soak,), daemon=True)
                for _ in range(4)
            ]
            for t in threads:
                t.start()
            time.sleep(0.05)  # traffic in flight on both replicas
            # SIGTERM-drain replica b mid-traffic (PR 2 handler): the
            # drain runs on a worker thread; in-flight work finishes,
            # new work sheds typed 503s that the pool routes around
            os.kill(os.getpid(), signal.SIGTERM)
            for t in threads:
                t.join(timeout=120)
            deadline = time.monotonic() + 10.0
            while (
                cores[1].server_state() != "stopped"
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            if cores[1].server_state() != "stopped":
                fail("pool cycle {}: SIGTERM drain never completed "
                     "(state={})".format(cycle, cores[1].server_state()))
            # revive: re-attach flips stopped -> ready (the balanced
            # detach keeps the frontend refcount at one)
            cores[1].attach_frontend()
            cores[1].detach_frontend()
            deadline = time.monotonic() + 10.0
            while (
                not (replica_b()["healthy"]
                     and replica_b()["breaker"] == "closed")
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            b = replica_b()
            if not b["healthy"] or b["breaker"] != "closed":
                fail("pool cycle {}: drained replica never recovered: "
                     "{}".format(cycle, b))
            print("pool cycle {:2d} outcomes={} replica_b={}".format(
                cycle, outcomes, replica_b()))
    finally:
        signal.signal(signal.SIGTERM, previous)
        pool.close()
        for f in frontends:
            f.stop()


def router_phase(cycles, soak, budget):
    """Fleet-router soak: plain clients stream through a FleetRouter
    over two replicas while one replica SIGTERM-drains/revives and live
    upstream streams are severed mid-generation every cycle."""
    import signal

    import tritonclient.http as httpclient

    from tpuserver.core import install_sigterm_drain
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models.simple import SimpleModel
    from tpuserver.router import FleetRouter

    scopes = ("router-a", "router-b")
    models = [
        LlamaGenerateModel(
            cfg=llama.tiny(vocab=512), max_seq=64, max_slots=4,
            max_restarts=64, restart_window_s=3600.0,
            restart_backoff_s=0.01)
        for _ in scopes
    ]
    cores = [
        InferenceServer([model, SimpleModel()], fault_scope=scope)
        for model, scope in zip(models, scopes)
    ]
    frontends = [HttpFrontend(core, port=0).start() for core in cores]
    urls = ["127.0.0.1:{}".format(f.port) for f in frontends]
    router = FleetRouter(urls, probe_interval_s=0.05,
                         probe_timeout_s=1.0).start()
    previous = install_sigterm_drain(cores[1], drain_timeout=10.0)

    print("warming up both replicas (compiles the scheduler fns)...")
    reference = [generate(cores[0], p, budget) for p in PROMPTS]
    twin = [generate(cores[1], p, budget) for p in PROMPTS]
    if reference != twin:
        fail("router: replicas disagree on greedy reference tokens — "
             "cross-replica handoff cannot be token-identical")
    shared_ref = generate(cores[0], SHARED_PROMPT, budget)
    if shared_ref != generate(cores[1], SHARED_PROMPT, budget):
        fail("router: replicas disagree on the shared-prefix prompt's "
             "greedy tokens")
    print("reference captured; {} cycles of SIGTERM-drain + mid-stream "
          "severs through the router".format(cycles))

    metrics_check = RouterMetricsCheck(
        router.url, "router", require_prefix=True)
    metrics_check.check(-1)  # seed the baseline pre-chaos
    resumes = [0]

    def replica_stats(url):
        return [r for r in router.stats()["replicas"]
                if r["url"] == url][0]

    def worker(wid, n, cycle):
        client = httpclient.InferenceServerClient(router.url)
        try:
            for i in range(n):
                which = (wid + i) % len(PROMPTS)
                tokens = []
                seqs = []
                try:
                    for event in client.generate_stream(
                            "llama_generate",
                            {"PROMPT_IDS": PROMPTS[which],
                             "MAX_TOKENS": np.array([budget], np.int32)},
                            on_reconnect=lambda a, e: resumes.__setitem__(
                                0, resumes[0] + 1)):
                        for out in event.get("outputs", []):
                            if out["name"] == "TOKEN":
                                tokens.append(int(out["data"][0]))
                        params = event.get("parameters") or {}
                        if "seq" in params:
                            seqs.append(params["seq"])
                except Exception as e:  # noqa: BLE001 — the invariant
                    fail("router cycle {}: user-visible stream error "
                         "({}: {})".format(cycle, type(e).__name__, e))
                    continue
                chaoslib.check_token_identity(
                    RECORDER, reference[which], tokens,
                    context="router cycle {}".format(cycle),
                    message="router cycle {}: stream tokens diverged: "
                            "{} != {}".format(cycle, tokens,
                                              reference[which]))
                chaoslib.check_seq_continuity(
                    RECORDER, seqs, expected_len=budget,
                    context="router cycle {}".format(cycle),
                    message="router cycle {}: seq gap/duplicate: "
                            "{}".format(cycle, seqs))
        finally:
            client.close()

    try:
        for cycle in range(cycles):
            stats_before = router.stats()
            # sever the serving connection of up to 2 live streams per
            # replica this cycle: a mid-generation replica-connection
            # death the router must absorb via handoff
            for scope in scopes:
                faults.install("http.generate_stream", mode="raise",
                               times=2, skip=3, scope=scope)
            threads = [
                threading.Thread(target=worker, args=(w, soak, cycle), daemon=True)
                for w in range(4)
            ]
            for t in threads:
                t.start()
            time.sleep(0.05)  # streams in flight through the router
            # SIGTERM-drain replica b mid-traffic: in-flight work
            # finishes, new work sheds typed 503 the router routes
            # around, and the prober rotates b out
            os.kill(os.getpid(), signal.SIGTERM)
            for t in threads:
                t.join(timeout=300)
            faults.clear("http.generate_stream")

            deadline = time.monotonic() + 15.0
            while (cores[1].server_state() != "stopped"
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            if cores[1].server_state() != "stopped":
                fail("router cycle {}: SIGTERM drain never completed "
                     "(state={})".format(cycle, cores[1].server_state()))
            if replica_stats(urls[1])["eligible"]:
                # the prober had a whole drain to notice
                fail("router cycle {}: drained replica still "
                     "eligible".format(cycle))
            # revive: re-attach flips stopped -> ready, the prober
            # rotates b back in
            cores[1].attach_frontend()
            cores[1].detach_frontend()
            deadline = time.monotonic() + 10.0
            while (not replica_stats(urls[1])["eligible"]
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            if not replica_stats(urls[1])["eligible"]:
                fail("router cycle {}: revived replica never rotated "
                     "back in".format(cycle))
            for model, scope in zip(models, scopes):
                if model._scheduler is not None:
                    wait_no_leaks(model, "router cycle {} ({})".format(
                        cycle, scope))
            # telemetry invariant: scrapeable + monotonic across the
            # drain/revive (the fleet view must not reset), and the
            # prefix cache keeps WARMING: the drained replica's
            # scheduler (and radix cache) was rebuilt, so these
            # streams must both succeed and move the fleet hit counter
            hits_before = metrics_check.prefix_hits
            drive_shared_streams(router.url, "router", cycle,
                                 shared_ref, budget)
            metrics_check.check(cycle)
            assert_prefix_rewarmed(metrics_check, hits_before, cycle)
            stats = router.stats()
            print("cycle {:2d} handoffs={} failovers={} shed={} "
                  "client_resumes={}".format(
                      cycle,
                      stats["handoffs"] - stats_before["handoffs"],
                      stats["failovers"] - stats_before["failovers"],
                      stats["shed"] - stats_before["shed"],
                      resumes[0]))
        stats = router.stats()
        if stats["handoffs"] == 0:
            fail("router: the soak never exercised a cross-replica "
                 "handoff (severs did not land mid-stream?)")
    finally:
        signal.signal(signal.SIGTERM, previous)
        router.stop()
        for f in frontends:
            f.stop()
        for c in cores:
            c.close()


def fleet_phase(cycles, soak, budget):
    """Supervised-fleet soak: SIGKILL a random replica PROCESS
    mid-traffic every cycle; the router's handoff keeps every stream
    token-identical and the supervisor restores the replica count."""
    import random
    import signal

    import tritonclient.http as httpclient

    from tpuserver.fleet import FleetSupervisor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    command = [
        sys.executable, os.path.join(repo, "tools", "fleet.py"),
        "--serve-replica", "--port", "{port}", "--scope", "{scope}",
        "--models", "llama,simple", "--slots", "4",
        "--drain-timeout", "10",
    ]
    # min == max pins the target count: this soak is about HEALING
    # back to target, not elastic scaling
    supervisor = FleetSupervisor(
        command, replicas=2, min_replicas=2, max_replicas=2,
        probe_interval_s=0.2, probe_timeout_s=5.0,
        start_timeout_s=180.0, drain_grace_s=10.0,
        # a just-respawned replica compiling its scheduler under full
        # load can stall health answers for seconds; that is warmup,
        # not a wedge — keep the wedge verdict far out of its reach
        # (the PR 5 watchdog's "warm up before tightening" lesson,
        # one level up)
        unhealthy_after=20,
        max_restarts=cycles + 4, restart_window_s=3600.0,
        restart_backoff_s=0.05, scope_prefix="chaos-fleet-r",
        router_kwargs={"probe_interval_s": 0.05},
        env={"PYTHONPATH": os.path.join(repo, "src", "python"),
             "JAX_PLATFORMS": "cpu"},
    ).start()
    rng = random.Random(1234)

    def fleet_recovered(restarts_before, timeout_s=180.0):
        """Recovered = the kill was actually NOTICED (restart counter
        moved past the cycle's baseline — guards against polling a
        stale 'up' before the monitor's next tick) AND the fleet is
        back at target count with full router membership
        (:func:`chaoslib.wait_fleet_converged`)."""
        return chaoslib.wait_fleet_converged(
            supervisor.stats, membership_fn=supervisor.router.membership,
            restarts_above=restarts_before, up=2, members=2,
            timeout_s=timeout_s)

    try:
        if not supervisor.wait_ready(timeout_s=180.0):
            fail("fleet: replicas never became ready")
            return
        client = httpclient.InferenceServerClient(supervisor.router.url)
        print("warming up both replica processes (compiles each "
              "scheduler)...")

        def stream_prompt(prompt):
            tokens, seqs = [], []
            for event in client.generate_stream(
                    "llama_generate",
                    {"PROMPT_IDS": prompt,
                     "MAX_TOKENS": np.array([budget], np.int32)}):
                for out in event.get("outputs", []):
                    if out["name"] == "TOKEN":
                        tokens.append(int(out["data"][0]))
                params = event.get("parameters") or {}
                if "seq" in params:
                    seqs.append(params["seq"])
            return tokens, seqs

        def stream_once(which):
            return stream_prompt(PROMPTS[which])

        reference = []
        for which in range(len(PROMPTS)):
            # one pass per replica so BOTH processes compile outside
            # the soak; greedy decode must agree across processes
            tokens, _ = stream_once(which)
            twin, _ = stream_once(which)
            if tokens != twin:
                fail("fleet: replicas disagree on greedy reference "
                     "tokens for prompt {}".format(which))
            reference.append(tokens)
        shared_ref, _ = stream_prompt(SHARED_PROMPT)
        shared_twin, _ = stream_prompt(SHARED_PROMPT)
        if shared_ref != shared_twin:
            fail("fleet: shared-prefix greedy tokens disagree across "
                 "streams")
        client.close()
        print("reference captured; {} cycles of SIGKILL "
              "mid-traffic".format(cycles))

        metrics_check = RouterMetricsCheck(
            supervisor.router.url, "fleet", require_prefix=True)
        metrics_check.check(-1)  # seed the baseline pre-chaos

        for cycle in range(cycles):
            restarts_before = supervisor.stats()["replica_restarts"]

            def worker(wid, n, cycle=cycle):
                wclient = httpclient.InferenceServerClient(
                    supervisor.router.url)
                try:
                    for i in range(n):
                        which = (wid + i) % len(PROMPTS)
                        try:
                            tokens, seqs = [], []
                            for event in wclient.generate_stream(
                                    "llama_generate",
                                    {"PROMPT_IDS": PROMPTS[which],
                                     "MAX_TOKENS": np.array(
                                         [budget], np.int32)}):
                                for out in event.get("outputs", []):
                                    if out["name"] == "TOKEN":
                                        tokens.append(
                                            int(out["data"][0]))
                                params = event.get("parameters") or {}
                                if "seq" in params:
                                    seqs.append(params["seq"])
                        except Exception as e:  # noqa: BLE001
                            fail("fleet cycle {}: user-visible stream "
                                 "error ({}: {})".format(
                                     cycle, type(e).__name__, e))
                            continue
                        chaoslib.check_token_identity(
                            RECORDER, reference[which], tokens,
                            context="fleet cycle {}".format(cycle),
                            message="fleet cycle {}: stream tokens "
                                    "diverged: {} != {}".format(
                                        cycle, tokens,
                                        reference[which]))
                        chaoslib.check_seq_continuity(
                            RECORDER, seqs, expected_len=budget,
                            context="fleet cycle {}".format(cycle),
                            message="fleet cycle {}: seq gap/"
                                    "duplicate: {}".format(cycle, seqs))
                finally:
                    wclient.close()

            threads = [
                threading.Thread(target=worker, args=(w, soak),
                                 daemon=True)
                for w in range(4)
            ]
            for t in threads:
                t.start()
            time.sleep(0.2)  # streams in flight through the router
            ups = [r for r in supervisor.stats()["replicas"]
                   if r["state"] == "up" and r["pid"]]
            if not ups:
                fail("fleet cycle {}: no live replica to kill".format(
                    cycle))
            else:
                victim = rng.choice(ups)
                os.kill(victim["pid"], signal.SIGKILL)
            for t in threads:
                t.join(timeout=600)
            if not fleet_recovered(restarts_before):
                fail("fleet cycle {}: replica count never recovered "
                     "to target (stats={})".format(
                         cycle, supervisor.stats()))
            # telemetry invariant: the SIGKILLed replica's counters
            # reset to zero in ITS exposition, but the router's
            # fleet-aggregated view must stay monotonic — and stay
            # scrapeable mid-heal.  The respawned replica's cold radix
            # cache must also RE-WARM: shared-prompt siblings succeed
            # and the fleet hit counter keeps moving.
            hits_before = metrics_check.prefix_hits
            drive_shared_streams(supervisor.router.url, "fleet", cycle,
                                 shared_ref, budget)
            metrics_check.check(cycle)
            assert_prefix_rewarmed(metrics_check, hits_before, cycle)
            stats = supervisor.stats()
            print("cycle {:2d} restarts {} -> {} up={} handoffs={}"
                  .format(cycle, restarts_before,
                          stats["replica_restarts"], stats["up"],
                          supervisor.router.stats()["handoffs"]))
        stats = supervisor.stats()
        if stats["replica_restarts"] < cycles:
            fail("fleet: expected >= {} supervised restarts, saw {}"
                 .format(cycles, stats["replica_restarts"]))
        if stats["retired_replicas"]:
            fail("fleet: {} replica(s) retired inside the budget"
                 .format(stats["retired_replicas"]))
    finally:
        supervisor.stop()


def kill_loop_phase(rounds, slots, budget):
    """Repeatedly kill the decode loop mid-traffic; assert supervised
    auto-restart with zero lost or corrupted streams."""
    model = LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=64, max_slots=slots,
        max_restarts=rounds + 4, restart_window_s=3600.0,
        restart_backoff_s=0.01)
    core = InferenceServer([model])
    print("warming up (compiles the scheduler fns)...")
    reference = [generate(core, p, budget) for p in PROMPTS]
    print("reference captured; killing the loop {} times "
          "mid-traffic".format(rounds))

    for rnd in range(rounds):
        restarts_before = model._scheduler.stats()["restarts"]
        outcomes = [None] * len(PROMPTS)
        started = threading.Event()

        def worker(i):
            if i == 0:
                started.set()
            try:
                outcomes[i] = ("ok", generate(core, PROMPTS[i], budget))
            except ServerError as e:
                outcomes[i] = ("err", e)

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(len(PROMPTS))
        ]
        for t in threads:
            t.start()
        started.wait(timeout=10)
        time.sleep(0.01)  # streams in flight on the loop
        # one unattributable step failure = loop death
        faults.install("scheduler.step", mode="raise", times=1)
        for t in threads:
            t.join(timeout=120)
        faults.clear("scheduler.step")

        stats = model._scheduler.stats()
        for i, outcome in enumerate(outcomes):
            if outcome is None:
                fail("kill-loop round {}: request {} never "
                     "terminated".format(rnd, i))
            elif outcome[0] != "ok":
                fail("kill-loop round {}: request {} failed instead of "
                     "healing: {}".format(rnd, i, outcome[1]))
            else:
                chaoslib.check_token_identity(
                    RECORDER, reference[i], outcome[1],
                    context="kill-loop round {}".format(rnd),
                    message="kill-loop round {}: request {} tokens "
                            "corrupted: {} != {}".format(
                                rnd, i, outcome[1], reference[i]))
        if stats["tripped"]:
            fail("kill-loop round {}: scheduler tripped inside the "
                 "budget".format(rnd))
        if not model.healthy():
            fail("kill-loop round {}: unhealthy after restart".format(rnd))
        wait_no_leaks(model, "kill-loop round {}".format(rnd))
        print("round {:2d} restarts {} -> {} outcomes={}".format(
            rnd, restarts_before, stats["restarts"],
            [o[0] if o else "hang" for o in outcomes]))

    core.drain(timeout=10.0)
    if core.server_state() != "stopped":
        fail("kill-loop drain did not stop the server (state={})".format(
            core.server_state()))


def shm_phase(rounds, slots, budget):
    """Soak the shm data plane (ISSUE 12): concurrent token-ring
    generations with the decode loop killed mid-traffic every round,
    plus a disconnect -> park-export -> attach-resume cycle.
    Invariants: every stream heals with ring content token-identical
    to the fault-free reference, ``xla_shm_status`` stays consistent
    after healing (exactly the client's ring region — no stale
    ``kvexport/*``), and teardown leaves ZERO leaked regions."""
    from tritonclient.utils import xla_shared_memory as xshm

    model = LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=64, max_slots=slots,
        max_restarts=rounds + 4, restart_window_s=3600.0,
        restart_backoff_s=0.01)
    core = InferenceServer([model])
    lane_bytes = budget * 8
    ring_size = lane_bytes * (len(PROMPTS) + 1)
    ring = xshm.create_shared_memory_region("chaos_ring", ring_size)
    core.register_xla_shm(
        "chaos_ring", xshm.get_raw_handle(ring), 0, ring_size)

    def ring_tokens(lane, n):
        return [int(xshm.get_contents_as_numpy(
            ring, "INT32", [1], lane * lane_bytes + 8 * (s % budget))[0])
            for s in range(n)]

    print("warming up (compiles the scheduler fns)...")
    reference = [generate(core, p, budget) for p in PROMPTS]
    print("reference captured; {} shm-ring chaos rounds".format(rounds))

    for rnd in range(rounds):
        outcomes = [None] * len(PROMPTS)
        started = threading.Event()

        def worker(i, rnd=rnd):
            if i == 0:
                started.set()
            try:
                outcomes[i] = ("ok", generate(
                    core, PROMPTS[i], budget,
                    parameters={
                        "generation_id": "shm-{}-{}".format(rnd, i),
                        "shm_ring_region": "chaos_ring",
                        "shm_ring_slots": budget,
                        "shm_ring_offset": i * lane_bytes,
                    }))
            except ServerError as e:
                outcomes[i] = ("err", e)

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(len(PROMPTS))
        ]
        for t in threads:
            t.start()
        started.wait(timeout=10)
        time.sleep(0.01)  # streams in flight on the loop
        # loop death mid-traffic: the supervised restart must heal the
        # rings too (replayed slots rewrite, seq numbering preserved)
        faults.install("scheduler.step", mode="raise", times=1)
        for t in threads:
            t.join(timeout=120)
        faults.clear("scheduler.step")
        for i, outcome in enumerate(outcomes):
            if outcome is None:
                fail("shm round {}: stream {} never terminated".format(
                    rnd, i))
            elif outcome[0] != "ok":
                fail("shm round {}: stream {} failed instead of "
                     "healing: {}".format(rnd, i, outcome[1]))
            else:
                got = ring_tokens(i, budget)
                chaoslib.check_token_identity(
                    RECORDER, reference[i], got,
                    context="shm round {}".format(rnd),
                    message="shm round {}: ring {} tokens corrupted "
                            "after healing: {} != {}".format(
                                rnd, i, got, reference[i]))
        # disconnect -> park-export -> attach-resume, on the spare lane
        lane = len(PROMPTS)
        gid = "shm-park-{}".format(rnd)
        params = {"generation_id": gid, "kv_park": True,
                  "shm_ring_region": "chaos_ring",
                  "shm_ring_slots": budget,
                  "shm_ring_offset": lane * lane_bytes}
        req = InferRequest(
            "llama_generate",
            inputs={"PROMPT_IDS": PROMPTS[0],
                    "MAX_TOKENS": np.array([budget], np.int32)},
            parameters=params)
        stream = core.infer_stream(req)
        for _ in range(max(1, budget // 2)):
            next(stream)
        stream.close()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if "kvexport/" + gid in core.xla_shm_status():
                break
            time.sleep(0.02)
        resume_req = InferRequest(
            "llama_generate",
            inputs={"PROMPT_IDS": PROMPTS[0],
                    "MAX_TOKENS": np.array([budget], np.int32)},
            parameters={"resume_generation_id": gid,
                        "resume_from_seq": 0,
                        "shm_ring_region": "chaos_ring",
                        "shm_ring_slots": budget,
                        "shm_ring_offset": lane * lane_bytes})
        # ring-mode events carry only descriptors: token identity is
        # judged on the ring lane; here pin gap-free seq numbering
        seqs = [resp.parameters.get("seq")
                for resp in core.infer_stream(resume_req)]
        chaoslib.check_seq_continuity(
            RECORDER, seqs, expected_len=budget,
            context="shm round {}".format(rnd),
            message="shm round {}: attach-resume seqs not gap-free: "
                    "{}".format(rnd, seqs))
        chaoslib.check_token_identity(
            RECORDER, reference[0], ring_tokens(lane, budget),
            context="shm round {}".format(rnd),
            message="shm round {}: attach-resume ring lane not "
                    "rewritten".format(rnd))
        status = set(core.xla_shm_status())
        chaoslib.check_shm_consistency(
            RECORDER, status, {"chaos_ring"},
            context="shm round {}".format(rnd),
            message="shm round {}: xla_shm_status inconsistent after "
                    "healing: {}".format(rnd, sorted(status)))
        wait_no_leaks(model, "shm round {}".format(rnd))
        stats = model._scheduler.stats()
        print("round {:2d} restarts={} status ok".format(
            rnd, stats["restarts"]))

    core.drain(timeout=10.0)
    if core.server_state() != "stopped":
        fail("shm drain did not stop the server (state={})".format(
            core.server_state()))
    # drain dropped every server-owned export; only the client ring
    # remains, and its unregister must now succeed (no lingering pins)
    leftovers = set(core.xla_shm_status())
    chaoslib.check_shm_consistency(
        RECORDER, leftovers, {"chaos_ring"}, context="shm teardown",
        message="shm teardown: leaked regions {}".format(
            sorted(leftovers)))
    try:
        core.unregister_xla_shm("chaos_ring")
    except ServerError as e:
        fail("shm teardown: ring still pinned after drain: {}".format(e))
    if core.xla_shm_status() != {}:
        fail("shm teardown: regions leaked past unregister")
    xshm.destroy_shared_memory_region(ring)


def gray_phase(cycles, soak):
    """``--gray``: gray-failure ejection soak (tail-latency defense).

    A FleetRouter fronts three stdlib STUB replicas (tests/
    fleet_stub.py — no jax import, per the tier-1 runtime budget) with
    baseline latency jitter.  Each cycle one replica turns GRAY — it
    keeps answering health probes but serves ``/infer`` two orders of
    magnitude slower (``POST /stub/state {"infer_delay_ms": ...}``,
    the stub twin of arming ``scheduler.step@scope`` with the
    ``slow`` fault mode on a real replica) — while plain unary
    traffic keeps flowing through the router.  Invariants:

      1. the router SOFT-EJECTS the gray replica (its ``/router/stats``
         row reads ``soft-ejected`` and ``tpu_router_ejections_total``
         moves on ``/metrics``) without any health signal changing;
      2. fleet p99 over the post-ejection window returns to within 2x
         of the healthy baseline (ejected-replica probes are shadowed,
         so the probe fraction never reappears in the tail);
      3. ZERO user-visible errors at any point;
      4. after the fault clears, probe traffic re-admits the replica
         (status back to ``ok``) — no operator, no restart.
    """
    import http.client
    import json as _json
    import subprocess

    from perfanalyzer.metrics import percentile
    from tpuserver.router import FleetRouter

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stub_path = os.path.join(repo, "tests", "fleet_stub.py")
    sys.path.insert(0, os.path.join(repo, "tests"))
    from fleet_stub import free_port, wait_ready

    ports = [free_port() for _ in range(3)]
    procs = [
        subprocess.Popen([
            sys.executable, stub_path, "--port", str(p),
            "--infer-jitter-ms", "2",
        ])
        for p in ports
    ]
    urls = ["127.0.0.1:{}".format(p) for p in ports]
    infer_body = _json.dumps({"inputs": [
        {"name": "INPUT0", "datatype": "FP32", "shape": [8],
         "data": [0.0] * 8}]}).encode("utf-8")

    def set_state(port, **state):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        try:
            conn.request("POST", "/stub/state", _json.dumps(state),
                         {"Content-Type": "application/json"})
            if conn.getresponse().status != 200:
                fail("gray: stub state update refused")
        finally:
            conn.close()

    def infer_once(router):
        """One unary infer through the router: latency seconds, or
        None on a user-visible error (the invariant-3 signal)."""
        host, _, port = router.url.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=30)
        t0 = time.monotonic()
        try:
            conn.request("POST", "/v2/models/stub/infer", infer_body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                fail("gray: user-visible error {}: {}".format(
                    resp.status, body[:200]))
                return None
            return time.monotonic() - t0
        except (OSError, http.client.HTTPException) as e:
            fail("gray: user-visible transport error: {}".format(e))
            return None
        finally:
            conn.close()

    def drive(router, n, workers=4):
        """``n`` requests spread over concurrent workers (sequential
        clients all tie at load 0 and pile onto one replica — the
        in-flight spread is what gives every replica digest coverage,
        exactly like production concurrency would)."""
        lats = []
        lock = threading.Lock()

        def worker(count):
            for _ in range(count):
                lat = infer_once(router)
                if lat is not None:
                    with lock:
                        lats.append(lat)

        per = max(1, n // workers)
        threads = [threading.Thread(target=worker, args=(per,),
                                    daemon=True)
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return lats

    def victim_row(router, url):
        for row in router.stats()["replicas"]:
            if row["url"] == url:
                return row
        return None

    def ejections_metric(router):
        text = router.metrics_text()
        for line in text.splitlines():
            if line.startswith("tpu_router_ejections_total"):
                return float(line.split()[-1])
        return None

    try:
        for p in ports:
            if not wait_ready(p):
                fail("gray: stub replica never became ready")
                return
        # fast knobs so each cycle's eject->recover->re-admit arc fits
        # a soak budget: small digest, quarter probe fraction, 10 Hz
        # probes driving the (0.1s-throttled) ejection evaluation
        router = FleetRouter(
            urls, probe_interval_s=0.1, outlier_factor=3.0,
            outlier_min_samples=6, min_eligible=1,
            probe_fraction=1.0 / 4, eject_interval_s=0.1,
            digest_window=12).start()
        try:
            drive(router, 12)  # connection/thread warmup out of baseline
            for cycle in range(cycles):
                victim = ports[cycle % len(ports)]
                victim_url = "127.0.0.1:{}".format(victim)
                baseline = drive(router, soak)
                if not baseline:
                    return
                healthy_p99 = percentile(baseline, 99)
                ejections_before = ejections_metric(router)
                set_state(victim, infer_delay_ms=200)
                # traffic under the gray fault: the router needs
                # enough completed requests to see the outlier
                deadline = time.monotonic() + 30.0
                ejected = False
                while time.monotonic() < deadline:
                    drive(router, 6)
                    row = victim_row(router, victim_url)
                    if row is not None and row["status"] == "soft-ejected":
                        ejected = True
                        break
                if not ejected:
                    fail("gray cycle {}: router never soft-ejected the "
                         "slow replica".format(cycle))
                    set_state(victim, infer_delay_ms=0)
                    continue
                row = victim_row(router, victim_url)
                if not row["eligible"]:
                    fail("gray cycle {}: ejection leaked into health "
                         "eligibility (gray != down)".format(cycle))
                after = ejections_metric(router)
                if ejections_before is not None and (
                        after is None or after <= ejections_before):
                    fail("gray cycle {}: tpu_router_ejections_total did "
                         "not move ({} -> {})".format(
                             cycle, ejections_before, after))
                # invariant 2: the tail recovers while the fault is
                # STILL active — ejection (plus shadowed probes) is
                # what defends p99, not the fault clearing
                # within 2x of healthy (floored at 50ms of noise
                # headroom) AND strictly under the injected 200ms
                # delay — a single un-shadowed request to the gray
                # replica in the window would break the latter, so a
                # noisy healthy baseline can never mask a defense that
                # is not actually working.  One re-measure absorbs a
                # lone scheduler spike on a loaded CI box; a real
                # defense failure repeats.
                bound = min(max(2 * healthy_p99, 0.05), 0.18)
                p99 = None
                for _attempt in range(2):
                    recovered = drive(router, soak)
                    if not recovered:
                        break
                    p99 = percentile(recovered, 99)
                    if p99 <= bound:
                        break
                if p99 is not None and p99 > bound:
                    fail("gray cycle {}: fleet p99 {:.1f}ms did not "
                         "recover (healthy baseline {:.1f}ms, bound "
                         "{:.1f}ms)".format(
                             cycle, p99 * 1e3, healthy_p99 * 1e3,
                             bound * 1e3))
                # recovery: clear the fault, probe traffic re-admits
                set_state(victim, infer_delay_ms=0)
                deadline = time.monotonic() + 30.0
                readmitted = False
                while time.monotonic() < deadline:
                    drive(router, 8)
                    row = victim_row(router, victim_url)
                    if row is not None and row["status"] == "ok":
                        readmitted = True
                        break
                if not readmitted:
                    fail("gray cycle {}: replica never re-admitted "
                         "after the fault cleared".format(cycle))
                print("gray cycle {}: ejected + p99 recovered + "
                      "re-admitted (healthy p99 {:.1f}ms)".format(
                          cycle, healthy_p99 * 1e3), flush=True)
        finally:
            router.stop()
    finally:
        for proc in procs:
            proc.kill()
        for proc in procs:
            proc.wait(timeout=10)


def router_kill_phase(cycles, soak, budget):
    """``--router-kill``: router-HA soak (ISSUE 15).

    A FleetSupervisor owns two stdlib stub replicas AND the front tier
    itself: an active router process (``tools/router.py --journal``)
    plus a warm standby tailing the same journal.  Each cycle, worker
    clients — carrying BOTH router urls, the ``fallback_urls`` rotation
    — stream slow generations while the ACTIVE router is SIGKILLed
    mid-traffic.  Invariants:

      1. the supervisor promotes the standby (``router_takeovers``
         moves) and respawns the casualty as the new standby, ports
         stable;
      2. ZERO user-visible stream errors — the kill costs each live
         stream one client reconnect, absorbed inside the resume
         retry budget;
      3. every stream's tokens are identical to the fault-free
         reference with gap-free, duplicate-free seqs (the promoted
         router's journal-recovered offset maps serve even
         handoff-marked resumes);
      4. journal recovery is observable: the new active's
         ``recovered_generations`` counter is nonzero and its
         ``tpu_router_journal_records_total`` family is live.
    """
    import http.client
    import json as _json
    import signal

    import tritonclient.http as httpclient

    from tpuserver.fleet import FleetSupervisor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stub_path = os.path.join(repo, "tests", "fleet_stub.py")
    command = [sys.executable, stub_path, "--port", "{port}",
               "--scope", "{scope}"]
    router_command = [
        sys.executable, os.path.join(repo, "tools", "router.py"),
        "--backends", "{backends}", "--port", "{port}",
        "--journal", "{journal}", "--probe-interval", "0.1",
    ]
    supervisor = FleetSupervisor(
        command, replicas=2, min_replicas=2, max_replicas=2,
        probe_interval_s=0.1, probe_timeout_s=2.0,
        start_timeout_s=60.0, drain_grace_s=5.0,
        max_restarts=cycles + 4, restart_window_s=3600.0,
        restart_backoff_s=0.05, scope_prefix="rk-stub-",
        router_command=router_command, router_standby=True,
        env={"PYTHONPATH": os.path.join(repo, "src", "python")},
    ).start()

    def routers_up(timeout_s=60.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            routers = supervisor.stats().get("routers", [])
            if routers and all(r["state"] == "up" for r in routers):
                return True
            time.sleep(0.1)
        return False

    def active_router_stats():
        url = supervisor.active_router_url()
        host, _, port = url.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=5)
        try:
            conn.request("GET", "/router/stats")
            resp = conn.getresponse()
            if resp.status != 200:
                return {}
            return _json.loads(resp.read())
        except (OSError, ValueError, http.client.HTTPException):
            return {}
        finally:
            conn.close()

    def journal_records_metric():
        url = supervisor.active_router_url()
        host, _, port = url.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=5)
        try:
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            if resp.status != 200:
                return None
            for line in resp.read().decode().splitlines():
                if line.startswith("tpu_router_journal_records_total"):
                    return float(line.split()[-1])
            return None
        except (OSError, http.client.HTTPException):
            return None
        finally:
            conn.close()

    try:
        if not supervisor.wait_ready(timeout_s=60.0):
            fail("router-kill: stub replicas never became ready")
            return
        if not routers_up():
            fail("router-kill: router processes never came up")
            return
        prompt = np.array([5, 7, 9], dtype=np.int32)

        def run_stream(client, urls, cycle, wid, i):
            tokens, seqs = [], []
            try:
                for event in client.generate_stream(
                        "stub",
                        {"PROMPT_IDS": prompt,
                         "MAX_TOKENS": np.array([budget], np.int32)},
                        parameters={"token_delay_ms": 25},
                        fallback_urls=urls[1:], max_reconnects=10):
                    for out in event.get("outputs", []):
                        if out["name"] == "TOKEN":
                            tokens.append(int(out["data"][0]))
                    params = event.get("parameters") or {}
                    if "seq" in params:
                        seqs.append(params["seq"])
            except Exception as e:  # noqa: BLE001 — the invariant
                fail("router-kill cycle {}: user-visible stream error "
                     "(worker {} stream {}: {}: {})".format(
                         cycle, wid, i, type(e).__name__, e))
                return None, None
            return tokens, seqs

        urls = supervisor.router_urls()
        ref_client = httpclient.InferenceServerClient(urls[0])
        reference, _ = run_stream(ref_client, urls, -1, 0, 0)
        ref_client.close()
        if reference is None:
            return
        print("reference tokens: {}; {} SIGKILL-the-active-router "
              "cycles".format(reference, cycles), flush=True)

        for cycle in range(cycles):
            stats_before = supervisor.stats()
            urls = supervisor.router_urls()

            def worker(wid, cycle=cycle, urls=urls):
                client = httpclient.InferenceServerClient(urls[0])
                try:
                    for i in range(soak):
                        tokens, seqs = run_stream(
                            client, urls, cycle, wid, i)
                        if tokens is None:
                            continue
                        chaoslib.check_token_identity(
                            RECORDER, reference, tokens,
                            context="router-kill cycle {}".format(
                                cycle),
                            message="router-kill cycle {}: stream "
                                    "tokens diverged: {} != {}".format(
                                        cycle, tokens, reference))
                        chaoslib.check_seq_continuity(
                            RECORDER, seqs, expected_len=budget,
                            context="router-kill cycle {}".format(
                                cycle),
                            message="router-kill cycle {}: seq gap/"
                                    "duplicate: {}".format(cycle, seqs))
                finally:
                    client.close()

            threads = [
                threading.Thread(target=worker, args=(w,), daemon=True)
                for w in range(3)
            ]
            for t in threads:
                t.start()
            time.sleep(0.3)  # streams mid-generation on the router
            active = [r for r in supervisor.stats().get("routers", [])
                      if r["role"] == "active" and r["state"] == "up"
                      and r["pid"]]
            if not active:
                fail("router-kill cycle {}: no live active router to "
                     "kill".format(cycle))
            else:
                os.kill(active[0]["pid"], signal.SIGKILL)
            for t in threads:
                t.join(timeout=300)
            # recovery bar: takeover (or at minimum a healed restart)
            # observed, both router processes back up
            deadline = time.monotonic() + 60.0
            healed = False
            while time.monotonic() < deadline:
                stats = supervisor.stats()
                if (stats.get("router_takeovers", 0)
                        > stats_before.get("router_takeovers", 0)
                        and routers_up(timeout_s=0.1)):
                    healed = True
                    break
                time.sleep(0.1)
            if not healed:
                fail("router-kill cycle {}: standby takeover never "
                     "completed (stats={})".format(
                         cycle, supervisor.stats()))
            rstats = active_router_stats()
            if not rstats.get("recovered_generations"):
                fail("router-kill cycle {}: promoted router recovered "
                     "zero generations from the journal".format(cycle))
            records = journal_records_metric()
            if not records:
                fail("router-kill cycle {}: "
                     "tpu_router_journal_records_total missing or zero "
                     "on the active router".format(cycle))
            stats = supervisor.stats()
            print("cycle {:2d} takeovers={} router_restarts={} "
                  "recovered={} journal_records={}".format(
                      cycle, stats.get("router_takeovers"),
                      stats.get("router_restarts"),
                      rstats.get("recovered_generations"), records),
                  flush=True)
    finally:
        supervisor.stop()


def multi_router_phase(cycles, soak, budget):
    """``--multi-router``: the horizontal front tier (ISSUE 20).

    A FleetSupervisor owns two stub replicas and a PARTITIONED front
    tier: TWO active routers (partitions 0 and 1, each with its own
    journal subdirectory and the selector SSE relay) plus one warm
    standby tailing every partition.  Each cycle, clients pinned to
    BOTH partitions stream slow generations while partition 0's active
    is SIGKILLed mid-traffic.  Invariants:

      1. ``partition_blast_radius``: partition-1 streams — dialed at
         their owner on a single connection with NO fallback urls —
         ride through the sibling's kill with zero reconnects and
         gap-free seqs;
      2. the standby promotes INTO partition 0 (``router_takeovers``
         and the partition-map epoch both advance) and the killed
         partition's streams resume token-identically inside the
         reconnect budget;
      3. ``journal_single_writer`` holds PER PARTITION throughout;
      4. peer handoff: a stream pinned to partition 1 but dialed at
         partition 0's owner relays through the thin proxy hop
         token-identically (the owner's ``partition.forwarded``
         counter moves).
    """
    import http.client
    import json as _json
    import signal

    import tritonclient.http as httpclient

    from tpuserver.fleet import FleetSupervisor
    from tpuserver.router import FleetRouter

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stub_path = os.path.join(repo, "tests", "fleet_stub.py")
    command = [sys.executable, stub_path, "--port", "{port}",
               "--scope", "{scope}"]
    router_command = [
        sys.executable, os.path.join(repo, "tools", "router.py"),
        "--backends", "{backends}", "--port", "{port}",
        "--journal", "{journal}", "--probe-interval", "0.1",
    ]
    supervisor = FleetSupervisor(
        command, replicas=2, min_replicas=2, max_replicas=2,
        probe_interval_s=0.1, probe_timeout_s=2.0,
        start_timeout_s=60.0, drain_grace_s=5.0,
        max_restarts=cycles + 4, restart_window_s=3600.0,
        restart_backoff_s=0.05, scope_prefix="mr-stub-",
        router_command=router_command, router_standby=True,
        active_routers=2,
        env={"PYTHONPATH": os.path.join(repo, "src", "python")},
    ).start()

    def routers_up(timeout_s=60.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            routers = supervisor.stats().get("routers", [])
            if routers and all(r["state"] == "up" for r in routers):
                return True
            time.sleep(0.1)
        return False

    def router_stats(url):
        host, _, port = url.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=5)
        try:
            conn.request("GET", "/router/stats")
            resp = conn.getresponse()
            if resp.status != 200:
                return {}
            return _json.loads(resp.read())
        except (OSError, ValueError, http.client.HTTPException):
            return {}
        finally:
            conn.close()

    def pin_gid(part, tag):
        """A generation id that hashes into ``part`` (brute-forced —
        the partition function is pure, so the draw is deterministic
        per tag)."""
        n = 0
        while True:
            gid = "mr-{}-{}".format(tag, n)
            if FleetRouter.partition_of(gid, 2) == part:
                return gid
            n += 1

    prompt = np.array([5, 7, 9], dtype=np.int32)

    def run_stream(client, gid, urls, reconnects, cycle, what,
                   max_reconnects=10):
        """One pinned stream; returns (tokens, seqs) or (None, None)
        on a user-visible error (recorded).  ``reconnects`` is a
        per-stream observation list the blast-radius check reads."""
        tokens, seqs = [], []
        count = [0]

        def on_reconnect(attempt, dropped):
            count[0] += 1

        try:
            for event in client.generate_stream(
                    "stub",
                    {"PROMPT_IDS": prompt,
                     "MAX_TOKENS": np.array([budget], np.int32)},
                    parameters={"token_delay_ms": 25,
                                "generation_id": gid},
                    fallback_urls=urls, max_reconnects=max_reconnects,
                    on_reconnect=on_reconnect):
                for out in event.get("outputs", []):
                    if out["name"] == "TOKEN":
                        tokens.append(int(out["data"][0]))
                params = event.get("parameters") or {}
                if "seq" in params:
                    seqs.append(params["seq"])
        except Exception as e:  # noqa: BLE001 — the invariant
            fail("multi-router cycle {}: user-visible stream error "
                 "({}: {}: {})".format(cycle, what, type(e).__name__, e))
            return None, None
        finally:
            reconnects.append(count[0])
        return tokens, seqs

    try:
        if not supervisor.wait_ready(timeout_s=60.0):
            fail("multi-router: stub replicas never became ready")
            return
        if not routers_up():
            fail("multi-router: router processes never came up")
            return

        def owner_urls():
            pmap = supervisor.stats().get("partition_map") or []
            if len(pmap) != 2 or not all(pmap):
                fail("multi-router: partition map incomplete: "
                     "{}".format(pmap))
                return None
            return pmap

        pmap = owner_urls()
        if pmap is None:
            return
        scratch = []
        ref_client = httpclient.InferenceServerClient(pmap[0])
        reference, _ = run_stream(
            ref_client, pin_gid(0, "ref"), [pmap[1]], scratch, -1,
            "reference")
        ref_client.close()
        if reference is None:
            return
        print("reference tokens: {}; {} partitioned-tier SIGKILL "
              "cycles".format(reference, cycles), flush=True)

        for cycle in range(cycles):
            stats_before = supervisor.stats()
            pmap = owner_urls()
            if pmap is None:
                return
            all_urls = supervisor.router_urls()
            epoch_before = (router_stats(pmap[1]) or {}).get("epoch", 0)

            # (4) peer handoff, fault-free: pinned to partition 1,
            # dialed at partition 0's owner — the thin proxy hop
            fwd_before = (router_stats(pmap[0]).get("partition") or
                          {}).get("forwarded", 0)
            hop_client = httpclient.InferenceServerClient(pmap[0])
            hop_scratch = []
            tokens, seqs = run_stream(
                hop_client,
                pin_gid(1, "hop-c{}".format(cycle)),
                [u for u in all_urls if u != pmap[0]],
                hop_scratch, cycle, "peer-hop")
            hop_client.close()
            if tokens is not None:
                chaoslib.check_token_identity(
                    RECORDER, reference, tokens,
                    context="multi-router cycle {}".format(cycle),
                    message="multi-router cycle {}: peer-forwarded "
                            "stream tokens diverged: {} != {}".format(
                                cycle, tokens, reference))
                chaoslib.check_seq_continuity(
                    RECORDER, seqs, expected_len=budget,
                    context="multi-router cycle {}".format(cycle))
            fwd_after = (router_stats(pmap[0]).get("partition") or
                         {}).get("forwarded", 0)
            if not fwd_after > fwd_before:
                fail("multi-router cycle {}: partition.forwarded never "
                     "moved across a peer-forwarded stream ({} -> {})"
                     .format(cycle, fwd_before, fwd_after))

            # main traffic: victim-partition streams carry the full
            # fallback rotation; survivor streams get NO fallbacks —
            # one unbroken connection or a recorded violation
            survivor_obs = []
            victim_results = []
            survivor_lock = threading.Lock()

            def victim_worker(wid, cycle=cycle, urls=all_urls):
                client = httpclient.InferenceServerClient(pmap[0])
                try:
                    for i in range(soak):
                        rec = []
                        tokens, seqs = run_stream(
                            client,
                            pin_gid(0, "v-c{}-w{}-s{}".format(
                                cycle, wid, i)),
                            [u for u in urls if u != pmap[0]],
                            rec, cycle, "victim w{} s{}".format(wid, i))
                        if tokens is None:
                            continue
                        with survivor_lock:
                            victim_results.append((tokens, seqs))
                finally:
                    client.close()

            def survivor_worker(wid, cycle=cycle):
                client = httpclient.InferenceServerClient(pmap[1])
                try:
                    for i in range(soak):
                        rec = []
                        tokens, seqs = run_stream(
                            client,
                            pin_gid(1, "s-c{}-w{}-s{}".format(
                                cycle, wid, i)),
                            [], rec, cycle,
                            "survivor w{} s{}".format(wid, i),
                            max_reconnects=0)
                        if tokens is None:
                            continue
                        with survivor_lock:
                            survivor_obs.append({
                                "partition": 1,
                                "reconnects": rec[0],
                                "seqs": seqs,
                            })
                            victim_results.append((tokens, None))
                finally:
                    client.close()

            threads = ([threading.Thread(target=victim_worker,
                                         args=(w,), daemon=True)
                        for w in range(2)]
                       + [threading.Thread(target=survivor_worker,
                                           args=(w,), daemon=True)
                          for w in range(2)])
            for t in threads:
                t.start()
            time.sleep(0.3)  # streams mid-generation on both actives
            victims = [r for r in supervisor.stats().get("routers", [])
                       if r.get("partition") == 0
                       and r["state"] == "up" and r["pid"]]
            if not victims:
                fail("multi-router cycle {}: no live partition-0 "
                     "active to kill".format(cycle))
            else:
                os.kill(victims[0]["pid"], signal.SIGKILL)
            for t in threads:
                t.join(timeout=300)

            for tokens, seqs in victim_results:
                chaoslib.check_token_identity(
                    RECORDER, reference, tokens,
                    context="multi-router cycle {}".format(cycle),
                    message="multi-router cycle {}: stream tokens "
                            "diverged: {} != {}".format(
                                cycle, tokens, reference))
                if seqs is not None:
                    chaoslib.check_seq_continuity(
                        RECORDER, seqs, expected_len=budget,
                        context="multi-router cycle {}".format(cycle))
            # (1) the blast radius stayed partition-sized
            chaoslib.check_partition_blast_radius(
                RECORDER, survivor_obs,
                context="multi-router cycle {}".format(cycle))
            if len(survivor_obs) < 2 * soak:
                fail("multi-router cycle {}: only {}/{} survivor "
                     "streams completed".format(
                         cycle, len(survivor_obs), 2 * soak))

            # (2) recovery bar: takeover INTO partition 0 observed,
            # every router process back up, the map rebound under a
            # newer epoch
            deadline = time.monotonic() + 60.0
            healed = False
            while time.monotonic() < deadline:
                stats = supervisor.stats()
                if (stats.get("router_takeovers", 0)
                        > stats_before.get("router_takeovers", 0)
                        and routers_up(timeout_s=0.1)):
                    healed = True
                    break
                time.sleep(0.1)
            if not healed:
                fail("multi-router cycle {}: takeover into the killed "
                     "partition never completed (stats={})".format(
                         cycle, supervisor.stats()))
                return
            pmap = owner_urls()
            if pmap is None:
                return
            epoch_after = (router_stats(pmap[1]) or {}).get("epoch", 0)
            if not epoch_after > epoch_before:
                fail("multi-router cycle {}: partition-map epoch never "
                     "advanced across the takeover ({} -> {})".format(
                         cycle, epoch_before, epoch_after))
            # (3) one journal writer per partition, throughout
            stats = supervisor.stats()
            chaoslib.check_journal_single_writer(
                RECORDER, stats.get("routers", []),
                context="multi-router cycle {}".format(cycle))
            rstats = router_stats(pmap[0])
            if not rstats.get("recovered_generations"):
                fail("multi-router cycle {}: the promoted partition-0 "
                     "owner recovered zero generations from its "
                     "journal".format(cycle))
            print("cycle {:2d} takeovers={} epoch={} survivors={} "
                  "recovered={}".format(
                      cycle, stats.get("router_takeovers"),
                      epoch_after, len(survivor_obs),
                      rstats.get("recovered_generations")), flush=True)
    finally:
        supervisor.stop()


def disagg_phase(cycles, soak, budget):
    """``--disagg``: disaggregated prefill/decode soak (ISSUE 16).

    A FleetSupervisor owns a ROLE fleet of stdlib stub replicas — one
    ``--role prefill``, one ``--role decode`` — fronted by its
    in-process FleetRouter, whose PhaseSplitOrchestrator splits every
    admission: prefill leg on the prefill replica, one-shot KV-export
    descriptor claim, decode leg (handoff body + ``kv_attach``) on the
    decode replica.  Each cycle, workers stream slowed generations
    (every stream is mid-handoff for most of its life) while the
    PREFILL replica is SIGKILLed.  Invariants:

      1. ZERO user-visible stream errors — a split orphaned by the
         kill (prefill leg dead, descriptor unreachable, release lost)
         degrades to the fused path inside the router, invisibly;
      2. every stream's tokens identical to the fault-free reference
         with gap-free, duplicate-free seqs — across the prefill-leg
         -> decode-leg seam AND across every fallback flavor;
      3. the supervisor heals the prefill pool back to target WITH the
         role (``phase_replicas_up`` restored, membership back to
         full), never by stealing from the decode pool;
      4. the healed replica rejoins the split plane: the router's
         ``splits`` counter resumes moving after recovery, and the
         disagg counters never move backwards.
    """
    import signal

    import tritonclient.http as httpclient

    from tpuserver.fleet import FleetSupervisor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stub_path = os.path.join(repo, "tests", "fleet_stub.py")
    command = [sys.executable, stub_path, "--port", "{port}",
               "--scope", "{scope}"]
    # min == max pins both role pools at their targets: this soak is
    # about HEALING a killed prefill replica back into its pool, not
    # elastic scaling
    supervisor = FleetSupervisor(
        command, prefill_replicas=1, decode_replicas=1,
        min_replicas=1, max_replicas=1,
        probe_interval_s=0.1, probe_timeout_s=2.0,
        start_timeout_s=60.0, drain_grace_s=5.0,
        max_restarts=cycles + 4, restart_window_s=3600.0,
        restart_backoff_s=0.05, scope_prefix="disagg-stub-",
        router_kwargs={"probe_interval_s": 0.05},
        env={"PYTHONPATH": os.path.join(repo, "src", "python")},
    ).start()
    router = supervisor.router
    prompt = np.array([5, 7, 9, 2, 4], dtype=np.int32)

    def stream_once(client, cycle, wid, i):
        tokens, seqs = [], []
        try:
            for event in client.generate_stream(
                    "stub",
                    {"PROMPT_IDS": prompt,
                     "MAX_TOKENS": np.array([budget], np.int32)},
                    parameters={"token_delay_ms": 25}):
                for out in event.get("outputs", []):
                    if out["name"] == "TOKEN":
                        tokens.append(int(out["data"][0]))
                params = event.get("parameters") or {}
                if "seq" in params:
                    seqs.append(params["seq"])
        except Exception as e:  # noqa: BLE001 — the invariant
            fail("disagg cycle {}: user-visible stream error "
                 "(worker {} stream {}: {}: {})".format(
                     cycle, wid, i, type(e).__name__, e))
            return None, None
        return tokens, seqs

    def prefill_handle():
        rows = [r for r in supervisor.stats()["replicas"]
                if r.get("role") == "prefill"]
        return rows[0] if rows else None

    def disagg_stats():
        return router.stats()["disagg"]

    def fleet_recovered(restarts_before, timeout_s=60.0):
        return chaoslib.wait_fleet_converged(
            supervisor.stats, membership_fn=router.membership,
            restarts_above=restarts_before,
            phase_up={"prefill": 1, "decode": 1}, members=2,
            timeout_s=timeout_s)

    def splits_resume(splits_before, client, cycle, timeout_s=30.0):
        """The healed prefill replica must REJOIN the split plane:
        drive streams until the router's splits counter moves past the
        post-kill value (the prober re-admitting the respawn is part
        of the recovery bar)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            tokens, _ = stream_once(client, cycle, "probe", 0)
            if tokens is not None and not chaoslib.check_token_identity(
                    RECORDER, reference, tokens,
                    context="disagg cycle {}".format(cycle),
                    message="disagg cycle {}: post-heal tokens "
                            "diverged: {} != {}".format(
                                cycle, tokens, reference)):
                return False
            if disagg_stats()["splits"] > splits_before:
                return True
        return False

    try:
        if not supervisor.wait_ready(timeout_s=60.0):
            fail("disagg: role replicas never became ready")
            return
        client = httpclient.InferenceServerClient(router.url)
        reference, ref_seqs = stream_once(client, -1, 0, 0)
        if reference is None:
            client.close()
            return
        if ref_seqs != list(range(budget)):
            fail("disagg: reference stream seqs not gap-free: "
                 "{}".format(ref_seqs))
        if disagg_stats()["splits"] < 1:
            fail("disagg: the reference stream did not take the "
                 "phase-split path (stats={})".format(disagg_stats()))
        print("reference tokens: {}; {} SIGKILL-the-prefill-replica "
              "cycles".format(reference, cycles), flush=True)

        for cycle in range(cycles):
            restarts_before = supervisor.stats()["replica_restarts"]
            before = disagg_stats()

            def worker(wid, cycle=cycle):
                wclient = httpclient.InferenceServerClient(router.url)
                try:
                    for i in range(soak):
                        tokens, seqs = stream_once(
                            wclient, cycle, wid, i)
                        if tokens is None:
                            continue
                        chaoslib.check_token_identity(
                            RECORDER, reference, tokens,
                            context="disagg cycle {}".format(cycle),
                            message="disagg cycle {}: stream tokens "
                                    "diverged: {} != {}".format(
                                        cycle, tokens, reference))
                        chaoslib.check_seq_continuity(
                            RECORDER, seqs, expected_len=budget,
                            context="disagg cycle {}".format(cycle),
                            message="disagg cycle {}: seq gap/"
                                    "duplicate: {}".format(cycle, seqs))
                finally:
                    wclient.close()

            threads = [
                threading.Thread(target=worker, args=(w,), daemon=True)
                for w in range(3)
            ]
            for t in threads:
                t.start()
            # 25ms token cadence x `budget` tokens: by now every
            # worker's stream is mid-handoff (prefill leg relayed,
            # decode leg streaming) or about to re-admit one
            time.sleep(0.3)
            victim = prefill_handle()
            if victim is None or victim["state"] != "up" \
                    or not victim["pid"]:
                fail("disagg cycle {}: no live prefill replica to "
                     "kill".format(cycle))
            else:
                os.kill(victim["pid"], signal.SIGKILL)
            for t in threads:
                t.join(timeout=300)
            if not fleet_recovered(restarts_before):
                fail("disagg cycle {}: prefill pool never healed back "
                     "to target with its role (stats={})".format(
                         cycle, supervisor.stats()))
            healed = prefill_handle()
            if healed is None or healed.get("role") != "prefill":
                fail("disagg cycle {}: healed replica lost its role: "
                     "{}".format(cycle, healed))
            after = disagg_stats()
            chaoslib.check_counters_monotonic(
                RECORDER, before, after,
                ("splits", "transfers", "transfer_bytes"),
                context="disagg cycle {}".format(cycle),
                message_fmt=lambda key, prev, now, cycle=cycle:
                    "disagg cycle {}: counter {} moved backwards "
                    "{} -> {}".format(cycle, key, prev, now))
            if not splits_resume(after["splits"], client, cycle):
                fail("disagg cycle {}: healed prefill replica never "
                     "rejoined the split plane (stats={})".format(
                         cycle, disagg_stats()))
            stats = disagg_stats()
            print("cycle {:2d} splits {} -> {} fallbacks={} "
                  "restarts={}".format(
                      cycle, before["splits"], stats["splits"],
                      stats["fallbacks"],
                      supervisor.stats()["replica_restarts"]),
                  flush=True)
        client.close()
    finally:
        supervisor.stop()


def supervisor_phase(cycles, soak, budget):
    """``--supervisor``: supervisor crash durability soak (ISSUE 18).

    Unlike every other phase, the supervisor here is a REAL
    ``tools/fleet.py`` PROCESS — crash durability is about the
    supervisor process dying, so an in-process FleetSupervisor would
    be cheating.  It runs stub replicas behind a supervised router
    process, journaling fleet state to ``--manifest`` and stamping
    liveness + adoption counters to ``--heartbeat-file``.  Each cycle,
    workers stream slowed generations through the router process while
    the SUPERVISOR ITSELF is SIGKILLed mid-traffic; the streams keep
    flowing UNSUPERVISED (router and replicas are their own
    processes), then a successor supervisor boots against the same
    manifest under live traffic.  Invariants:

      1. ZERO user-visible stream errors — while headless AND across
         the successor's adoption;
      2. the successor ADOPTS the survivors: the heartbeat
         ``adoptions`` counter advances by at least the replica count,
         and every replica keeps its pid AND its restart count — no
         double-spawn, no budget burn for a crash that never happened;
      3. port-collision probe: while headless, each replica's port
         still serves ``/v2/health/stats`` from the SAME pid the last
         heartbeat reported (no zombie twin fighting for the socket);
      4. the kernel released the manifest flock with the SIGKILL: the
         successor acquires it WITHOUT ``--takeover``.
    """
    import http.client
    import json as _json
    import shutil
    import signal
    import socket
    import subprocess
    import tempfile

    import tritonclient.http as httpclient

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    workdir = tempfile.mkdtemp(prefix="chaos-supervisor-")
    manifest_dir = os.path.join(workdir, "manifest")
    heartbeat = os.path.join(workdir, "heartbeat.json")

    # pin the router port up front: the router PROCESS outlives every
    # supervisor death, so clients keep one stable address all soak
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        router_port = probe.getsockname()[1]
    router_url = "127.0.0.1:{}".format(router_port)

    # --stop-fleet pins the FINAL SIGTERM to full teardown (this soak
    # proves adoption via SIGKILL, which never reaches a handler; the
    # SIGTERM-handover split is pinned by tests/test_fleet_ha.py)
    argv = [
        sys.executable, os.path.join(repo, "tools", "fleet.py"),
        "--stub", "--replicas", "2", "--min-replicas", "2",
        "--max-replicas", "2", "--router-processes",
        "--router-port", str(router_port),
        "--manifest", manifest_dir, "--heartbeat-file", heartbeat,
        "--probe-interval", "0.1",
        "--max-restarts", str(cycles + 4),
        "--restart-window", "3600", "--stop-fleet",
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src", "python")
    generation = [0]

    def spawn_supervisor():
        generation[0] += 1
        log = open(os.path.join(
            workdir, "supervisor-{}.log".format(generation[0])), "wb")
        try:
            return subprocess.Popen(argv, env=env, stdout=log,
                                    stderr=subprocess.STDOUT)
        finally:
            log.close()

    def supervisor_log_tail():
        path = os.path.join(
            workdir, "supervisor-{}.log".format(generation[0]))
        try:
            with open(path, "rb") as fh:
                return fh.read().decode(errors="replace")[-2000:]
        except OSError:
            return "<no log>"

    def read_heartbeat():
        try:
            with open(heartbeat) as fh:
                return _json.load(fh)
        except (OSError, ValueError):
            return None

    def wait_heartbeat(predicate, timeout_s=60.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            beat = read_heartbeat()
            if beat is not None and predicate(beat):
                return beat
            time.sleep(0.1)
        return None

    def replica_health(url):
        host, _, port = url.rpartition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=5)
        try:
            conn.request("GET", "/v2/health/stats")
            resp = conn.getresponse()
            if resp.status != 200:
                return None
            return _json.loads(resp.read())
        except (OSError, ValueError, http.client.HTTPException):
            return None
        finally:
            conn.close()

    prompt = np.array([5, 7, 9], dtype=np.int32)

    def run_stream(client, cycle, wid, i):
        tokens, seqs = [], []
        try:
            for event in client.generate_stream(
                    "stub",
                    {"PROMPT_IDS": prompt,
                     "MAX_TOKENS": np.array([budget], np.int32)},
                    parameters={"token_delay_ms": 25},
                    max_reconnects=10):
                for out in event.get("outputs", []):
                    if out["name"] == "TOKEN":
                        tokens.append(int(out["data"][0]))
                params = event.get("parameters") or {}
                if "seq" in params:
                    seqs.append(params["seq"])
        except Exception as e:  # noqa: BLE001 — the invariant
            fail("supervisor cycle {}: user-visible stream error "
                 "(worker {} stream {}: {}: {})".format(
                     cycle, wid, i, type(e).__name__, e))
            return None, None
        return tokens, seqs

    sup = spawn_supervisor()
    try:
        beat = wait_heartbeat(
            lambda b: b.get("replicas") and b.get("routers")
            and all(r["state"] == "up" for r in b["replicas"])
            and all(r["state"] == "up" for r in b["routers"]))
        if beat is None:
            fail("supervisor: fleet never became ready (heartbeat={} "
                 "log tail: {})".format(
                     read_heartbeat(), supervisor_log_tail()))
            return

        ref_client = httpclient.InferenceServerClient(router_url)
        reference, _ = run_stream(ref_client, -1, 0, 0)
        ref_client.close()
        if reference is None:
            return
        print("reference tokens: {}; {} SIGKILL-the-SUPERVISOR "
              "cycles".format(reference, cycles), flush=True)

        for cycle in range(cycles):
            before = read_heartbeat()
            if not before or not before.get("replicas"):
                fail("supervisor cycle {}: no heartbeat before the "
                     "kill".format(cycle))
                return

            def worker(wid, cycle=cycle):
                client = httpclient.InferenceServerClient(router_url)
                try:
                    for i in range(soak):
                        tokens, seqs = run_stream(client, cycle, wid, i)
                        if tokens is None:
                            continue
                        chaoslib.check_token_identity(
                            RECORDER, reference, tokens,
                            context="supervisor cycle {}".format(cycle),
                            message="supervisor cycle {}: stream "
                                    "tokens diverged: {} != {}".format(
                                        cycle, tokens, reference))
                        chaoslib.check_seq_continuity(
                            RECORDER, seqs, expected_len=budget,
                            context="supervisor cycle {}".format(cycle),
                            message="supervisor cycle {}: seq gap/"
                                    "duplicate: {}".format(cycle, seqs))
                finally:
                    client.close()

            threads = [
                threading.Thread(target=worker, args=(w,), daemon=True)
                for w in range(3)
            ]
            for t in threads:
                t.start()
            time.sleep(0.3)  # streams mid-generation on the router
            os.kill(sup.pid, signal.SIGKILL)
            sup.wait(timeout=30)
            # the fleet is now HEADLESS: keep streaming through it for
            # a beat before anyone could possibly re-supervise it
            time.sleep(0.4)
            for row in before["replicas"]:
                snap = replica_health(row["url"])
                if snap is None:
                    fail("supervisor cycle {}: replica {} ({}) stopped "
                         "serving while unsupervised".format(
                             cycle, row["index"], row["url"]))
                elif snap.get("pid") != row["pid"]:
                    fail("supervisor cycle {}: replica {} port {} "
                         "served by pid {} != heartbeat pid {} — "
                         "something double-spawned it".format(
                             cycle, row["index"], row["url"],
                             snap.get("pid"), row["pid"]))
            # successor under LIVE traffic; the kernel released the
            # flock with the SIGKILL, so no --takeover needed
            sup = spawn_supervisor()
            new_pid = sup.pid
            for t in threads:
                t.join(timeout=300)
            beat = wait_heartbeat(
                lambda b: b.get("pid") == new_pid and b.get("replicas")
                and all(r["state"] == "up" for r in b["replicas"]))
            if beat is None:
                fail("supervisor cycle {}: successor never stamped a "
                     "healthy heartbeat (heartbeat={} log tail: "
                     "{})".format(cycle, read_heartbeat(),
                                  supervisor_log_tail()))
                return
            chaoslib.check_supervisor_adoption(
                RECORDER,
                {r["index"]: r for r in before["replicas"]},
                {r["index"] for r in before["replicas"]},
                {"adoptions": beat["adoptions"] - before["adoptions"],
                 "replicas": beat["replicas"]},
                context="supervisor cycle {}".format(cycle))
            print("cycle {:2d} adoptions {} -> {} replica pids {} "
                  "restarts={}".format(
                      cycle, before["adoptions"], beat["adoptions"],
                      [r["pid"] for r in beat["replicas"]],
                      beat["replica_restarts"]), flush=True)
    finally:
        if sup.poll() is None:
            sup.terminate()  # --stop-fleet: SIGTERM = full teardown
            try:
                sup.wait(timeout=60)
            except subprocess.TimeoutExpired:
                sup.kill()
                sup.wait(timeout=10)
        # belt and braces: if a cycle failed while headless, reap
        # whatever the last heartbeat still names
        beat = read_heartbeat()
        for row in ((beat or {}).get("replicas", [])
                    + (beat or {}).get("routers", [])):
            if row.get("pid"):
                try:
                    os.kill(row["pid"], signal.SIGKILL)
                except OSError:
                    pass
        shutil.rmtree(workdir, ignore_errors=True)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--rounds", type=int, default=8,
                        help="chaos rounds (default 8: two full cycles)")
    parser.add_argument("--slots", type=int, default=2,
                        help="scheduler slots (default 2)")
    parser.add_argument("--budget", type=int, default=6,
                        help="tokens per generation (default 6)")
    parser.add_argument("--pool", action="store_true",
                        help="soak the multi-replica pool layer instead "
                             "(SIGTERM-drain one of two replicas on a "
                             "cycle)")
    parser.add_argument("--router", action="store_true",
                        help="soak the fleet-router tier instead: plain "
                             "clients stream through a FleetRouter while "
                             "one replica SIGTERM-drains/revives and live "
                             "streams are severed mid-generation")
    parser.add_argument("--fleet", action="store_true",
                        help="soak the supervised fleet tier instead: "
                             "real replica processes under a "
                             "FleetSupervisor, one SIGKILLed at random "
                             "mid-traffic every cycle")
    parser.add_argument("--kill-loop", action="store_true",
                        help="soak the supervised-restart layer instead: "
                             "kill the decode loop mid-traffic every "
                             "round, assert auto-restart with zero lost "
                             "or corrupted streams")
    parser.add_argument("--router-kill", action="store_true",
                        help="soak router HA instead: a supervised "
                             "stub fleet with active + standby router "
                             "processes sharing one crash journal; "
                             "the ACTIVE router is SIGKILLed "
                             "mid-traffic every cycle — asserts "
                             "standby takeover, zero user-visible "
                             "errors, token-identical gap-free "
                             "streams, and journal recovery counters "
                             "moving")
    parser.add_argument("--multi-router", action="store_true",
                        dest="multi_router",
                        help="soak the horizontal front tier instead: "
                             "a supervised stub fleet with TWO active "
                             "partitioned routers + a warm standby; "
                             "partition 0's active is SIGKILLed "
                             "mid-traffic every cycle — asserts the "
                             "sibling partition rides through with "
                             "zero reconnects (partition blast "
                             "radius), standby promotion INTO the "
                             "killed partition, epoch advance, peer "
                             "handoff, and per-partition journal "
                             "single-writer discipline")
    parser.add_argument("--disagg", action="store_true",
                        help="soak disaggregated prefill/decode "
                             "serving instead: a role stub fleet "
                             "(one prefill + one decode replica) "
                             "with the PREFILL replica SIGKILLed "
                             "mid-handoff every cycle — asserts zero "
                             "user-visible errors, token-identical "
                             "gap-free streams, role-preserving "
                             "healing, and the healed replica "
                             "rejoining the split plane")
    parser.add_argument("--supervisor", action="store_true",
                        help="soak supervisor crash durability "
                             "instead: a real tools/fleet.py process "
                             "(stub replicas, router process, manifest "
                             "+ heartbeat) SIGKILLed mid-traffic every "
                             "cycle — asserts error-free unsupervised "
                             "streaming, live-child adoption by the "
                             "successor (pids and restart budgets "
                             "unchanged), and no double-spawn")
    parser.add_argument("--gray", action="store_true",
                        help="soak the gray-failure ejection layer "
                             "instead: a stub-fleet router with one "
                             "replica turned slow-but-alive mid-soak; "
                             "asserts soft-ejection, p99 recovery "
                             "within 2x of healthy, zero user-visible "
                             "errors, and re-admission on recovery")
    parser.add_argument("--shm", action="store_true",
                        help="soak the shm data plane instead: token-"
                             "ring streams + park-export/attach-resume "
                             "under decode-loop kills; asserts token-"
                             "identical rings, consistent "
                             "xla_shm_status, zero leaked regions")
    parser.add_argument("--cycles", type=int, default=4,
                        help="pool mode: drain/revive cycles (default 4)")
    parser.add_argument("--soak", type=int, default=None,
                        help="requests per worker per cycle (default: "
                             "40 in pool mode, 6 full generations in "
                             "router mode)")
    args = parser.parse_args()

    if args.router_kill:
        t0 = time.monotonic()
        # stub replicas + slowed token cadence: cycles are cheap, so
        # the default soak covers several full generations per worker
        router_kill_phase(args.cycles,
                          args.soak if args.soak is not None else 3,
                          args.budget * 2)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nrouter-kill chaos smoke FAILED: {} violation(s) "
                  "in {:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nrouter-kill chaos smoke OK: {} active-router SIGKILL "
              "cycles, {:.1f}s, standby takeover + journal recovery, "
              "zero user-visible errors, zero lost or duplicated "
              "tokens".format(args.cycles, elapsed))
        return 0

    if args.multi_router:
        t0 = time.monotonic()
        # stub replicas + slowed token cadence, like --router-kill:
        # cycles are cheap, and each one proves the blast radius of an
        # active's death stays partition-sized
        multi_router_phase(args.cycles,
                           args.soak if args.soak is not None else 2,
                           args.budget * 2)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nmulti-router chaos smoke FAILED: {} violation(s) "
                  "in {:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nmulti-router chaos smoke OK: {} partitioned-tier "
              "SIGKILL cycles, {:.1f}s, surviving partition "
              "uninterrupted (zero reconnects), standby promoted into "
              "the killed partition, epoch advanced, peer handoff "
              "token-identical".format(args.cycles, elapsed))
        return 0

    if args.disagg:
        t0 = time.monotonic()
        # stub replicas + slowed token cadence, like --router-kill:
        # cycles are cheap and every stream spends most of its life
        # mid-handoff
        disagg_phase(args.cycles,
                     args.soak if args.soak is not None else 3,
                     args.budget * 2)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\ndisagg chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\ndisagg chaos smoke OK: {} prefill-SIGKILL cycles, "
              "{:.1f}s, zero user-visible errors, token-identical "
              "gap-free streams, role-preserving healing, split "
              "plane re-armed every cycle".format(args.cycles, elapsed))
        return 0

    if args.supervisor:
        t0 = time.monotonic()
        # stub replicas + slowed token cadence, like --router-kill:
        # each cycle costs one supervisor-process respawn, and every
        # stream spends most of its life headless on purpose
        supervisor_phase(args.cycles,
                         args.soak if args.soak is not None else 3,
                         args.budget * 2)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nsupervisor chaos smoke FAILED: {} violation(s) "
                  "in {:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nsupervisor chaos smoke OK: {} supervisor-SIGKILL "
              "cycles, {:.1f}s, zero user-visible errors, every "
              "survivor adopted (no double-spawn, no budget "
              "burn)".format(args.cycles, elapsed))
        return 0

    if args.gray:
        t0 = time.monotonic()
        # a wide per-window sample keeps p99 meaningful: one stray
        # scheduling spike on a loaded CI box must not be the 99th
        # percentile of the whole window
        gray_phase(args.cycles,
                   args.soak if args.soak is not None else 160)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\ngray chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\ngray chaos smoke OK: {} gray cycles, {:.1f}s, "
              "soft-ejection + p99 recovery + re-admission, zero "
              "user-visible errors".format(args.cycles, elapsed))
        return 0

    if args.shm:
        t0 = time.monotonic()
        shm_phase(args.rounds, args.slots, args.budget)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nshm chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nshm chaos smoke OK: {} rounds, {:.1f}s, token-"
              "identical rings, consistent xla_shm_status, zero "
              "leaked regions".format(args.rounds, elapsed))
        return 0

    if args.fleet:
        t0 = time.monotonic()
        # fewer, heavier cycles: each costs a replica-process respawn
        # (jax import + scheduler compile on its first admission)
        soak = args.soak if args.soak is not None else 4
        fleet_phase(args.cycles, soak, args.budget)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nfleet chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nfleet chaos smoke OK: {} SIGKILL cycles, {:.1f}s, "
              "zero user-visible errors, zero lost or duplicated "
              "tokens, fleet back at target count every cycle".format(
                  args.cycles, elapsed))
        return 0

    if args.router:
        t0 = time.monotonic()
        # router soak default: fewer, heavier cycles (each cycle runs
        # 4 workers x soak full generations through the router)
        soak = args.soak if args.soak is not None else 6
        router_phase(args.cycles, soak, args.budget)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nrouter chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nrouter chaos smoke OK: {} drain/sever cycles, {:.1f}s, "
              "zero user-visible errors, zero lost or duplicated "
              "tokens".format(args.cycles, elapsed))
        return 0

    if args.pool:
        t0 = time.monotonic()
        pool_phase(args.cycles,
                   args.soak if args.soak is not None else 40)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\npool chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\npool chaos smoke OK: {} SIGTERM-drain cycles, {:.1f}s, "
              "all invariants held".format(args.cycles, elapsed))
        return 0

    if args.kill_loop:
        t0 = time.monotonic()
        kill_loop_phase(args.rounds, args.slots, args.budget)
        elapsed = time.monotonic() - t0
        if _failures:
            print("\nkill-loop chaos smoke FAILED: {} violation(s) in "
                  "{:.1f}s".format(len(_failures), elapsed),
                  file=sys.stderr)
            return 1
        print("\nkill-loop chaos smoke OK: {} loop kills healed, "
              "{:.1f}s, zero lost or corrupted streams".format(
                  args.rounds, elapsed))
        return 0

    model = LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=64, max_slots=args.slots,
        # every step/fetch round of the cycle costs one supervised
        # restart on purpose; the budget must outlast the soak
        max_restarts=args.rounds + 4, restart_window_s=3600.0,
        restart_backoff_s=0.01)
    core = InferenceServer([model])
    print("warming up (compiles the scheduler fns)...")
    reference = [generate(core, p, args.budget) for p in PROMPTS]
    print("reference tokens captured; starting {} chaos rounds".format(
        args.rounds))

    t0 = time.monotonic()
    for rnd in range(args.rounds):
        chaos_round(core, model, reference, args.budget, rnd)
    overload_phase(LlamaGenerateModel)

    # graceful drain at the end: accepted work finishes, then stop
    core.drain(timeout=10.0)
    if core.server_state() != "stopped":
        fail("drain did not stop the server (state={})".format(
            core.server_state()))

    elapsed = time.monotonic() - t0
    if _failures:
        print("\nchaos smoke FAILED: {} violation(s) in {:.1f}s".format(
            len(_failures), elapsed), file=sys.stderr)
        return 1
    print("\nchaos smoke OK: {} rounds + overload phase + drain, "
          "{:.1f}s, all invariants held".format(args.rounds, elapsed))
    return 0


if __name__ == "__main__":
    sys.exit(main())
