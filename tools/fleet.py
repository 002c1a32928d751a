#!/usr/bin/env python3
"""Fleet CLI: a supervised, elastically-scaled replica fleet behind one
router address.

    python tools/fleet.py --replicas 2 --min-replicas 1 \
        --max-replicas 4 --router-port 9000 --models llama,simple

Spawns N replica server processes (each a real OS process with its own
port and fault scope), fronts them with a FleetRouter whose membership
the supervisor keeps live, heals replica death (SIGKILL/crash) and
wedges (SIGTERM-drain first) under a bounded restart budget, and
scales the replica count with the fleet's queue pressure
(docs/resilience.md "Fleet supervisor & elastic scaling").

``--manifest DIR`` makes the SUPERVISOR itself crash-durable
(docs/resilience.md "Supervisor crash durability"): fleet state is
journaled to an append-only manifest, and a restarted supervisor
ADOPTS the still-running children instead of respawning a healthy
fleet.  Signal dispositions split with it:

- SIGTERM (manifest mode) = graceful HANDOVER — checkpoint the
  manifest, release the single-writer lock, exit WITHOUT touching the
  children; they keep serving until a successor adopts them.  Pass
  ``--stop-fleet`` to keep SIGTERM as full fleet teardown.
- SIGINT (and SIGTERM without a manifest) = stop the whole fleet,
  drain-first, exactly as before.

The hidden ``--serve-replica`` mode is the replica entry point the
supervisor spawns: one InferenceServer + HttpFrontend on ``--port``
with ``install_sigterm_drain`` installed, exiting once drained.

Every replica is its own JAX process, and a chip belongs to one process
at a time: this is a CPU tier (the replica model is the ``tiny`` llama)
until one process drives the replicas of a host (ROADMAP R6).  The
platform comes from the environment — run it with ``JAX_PLATFORMS=cpu``.
"""

import argparse
import os
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src", "python"))


def build_models(names, slots):
    from tpuserver.models.simple import SimpleModel

    models = []
    if "llama" in names:
        import tpuserver
        from tpuserver.models import llama
        from tpuserver.models.llama_serving import LlamaGenerateModel

        # only a replica that compiles needs the cache (and jax at all)
        tpuserver.enable_compile_cache()
        models.append(LlamaGenerateModel(
            cfg=llama.tiny(vocab=512), max_seq=64, max_slots=slots,
            restart_backoff_s=0.01))
    if "simple" in names:
        models.append(SimpleModel())
    if not models:
        raise SystemExit("--models must name llama and/or simple")
    return models


def serve_replica(args):
    """Child mode: one replica server process.  SIGTERM drains first
    (in-flight generations finish, the prober rotates the replica out)
    and the process exits once the server reaches ``stopped``."""
    from tpuserver.core import InferenceServer, install_sigterm_drain
    from tpuserver.http_frontend import HttpFrontend

    core = InferenceServer(
        build_models(args.models.split(","), args.slots),
        fault_scope=args.scope or None,
        role=args.role or None,
        spawn_nonce=args.spawn_nonce or None)
    frontend = HttpFrontend(core, port=args.port).start()
    install_sigterm_drain(core, drain_timeout=args.drain_timeout)
    print("replica[{}] serving on {} (pid {})".format(
        args.scope or "-", frontend.url, os.getpid()), flush=True)
    try:
        while core.server_state() != "stopped":
            time.sleep(0.1)
    finally:
        frontend.stop()
    print("replica[{}] drained and stopped".format(args.scope or "-"),
          flush=True)
    return 0


def signal_disposition(signum, manifest, stop_fleet):
    """What one shutdown signal means for THIS supervisor process:
    ``"handover"`` (checkpoint + release the manifest lock + leave the
    children serving) or ``"stop"`` (full drain-first fleet teardown).
    SIGTERM in manifest mode defaults to handover — the whole point of
    the manifest is that restarting the supervisor must not restart
    the fleet — unless ``--stop-fleet`` pins the old teardown
    behaviour; SIGINT (and any signal without a manifest) always
    stops."""
    if (signum == signal.SIGTERM and manifest is not None
            and not stop_fleet):
        return "handover"
    return "stop"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--serve-replica", action="store_true",
                    help=argparse.SUPPRESS)  # the spawned child mode
    ap.add_argument("--port", type=int, default=0,
                    help="(child mode) replica listen port")
    ap.add_argument("--scope", default="",
                    help="(child mode) fault-injection scope name")
    ap.add_argument("--role", default="",
                    help="(child mode) phase role the replica "
                         "advertises in /v2/health/stats "
                         "(prefill/decode; empty = fused)")
    ap.add_argument("--spawn-nonce", default="",
                    help="(child mode) spawn identity nonce echoed in "
                         "/v2/health/stats — the supervisor's "
                         "adoption contract after its own restart")
    ap.add_argument("--models", default="llama,simple",
                    help="comma list of replica models (llama, simple)")
    ap.add_argument("--slots", type=int, default=4,
                    help="llama scheduler slots per replica (default 4)")
    ap.add_argument("--drain-timeout", type=float, default=10.0,
                    help="replica SIGTERM drain budget in seconds")
    ap.add_argument("--replicas", type=int, default=2,
                    help="initial replica process count (default 2)")
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="disaggregated serving: dedicated prefill "
                         "replicas (requires --decode-replicas too; "
                         "--replicas then only adds fused capacity)")
    ap.add_argument("--decode-replicas", type=int, default=0,
                    help="disaggregated serving: dedicated decode "
                         "replicas the router attaches exported KV "
                         "onto")
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--router-host", default="127.0.0.1")
    ap.add_argument("--router-port", type=int, default=9000,
                    help="router listen port (0 = pick free)")
    ap.add_argument("--probe-interval", type=float, default=0.5,
                    help="supervisor monitor cadence (default 0.5s)")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="per-replica restart budget inside the window")
    ap.add_argument("--restart-window", type=float, default=60.0)
    ap.add_argument("--scale-high", type=float, default=0.85,
                    help="sustained fleet utilization that scales UP")
    ap.add_argument("--scale-low", type=float, default=0.10,
                    help="sustained fleet utilization that scales DOWN")
    ap.add_argument("--router-processes", action="store_true",
                    help="supervise the router as its own PROCESS "
                         "(tools/router.py with a crash journal) under "
                         "the same drain-first restart budget the "
                         "replicas get, instead of the in-process "
                         "router")
    ap.add_argument("--router-standby", action="store_true",
                    help="with --router-processes: run a warm-standby "
                         "router tailing the same journal; the "
                         "supervisor promotes it on active-router "
                         "death (clients carrying both urls reconnect "
                         "once, streams resume)")
    ap.add_argument("--router-journal", default=None, metavar="DIR",
                    help="journal directory the router processes "
                         "share (default: a supervisor-owned temp "
                         "directory)")
    ap.add_argument("--standby-port", type=int, default=0,
                    help="standby router listen port (0 = pick free)")
    ap.add_argument("--active-routers", type=int, default=1,
                    help="with --router-processes: N simultaneously-"
                         "active routers partitioning the generation-"
                         "id space (each owns a journal subdirectory "
                         "and peer-forwards siblings' requests); an "
                         "active's death promotes the standby INTO "
                         "its partition (default 1 = single active)")
    ap.add_argument("--manifest", default=None, metavar="DIR",
                    help="supervisor crash durability: journal fleet "
                         "state to this manifest directory; a "
                         "restarted supervisor ADOPTS the running "
                         "children instead of respawning them")
    ap.add_argument("--takeover", action="store_true",
                    help="with --manifest: wait (bounded) for the "
                         "incumbent supervisor's lock instead of "
                         "refusing when one is alive")
    ap.add_argument("--heartbeat-file", default=None, metavar="FILE",
                    help="stamp supervisor liveness + adoption "
                         "counters to this file every monitor tick "
                         "(atomic replace)")
    ap.add_argument("--stop-fleet", action="store_true",
                    help="with --manifest: keep SIGTERM as full fleet "
                         "teardown instead of the default graceful "
                         "handover that leaves children serving")
    ap.add_argument("--stub", action="store_true",
                    help=argparse.SUPPRESS)  # tests/fleet_stub.py
    # replicas: chaos/CI harness mode, no model deps
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.serve_replica:
        return serve_replica(args)

    from tpuserver.fleet import FleetSupervisor

    if args.stub:
        # chaos/CI harness replicas: the pure-stdlib stub server keeps
        # supervisor-kill campaigns fast and model-free
        command = [
            sys.executable, os.path.join(REPO, "tests", "fleet_stub.py"),
            "--port", "{port}", "--scope", "{scope}",
        ]
    else:
        command = [
            sys.executable, os.path.abspath(__file__), "--serve-replica",
            "--port", "{port}", "--scope", "{scope}",
            "--models", args.models, "--slots", str(args.slots),
            "--drain-timeout", str(args.drain_timeout),
        ]
    router_command = None
    if (args.router_processes or args.router_standby
            or args.active_routers > 1):
        router_command = [
            sys.executable, os.path.join(REPO, "tools", "router.py"),
            "--backends", "{backends}", "--host", args.router_host,
            "--port", "{port}", "--journal", "{journal}",
        ]
    supervisor = FleetSupervisor(
        command,
        replicas=args.replicas,
        prefill_replicas=args.prefill_replicas,
        decode_replicas=args.decode_replicas,
        min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        probe_interval_s=args.probe_interval,
        max_restarts=args.max_restarts,
        restart_window_s=args.restart_window,
        scale_high=args.scale_high,
        scale_low=args.scale_low,
        router_kwargs={"host": args.router_host, "port": args.router_port},
        router_command=router_command,
        router_standby=args.router_standby,
        router_journal=args.router_journal,
        router_port=args.router_port,
        standby_port=args.standby_port,
        active_routers=args.active_routers,
        env={"PYTHONPATH": os.path.join(REPO, "src", "python")},
        verbose=args.verbose,
        manifest_dir=args.manifest,
        takeover=args.takeover,
        heartbeat_file=args.heartbeat_file,
    ).start()

    stop = threading.Event()
    disposition = {"action": "stop"}

    def _signal(signum, frame):
        disposition["action"] = signal_disposition(
            signum, args.manifest, args.stop_fleet)
        stop.set()

    signal.signal(signal.SIGTERM, _signal)
    signal.signal(signal.SIGINT, _signal)
    print("fleet supervisor: router(s) on {} over {} replica(s) "
          "(min {}, max {}{})".format(
              ", ".join(supervisor.router_urls()), args.replicas,
              args.min_replicas, args.max_replicas,
              ", manifest {}".format(args.manifest)
              if args.manifest else ""), flush=True)
    supervisor.wait_ready(timeout_s=120.0)
    for rep in supervisor.stats()["replicas"]:
        print("  replica {url} [{scope}] pid={pid} state={state}".format(
            **rep), flush=True)
    try:
        # a wait that times out, so a signal the kernel handed to
        # another thread still reaches its handler (tools/router.py)
        while not stop.wait(0.2):
            pass
    finally:
        if disposition["action"] == "handover":
            supervisor.handover()
        else:
            supervisor.stop()
    print("fleet {}".format(
        "handed over (children still serving)"
        if disposition["action"] == "handover" else "stopped"),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
