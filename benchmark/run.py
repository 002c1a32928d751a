#!/usr/bin/env python3
"""Run ONE cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that takes the chip (no chip, or fewer chips than the cell
asks for: exit non-zero, no result, never the CPU), builds the cell's
server from its configuration file with weights from ``--seed``, warms
the cell's own shapes, ramps its closed-loop callers, measures for
``--seconds``, frees the program, compares what the window produced
with the plain reference, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``compared`` last.

``--trace 0`` reports the cell's end-to-end metrics with the profiler
off; ``--trace 1`` its per-layer metrics, from program counters sampled
over the window and a device trace of a few seconds inside it.

``--dry-run`` is the explicit CPU opt-in for the self-tests: the same
control flow at the configuration's ``dry_run`` sizes with interpreted
kernels; its last line names device ``cpu`` and no device metric.
"""

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src", "python"))

import compare  # noqa: E402
import manifest  # noqa: E402

TRACE_AFTER_S, TRACE_FOR_S = 2.0, 4.0
# A process that compiled freezes once, 0.7-1.2 s with every thread held,
# some seconds after its last long compile (seen 2.4-7.4 s after, cause
# not found: PERF.md).  The ramp goes on until twice the latest seen is
# behind it.  It belongs to the process, not to a traffic mix: one value.
LONG_COMPILE_S, SETTLE_AFTER_COMPILE_S, DRY_RUN_SETTLE_S = 1.0, 15.0, 1.0


class Ctx:
    """What one run knows; handed to kinds, readers and the check."""

    def log(self, msg):
        print("[bench +{:7.1f}s] {}".format(time.perf_counter() - T_START, msg),
              file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU, the configuration's dry_run sizes, "
                         "interpreted kernels; for the self-tests")
    return ap.parse_args(argv)


def take_device(ctx):
    """The chip, or an error: never the CPU unless --dry-run said so."""
    import jax
    import tpuserver
    from tpuserver.ops import flash

    import roofline

    tpuserver.enable_compile_cache()
    # the program keeps compiles under 1 s out of the cache; a run's
    # set-up is hundreds of those (eager ops), so the harness keeps all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if ctx.dry_run:
        flash.set_kernel_mode(interpret=True)
        device = jax.devices()[0]
    else:
        device = tpuserver.require_tpu()
        roofline.chip(device.device_kind)   # unknown kind: error
        if len(jax.devices()) < ctx.cell["chips"]:
            raise RuntimeError("cell {} asks for {} chips, jax has {}".format(
                ctx.cell["name"], ctx.cell["chips"], len(jax.devices())))
    ctx.device = device
    ctx.device_info = {"platform": device.platform, "kind": device.device_kind,
                       "count": len(jax.devices())}
    ctx.log("device {} cache {}".format(
        ctx.device_info, jax.config.jax_compilation_cache_dir))


def build_server(ctx):
    """The configuration's repository behind a real gRPC frontend, every
    model given its weights from the seed."""
    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend

    entries = ctx.config["repository"]
    builders = [importlib.import_module("models." + e["builder"])
                for e in entries]
    models = [b.build(ctx.config, e) for b, e in zip(builders, entries)]
    ctx.models = {e["name"]: m for e, m in zip(entries, models)}
    ctx.builders = {e["name"]: b for e, b in zip(entries, builders)}
    ctx.core = InferenceServer(models)
    ctx.frontend = GrpcFrontend(ctx.core, port=0, max_workers=64).start()
    ctx.url = ctx.frontend.url
    for b, e, m in zip(builders, entries, models):
        t = time.perf_counter()
        b.load(m, ctx.config, e, ctx.seed)
        ctx.log("model {} loaded in {:.1f}s".format(
            e["name"], time.perf_counter() - t))


def free_server(ctx):
    """Stop the frontend and drop every device array of the program, so
    the reference has the chip's memory."""
    ctx.frontend.stop()
    ctx.core.close()
    for m in ctx.models.values():
        m._params = None
    ctx.models = ctx.core = ctx.frontend = None
    gc.collect()


class CompileLog:
    """Every compile or cache read of the process, with its time.  jax
    reports ``backend_compile_duration`` for both, and for a read
    ``cache_retrieval_time_sec`` just before it on the same thread:
    ``compiled`` keeps the ``(time, seconds)`` of those that were no read."""

    def __init__(self):
        import jax.monitoring

        self.events, self.compiled, self._read = [], [], set()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kwargs):
        now, thread = time.perf_counter(), threading.get_ident()
        if name.endswith("cache_retrieval_time_sec"):
            self._read.add(thread)
        elif not name.endswith("backend_compile_duration"):
            return
        elif thread in self._read:
            self._read.discard(thread)
        else:
            self.compiled.append((now, secs))
        self.events.append((now, name, secs))

    def between(self, t0, t1):
        return [e for e in self.events if t0 <= e[0] < t1]

    def settled_at(self, after_s):
        """When a window may open: ``after_s`` past the last long backend
        compile, or now where the process compiled nothing long: a run
        that read everything from the cache waits for nothing."""
        long = [t for t, secs in self.compiled if secs >= LONG_COMPILE_S]
        return max(long) + after_s if long else 0.0


class Sampler(threading.Thread):
    """Reads the program's gauges a few times a second over the window
    (traced runs only)."""

    def __init__(self, ctx, stop):
        super().__init__(daemon=True, name="bench-sampler")
        self.ctx, self.stop_evt, self.samples = ctx, stop, []

    def run(self):
        import counters

        while not self.stop_evt.wait(0.25):
            self.samples.append(counters.gauges(self.ctx))


def measure(ctx, kind):
    """Ramp, window, and (traced) counters and a device trace inside it."""
    import counters
    import trace as trace_mod

    stop = threading.Event()
    ctx.clients = kind.clients(ctx, stop)
    for c in ctx.clients:
        c.start()
    for c in ctx.clients:
        if not c.ramped.wait(600):
            raise RuntimeError("ramp did not finish in 600 s")
    ctx.log("set-up compiled {} programs ({} of {} s or more), read {} from "
            "the cache".format(
                len(ctx.compiles.compiled),
                sum(1 for _, secs in ctx.compiles.compiled
                    if secs >= LONG_COMPILE_S), LONG_COMPILE_S,
                sum(1 for e in ctx.compiles.events
                    if e[1].endswith("cache_retrieval_time_sec"))))
    hold = ctx.compiles.settled_at(
        DRY_RUN_SETTLE_S if ctx.dry_run else SETTLE_AFTER_COMPILE_S
    ) - time.perf_counter()
    if hold > 0:
        ctx.log("ramp goes on {:.1f}s: this process compiled".format(hold))
        time.sleep(hold)
    sampler = None
    if ctx.trace:
        ctx.counters_t0 = counters.snapshot(ctx)
        sampler = Sampler(ctx, stop)
        sampler.start()
    ctx.t0 = time.perf_counter()
    ctx.t1 = ctx.t0 + ctx.seconds
    ctx.setup_s = ctx.t0 - T_START
    ctx.log("window opens: set-up {:.1f}s".format(ctx.setup_s))
    ctx.trace_dir = None
    if ctx.trace and ctx.seconds > TRACE_AFTER_S + 0.5:
        import jax

        time.sleep(TRACE_AFTER_S)
        ctx.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(trace_mod.SYNC_SPAN):
            ctx.trace_t0 = time.perf_counter()
        time.sleep(min(TRACE_FOR_S, max(0.2, ctx.t1 - time.perf_counter() - 0.3)))
        jax.profiler.stop_trace()
    time.sleep(max(0.0, ctx.t1 - time.perf_counter()))
    if ctx.trace:
        ctx.counters_t1 = counters.snapshot(ctx)
        # a traced window ends where its counters were read
        ctx.t1 = max(ctx.t1, time.perf_counter())
    stop.set()
    for c in ctx.clients:
        c.join(120)
        if c.is_alive():
            raise RuntimeError("a client did not stop within 120 s")
    if sampler is not None:
        sampler.join(10)
        ctx.samples = sampler.samples
    ctx.log("window closed")


def per_layer(ctx, kind):
    """The cell's per-layer metrics: each from its own reader; a reader
    with nothing to read returns None and the metric is left out."""
    import devicework
    import trace as trace_mod

    ctx.series = kind.series(ctx)
    ctx.trace_data = None
    if ctx.trace_dir is not None:
        try:
            ctx.trace_data = trace_mod.load(ctx.trace_dir, ctx.trace_t0,
                                            devicework.labels(ctx))
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    out = {}
    for metric in manifest.metrics_of(ctx.manifest, ctx.cell["name"],
                                      "per_layer"):
        spec, read = manifest.reader_of(metric["name"])
        value = read(ctx, spec)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def execute(args, broken=()):
    """One run of one cell.  Returns the result and, for each control (a
    precision below the configuration's) or fault named in ``broken``
    (``readings.py`` asks; a benchmark run never does), the numbers the
    comparison reads with it in the program's place, beside their limits."""
    ctx = Ctx()
    ctx.seed, ctx.seconds, ctx.trace = args.seed, args.seconds, bool(args.trace)
    ctx.dry_run = args.dry_run
    ctx.manifest = manifest.load_manifest()
    ctx.cell = manifest.cell(ctx.manifest, args.workload)

    def sized(data):
        """A data file as it is run: under --dry-run, its ``dry_run``
        keys laid over it."""
        return dict(data, **data.get("dry_run", {})) if ctx.dry_run else data

    ctx.config = sized(manifest.config_of(ctx.manifest, ctx.cell)[1])
    ctx.traffic = sized(manifest.traffic_of(ctx.cell))
    ctx.limits = sized(manifest.load_json(os.path.join(
        HERE, "limits", ctx.cell["name"] + ".json")))
    kind = ctx.kind = importlib.import_module("kinds." + ctx.traffic["kind"])

    take_device(ctx)
    ctx.compiles = CompileLog()
    build_server(ctx)
    try:
        kind.prepare(ctx)
        measure(ctx, kind)
        ctx.compiles_in_window = len(ctx.compiles.between(ctx.t0, ctx.t1))
        memory = ctx.device.memory_stats() or {}
        attempted, failed = kind.attempted_failed(ctx)
        for line in kind.histograms(ctx):
            ctx.log(line)
        if ctx.trace:
            metrics = per_layer(ctx, kind)
        else:
            values = dict(kind.end_to_end(ctx), setup_s=ctx.setup_s)
            metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                       for m in manifest.metrics_of(
                           ctx.manifest, ctx.cell["name"], "end_to_end")
                       if values.get(m["name"]) is not None}
    finally:
        free_server(ctx)

    t = time.perf_counter()
    numbers = kind.check(ctx)
    numbers["failed_requests"] = compare.at_most(failed, 0)
    ctx.log("reference and comparison: {:.1f}s".format(time.perf_counter() - t))
    device = dict(ctx.device_info)
    if not ctx.dry_run:
        device["memory_peak_bytes"] = memory.get("peak_bytes_in_use")
    result = {"correct": compare.verdict(numbers), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if ctx.trace and ctx.trace_data is not None and not ctx.dry_run:
        import trace as trace_mod

        device["busy_s"] = ctx.trace_data.busy_s
        device["window_s"] = ctx.trace_data.window_s
        result["breakdown"] = trace_mod.breakdown(ctx.trace_data)
    # the builder's contract: each number compared beside its limit, under
    # a key of its own that comes last (the driver ignores keys it does
    # not read), and as the last lines of standard error
    result["compared"] = {k: [n["value"], n["limit"]] for k, n in numbers.items()}
    for line in compare.lines(numbers):
        print(line, file=sys.stderr, flush=True)
    return result, {b: (kind.control(ctx, b) if b in kind.CONTROLS
                        else kind.fault(ctx, b)) for b in broken}


def run(argv):
    result, _ = execute(parse(argv))
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None):
    try:
        return run(sys.argv[1:] if argv is None else argv)
    except (RuntimeError, ValueError, ImportError, OSError) as e:
        # no chip, an unknown chip, a broken manifest, a checkout without
        # the program: no result line, a non-zero exit
        print("benchmark/run.py: {}: {}".format(type(e).__name__, e),
              file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
