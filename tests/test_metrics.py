"""The /metrics telemetry plane (docs/observability.md).

Acceptance shape of ISSUE 10: scrape ``GET /metrics`` on a live
replica AND on a router fronting it, parse the Prometheus text format
with a minimal IN-TEST parser (independent of
``tpuserver.metrics.parse_prometheus_text``, so the exposition format
itself is pinned from the outside — HELP/TYPE lines, histogram bucket
monotonicity, ``_sum``/``_count`` consistency), and watch request and
token counters move under traffic.  Plus the hot-path pin: the
registry's scheduler families and ``DecodeScheduler.stats()`` must
agree exactly after a run — one source of truth, no double
accounting — and the router's fleet aggregation must keep monotonic
counters monotonic across replica counter resets and membership
churn.
"""

import http.client
import re

import numpy as np
import pytest

pytestmark = pytest.mark.metrics


# -- the minimal in-test parser ---------------------------------------------

_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def parse_exposition(text):
    """(types, helps, samples): samples is a list of
    ``(name, labels_dict, float_value)``."""
    types, helps, samples = {}, {}, []
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            name, _, kind = line[len("# TYPE "):].partition(" ")
            types[name] = kind.strip()
        elif line.startswith("# HELP "):
            name, _, help_text = line[len("# HELP "):].partition(" ")
            helps[name] = help_text
        elif line and not line.startswith("#"):
            m = _SAMPLE.match(line)
            assert m is not None, "unparseable sample line: " + line
            labels = dict(_LABEL.findall(m.group(2) or ""))
            samples.append((m.group(1), labels, float(m.group(3))))
    return types, helps, samples


def sample_value(samples, name, **labels):
    for sname, slabels, value in samples:
        if sname == name and all(
                slabels.get(k) == v for k, v in labels.items()):
            return value
    return None


def check_histogram(samples, family, **labels):
    """Bucket monotonicity + _sum/_count consistency for one child."""
    buckets = [
        (slabels["le"], value) for sname, slabels, value in samples
        if sname == family + "_bucket" and all(
            slabels.get(k) == v for k, v in labels.items())
    ]
    assert buckets, "no buckets for {} {}".format(family, labels)
    assert buckets[-1][0] == "+Inf"
    values = [v for _, v in buckets]
    assert values == sorted(values), (
        "histogram buckets must be cumulative non-decreasing", buckets)
    count = sample_value(samples, family + "_count", **labels)
    total = sample_value(samples, family + "_sum", **labels)
    assert count == values[-1], "+Inf bucket must equal _count"
    assert total is not None and total >= 0.0
    if count:
        # the sum of N observations is bounded by N * the largest
        # finite bound only when nothing landed in +Inf; always bounded
        # below by 0 and consistent with a nonzero count
        assert total > 0.0 or count == 0
    return count, total


def scrape(port, path="/metrics"):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        assert resp.status == 200, (path, resp.status)
        assert resp.getheader("Content-Type", "").startswith("text/plain")
        return resp.read().decode("utf-8")
    finally:
        conn.close()


# -- replica: request counters, histograms, typed error codes ---------------


def test_replica_metrics_move_under_traffic():
    import tritonclient.http as httpclient

    from tpuserver.core import InferenceServer
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import default_models

    core = InferenceServer(default_models())
    frontend = HttpFrontend(core, port=0).start()
    try:
        types, helps, before = parse_exposition(scrape(frontend.port))
        # the exposition declares its families
        assert types["tpu_requests_total"] == "counter"
        assert types["tpu_request_seconds"] == "histogram"
        assert types["tpu_inflight_requests"] == "gauge"
        assert "tpu_requests_total" in helps
        base = sample_value(
            before, "tpu_requests_total", verb="infer") or 0
        client = httpclient.InferenceServerClient(
            "127.0.0.1:{}".format(frontend.port))
        inputs = [httpclient.InferInput("INPUT0", [1, 16], "INT32"),
                  httpclient.InferInput("INPUT1", [1, 16], "INT32")]
        arr = np.arange(16, dtype=np.int32).reshape(1, 16)
        for tin in inputs:
            tin.set_data_from_numpy(arr)
        for _ in range(3):
            client.infer("simple", inputs)
        # a typed failure: unknown model answers 404 and counts
        with pytest.raises(Exception):
            client.infer("no_such_model", inputs)
        client.close()
        _, _, after = parse_exposition(scrape(frontend.port))
        moved = sample_value(after, "tpu_requests_total", verb="infer")
        assert moved == base + 4  # 3 successes + the typed failure
        count, total = check_histogram(
            after, "tpu_request_seconds", verb="infer")
        assert count >= 4 and total > 0.0
        assert sample_value(
            after, "tpu_request_errors_total",
            verb="infer", code="404") == 1
        # the nv_* compatibility families still ride along
        assert sample_value(after, "nv_inference_count",
                            model="simple") >= 3
    finally:
        frontend.stop()
        core.close()


def test_scheduler_families_are_all_exported():
    """Every scheduler, page-pool and routed-layer family the catalog
    declares is in ``/metrics`` of a server whose one model runs on the
    scheduler, once it has served a generation; no family of a removed
    feature is."""
    from tpuserver.core import InferenceServer, InferRequest
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.metrics import CATALOG
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    core = InferenceServer([LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=64, max_slots=2)])
    frontend = HttpFrontend(core, port=0).start()
    try:
        tokens = list(core.infer_stream(InferRequest(
            "llama_generate",
            inputs={"PROMPT_IDS": np.array([5, 6, 7], np.int32),
                    "MAX_TOKENS": np.array([4], np.int32)})))
        assert len(tokens) == 4
        types, _, samples = parse_exposition(scrape(frontend.port))
    finally:
        frontend.stop()
        core.close()
    wanted = {name for name in CATALOG
              if name.startswith(("tpu_scheduler_", "tpu_kv_", "tpu_moe_"))}
    assert len(wanted) >= 24
    assert wanted <= set(types), sorted(wanted - set(types))
    sampled = {name for name, _, _ in samples}
    for family in wanted:
        assert (family in sampled
                or family + "_count" in sampled), family
    assert not [n for n in set(types) | sampled if n.startswith("tpu_spec")]
    assert sample_value(samples, "tpu_scheduler_tokens_total",
                        model="llama_generate") == 4


def test_a_flash_prefill_counts_its_block_pairs():
    """One whole-prompt prefill of 128 positions through the paged
    scheduler adds the flash kernel's block pairs to both families: under
    32 x 64 tiles a layer-head's grid has 4 x 2 pairs, of which the
    causal mask leaves 6 (the first two query blocks see no key of the
    second key block); 2 layers x 8 heads."""
    import dataclasses

    from tpuserver.core import InferenceServer, InferRequest
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    cfg = dataclasses.replace(llama.tiny(vocab=512), attn_impl="pallas",
                              flash_block_q=32, flash_block_k=64)
    core = InferenceServer([LlamaGenerateModel(
        cfg=cfg, max_seq=256, max_slots=2)])
    frontend = HttpFrontend(core, port=0).start()

    def counts():
        _, _, samples = parse_exposition(scrape(frontend.port))
        return [sample_value(samples, "tpu_scheduler_flash_{}_total".format(
            name), model="llama_generate") or 0 for name in ("pairs",
                                                             "grid_pairs")]

    try:
        before = counts()
        prompt = (np.arange(128) * 7 % 500).astype(np.int32)
        tokens = list(core.infer_stream(InferRequest(
            "llama_generate", inputs={"PROMPT_IDS": prompt,
                                      "MAX_TOKENS": np.array([2], np.int32)})))
        assert len(tokens) == 2
        after = counts()
    finally:
        frontend.stop()
        core.close()
    layer_heads = cfg.n_layers * cfg.n_heads
    assert [a - b for a, b in zip(after, before)] == [6 * layer_heads,
                                                      8 * layer_heads]


# -- replica + router: token counters, fleet aggregation, single source -----


def test_router_reserves_metrics_fleet_aggregated_with_token_counters():
    """The acceptance path: a llama replica under traffic THROUGH a
    fronting router; both tiers scrape, token/request counters move on
    both, and the replica registry agrees exactly with
    ``DecodeScheduler.stats()`` (single source, no double
    accounting)."""
    import tritonclient.http as httpclient

    from tpuserver.core import InferenceServer
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel
    from tpuserver.router import FleetRouter

    model = LlamaGenerateModel(
        cfg=llama.tiny(vocab=256), max_seq=48, max_slots=2,
        restart_backoff_s=0.01)
    core = InferenceServer([model])
    frontend = HttpFrontend(core, port=0).start()
    router = FleetRouter(
        ["127.0.0.1:{}".format(frontend.port)],
        probe_interval_s=0.1).start()
    try:
        client = httpclient.InferenceServerClient(router.url)
        tokens = []
        for event in client.generate_stream(
                "llama_generate",
                {"PROMPT_IDS": np.array([3, 1, 4, 1], np.int32),
                 "MAX_TOKENS": np.array([6], np.int32)}):
            for out in event.get("outputs", []):
                if out["name"] == "TOKEN":
                    tokens.append(int(out["data"][0]))
        client.close()
        assert len(tokens) == 6

        # replica exposition: stream verb + scheduler token counters
        rep_types, _, rep = parse_exposition(scrape(frontend.port))
        assert rep_types["tpu_scheduler_tokens_total"] == "counter"
        assert sample_value(rep, "tpu_requests_total",
                            verb="stream_infer") == 1
        assert sample_value(rep, "tpu_scheduler_tokens_total",
                            model="llama_generate") == 6
        assert sample_value(rep, "tpu_scheduler_admissions_total",
                            model="llama_generate") == 1
        check_histogram(rep, "tpu_scheduler_step_seconds",
                        model="llama_generate")
        check_histogram(rep, "tpu_scheduler_queue_wait_seconds",
                        model="llama_generate")

        # single source: the registry IS the scheduler's own account
        stats = model.scheduler_stats()
        assert sample_value(rep, "tpu_scheduler_tokens_total",
                            model="llama_generate") == stats["tokens"]
        assert sample_value(rep, "tpu_scheduler_admissions_total",
                            model="llama_generate") == stats["admitted"]
        assert sample_value(rep, "tpu_scheduler_restarts_total",
                            model="llama_generate") == stats["restarts"]
        assert sample_value(rep, "tpu_scheduler_replay_hits_total",
                            model="llama_generate") == stats["replay_hits"]
        # ... the loop's seconds by phase too (the loop is idle: its
        # floats stand still between the scrape and the stats call)
        assert rep_types["tpu_scheduler_loop_seconds_total"] == "counter"
        assert stats["loop_seconds"]["fetch"] > 0
        for phase, seconds in stats["loop_seconds"].items():
            assert sample_value(rep, "tpu_scheduler_loop_seconds_total",
                                model="llama_generate",
                                phase=phase) == seconds
        for family in ("tpu_scheduler_admit_seconds",
                       "tpu_scheduler_first_token_seconds"):
            check_histogram(rep, family, model="llama_generate")
            assert sample_value(rep, family + "_count",
                                model="llama_generate") == stats["admitted"]

        # router exposition: its own tier families + the replica's
        # families fleet-aggregated under their original names
        r_types, _, agg = parse_exposition(scrape(router.port))
        assert r_types["tpu_router_handoffs_total"] == "counter"
        assert sample_value(agg, "tpu_router_replica_eligible",
                            replica=frontend.url) == 1
        assert sample_value(agg, "tpu_scheduler_tokens_total",
                            model="llama_generate") == 6
        assert sample_value(agg, "tpu_requests_total",
                            verb="stream_infer") == 1
    finally:
        router.stop()
        frontend.stop()
        core.close()


# -- the churn-safe aggregator (pure unit) ----------------------------------


def _families(counter_value, url="a"):
    return {
        "tpu_requests_total": {
            "type": "counter", "help": "h",
            "samples": [("tpu_requests_total", {"verb": "infer"},
                         float(counter_value))],
        },
        "tpu_inflight_requests": {
            "type": "gauge", "help": "h",
            "samples": [("tpu_inflight_requests", {}, 2.0)],
        },
    }


def _agg_value(text, name):
    _, _, samples = parse_exposition(text)
    return sample_value(samples, name, verb="infer")


def test_fleet_aggregation_is_monotonic_across_resets_and_churn():
    from tpuserver.router import _FleetMetricsAggregator

    agg = _FleetMetricsAggregator()
    live = ["a", "b"]
    text = agg.render(live, {"a": _families(10), "b": _families(5)})
    assert _agg_value(text, "tpu_requests_total") == 15
    # replica 'a' process restarted: its counter reset to 2 — the
    # fleet view folds the pre-reset 10 and keeps rising
    text = agg.render(live, {"a": _families(2), "b": _families(7)})
    assert _agg_value(text, "tpu_requests_total") == 19
    # replica 'b' leaves the membership (scale-down): its history stays
    text = agg.render(["a"], {"a": _families(3)})
    assert _agg_value(text, "tpu_requests_total") == 20
    # ... and a fresh 'b' at the same url starts from zero, no reset
    text = agg.render(["a", "b"], {"a": _families(3),
                                   "b": _families(1)})
    assert _agg_value(text, "tpu_requests_total") == 21
    # gauges sum the CURRENT scrape only — no retained state
    _, _, samples = parse_exposition(text)
    assert sample_value(samples, "tpu_inflight_requests") == 4


def test_fleet_aggregation_orders_histogram_buckets_numerically():
    """Aggregated bucket samples must leave in ascending numeric
    ``le`` order (lexicographic order — "+Inf" first, "10" before
    "2.5" — is rejected by OpenMetrics consumers)."""
    from tpuserver.router import _FleetMetricsAggregator

    fam = {"tpu_request_seconds": {
        "type": "histogram", "help": "h",
        "samples": [
            ("tpu_request_seconds_bucket",
             {"verb": "infer", "le": "+Inf"}, 3.0),
            ("tpu_request_seconds_bucket",
             {"verb": "infer", "le": "10"}, 3.0),
            ("tpu_request_seconds_bucket",
             {"verb": "infer", "le": "2.5"}, 2.0),
            ("tpu_request_seconds_bucket",
             {"verb": "infer", "le": "0.5"}, 1.0),
            ("tpu_request_seconds_sum", {"verb": "infer"}, 1.2),
            ("tpu_request_seconds_count", {"verb": "infer"}, 3.0),
        ],
    }}
    text = _FleetMetricsAggregator().render(["a"], {"a": fam})
    les = [re.search(r'le="([^"]+)"', line).group(1)
           for line in text.splitlines() if "_bucket" in line]
    assert les == ["0.5", "2.5", "10", "+Inf"]
    _, _, samples = parse_exposition(text)
    check_histogram(samples, "tpu_request_seconds", verb="infer")


def test_fleet_aggregation_tolerates_unreachable_replica():
    from tpuserver.router import _FleetMetricsAggregator

    agg = _FleetMetricsAggregator()
    text = agg.render(["a", "b"], {"a": _families(4),
                                   "b": _families(6)})
    assert _agg_value(text, "tpu_requests_total") == 10
    # 'b' is a member but its scrape failed: its last contribution
    # still counts (a probe blip must not dip the fleet view)
    text = agg.render(["a", "b"], {"a": _families(5)})
    assert _agg_value(text, "tpu_requests_total") == 11


def test_fleet_aggregation_ignores_stale_concurrent_folds():
    """Two concurrent /metrics handlers scrape without locks; the
    aggregator folds in scrape-START order — a slower, older round
    landing after a newer one must not read lower values as a counter
    reset (which would permanently inflate the fleet totals)."""
    from tpuserver.router import _FleetMetricsAggregator

    agg = _FleetMetricsAggregator()
    agg.render(["a"], {"a": _families(100)}, stamp=1.0)
    # scrape B (started at t=3) folds first with the newer value ...
    text = agg.render(["a"], {"a": _families(120)}, stamp=3.0)
    assert _agg_value(text, "tpu_requests_total") == 120
    # ... then scrape A (started at t=2, delayed) lands with 110: no
    # fold — NOT a reset, and the total must not jump to ~230
    text = agg.render(["a"], {"a": _families(110)}, stamp=2.0)
    assert _agg_value(text, "tpu_requests_total") == 120
    # the next in-order round folds normally
    text = agg.render(["a"], {"a": _families(130)}, stamp=4.0)
    assert _agg_value(text, "tpu_requests_total") == 130


def test_counter_is_exact_under_concurrent_writers():
    """Counter.inc must not lose or roll back increments under
    contention: a stale lock-free += store would read as a fake
    counter reset to scrapers and the fleet aggregator."""
    import threading

    from tpuserver.metrics import Counter

    counter = Counter()

    def hammer():
        for _ in range(10_000):
            counter.inc()

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counter.value == 80_000


def test_owned_gauge_registers_and_renders():
    """The owned-gauge surface (vs collector-rendered gauges) stays a
    supported registration shape."""
    from tpuserver.metrics import MetricsRegistry

    registry = MetricsRegistry()
    gauge = registry.gauge("tpu_inflight_requests").child()
    gauge.set(3)
    gauge.inc(2)
    gauge.dec()
    _, _, samples = parse_exposition(registry.render())
    assert sample_value(samples, "tpu_inflight_requests") == 4


def test_label_escaping_round_trips():
    """Escape/unescape must round-trip adversarial label values — in
    particular a literal backslash followed by 'n' must NOT decode to
    a newline (sequential str.replace order bug)."""
    from tpuserver.metrics import (
        MetricsRegistry,
        parse_prometheus_text,
    )

    tricky = 'a\\n"quoted"\nnewline\\\\end'
    registry = MetricsRegistry()
    registry.counter(
        "tpu_requests_total", labelnames=("verb",)
    ).labels(verb=tricky).inc()
    families = parse_prometheus_text(registry.render())
    (_, labels, value), = families["tpu_requests_total"]["samples"]
    assert labels["verb"] == tricky
    assert value == 1.0


def test_stacked_routers_emit_a_valid_exposition():
    """Routers stack (a router can front other routers): the outer
    router's /metrics must not re-declare its own tier families from
    the inner router's scrape — duplicate ``# TYPE`` blocks invalidate
    the exposition for real Prometheus scrapers."""
    from tpuserver.core import InferenceServer
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import default_models
    from tpuserver.router import FleetRouter

    core = InferenceServer(default_models())
    frontend = HttpFrontend(core, port=0).start()
    inner = FleetRouter(["127.0.0.1:{}".format(frontend.port)],
                        probe_interval_s=0.1).start()
    outer = FleetRouter(["127.0.0.1:{}".format(inner.port)],
                        probe_interval_s=0.1).start()
    try:
        text = scrape(outer.port)
        declared = [line.split()[2] for line in text.splitlines()
                    if line.startswith("# TYPE ")]
        dupes = {n for n in declared if declared.count(n) > 1}
        assert not dupes, dupes
        # the outer tier's own families render once, and the
        # replica-level families still flow through BOTH tiers
        _, _, samples = parse_exposition(text)
        assert sum(1 for n, _, _ in samples
                   if n == "tpu_router_handoffs_total") == 1
        assert sample_value(samples, "tpu_inflight_requests") is not None
    finally:
        outer.stop()
        inner.stop()
        frontend.stop()
        core.close()


def test_router_metrics_include_supervisor_counters():
    """A fleet supervisor attached to the router surfaces its
    process-healing counters as tpu_fleet_* families — the scrape twin
    of the /router/stats "supervisor" block."""
    from tpuserver.router import FleetRouter

    router = FleetRouter(["127.0.0.1:1"])  # never started, no probes
    try:
        router.attach_supervisor(lambda: {
            "replica_restarts": 3, "scale_up_events": 1,
            "scale_down_events": 0, "retired_replicas": 2, "up": 4})
        types, _, samples = parse_exposition(router.metrics.render())
        assert types["tpu_fleet_replica_restarts_total"] == "counter"
        assert sample_value(
            samples, "tpu_fleet_replica_restarts_total") == 3
        assert sample_value(samples, "tpu_fleet_scale_up_total") == 1
        assert sample_value(
            samples, "tpu_fleet_retired_replicas_total") == 2
        assert sample_value(samples, "tpu_fleet_replicas_up") == 4
    finally:
        router._httpd.server_close()


# -- gRPC: the same snapshot over the ServerMetrics unary -------------------


def test_grpc_server_metrics_unary_matches_http():
    import tritonclient.grpc as grpcclient

    from tpuserver.core import InferenceServer, InferRequest
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.models import default_models

    core = InferenceServer(default_models())
    frontend = GrpcFrontend(core, port=0).start()
    try:
        req = InferRequest("simple", inputs={
            "INPUT0": np.zeros((1, 16), np.int32),
            "INPUT1": np.zeros((1, 16), np.int32)})
        core.infer(req)
        client = grpcclient.InferenceServerClient(frontend.url)
        text = client.get_metrics()
        client.close()
        types, _, samples = parse_exposition(text)
        assert types["tpu_requests_total"] == "counter"
        assert sample_value(samples, "tpu_requests_total",
                            verb="infer") == 1
        check_histogram(samples, "tpu_request_seconds", verb="infer")
    finally:
        frontend.stop()
        core.close()
