"""Full benchmark: all five BASELINE.md target configs.

Mirrors `BASELINE.json`'s target list (see BASELINE.md "Target metric"):

  1. simple add/sub model, HTTP, sync, concurrency 1          (infer/sec, p50)
  2. ResNet-50 over GRPC — in-band vs system-shm vs XLA-shm   (infer/sec, p50)
  3. DenseNet-121 over GRPC with an XLA (TPU HBM) shm region  (infer/sec, p50)
  4. BERT-base ensemble (tokenizer → encoder), async GRPC
     streaming, pipelined                                     (infer/sec)
  5. Llama decoupled token-by-token generation with the KV
     cache parked in an XLA shm region                        (tokens/sec)

Each config prints ONE JSON line:
  {"config": N, "metric": "...", "value": X, "unit": "...",
   "vs_baseline": Y|null, ...}

The reference publishes baselines only for configs 1 (1407.84 infer/sec,
p50 690 usec — quick_start.md:94-108) and ResNet-50-shaped serving (165.8
infer/sec TF-Serving gRPC / 159.8 TorchServe HTTP — benchmarking.md:121-204);
the other configs report vs_baseline against the closest of those or null.

Usage:  python bench_full.py [--configs 1,2,3,4,5] [--quick]
`--quick` shrinks windows for smoke runs (not for reported numbers).
"""

import argparse
import json
import os
import statistics
import sys
import traceback
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src", "python"))

import numpy as np  # noqa: E402

import tpuserver  # noqa: E402

from bench import BASELINE_INFER_PER_SEC, BASELINE_P50_USEC  # noqa: E402

BASELINES = {
    "simple_http": BASELINE_INFER_PER_SEC,   # quick_start.md:94
    "simple_http_p50": BASELINE_P50_USEC,    # quick_start.md:96
    "resnet50_grpc": 165.8,      # benchmarking.md:121-129 (TF-Serving gRPC)
    "densenet_grpc": 159.8,      # benchmarking.md:196-204 (TorchServe HTTP)
}


def _measure(call, window_s, windows, warmup=20):
    """Median infer/sec over `windows` timed windows + overall p50 latency.

    The reference's methodology is 3 stable windows (perf_analyzer
    stability-percentage, inference_profiler.cc:780-833); here each window
    is fixed-duration and the reported rate is the median across windows.
    ``call`` receives a monotonically increasing iteration index so the
    workload can rotate DISTINCT inputs per iteration (hygiene rule 1).
    """
    seq = 0
    for _ in range(warmup):
        call(seq)
        seq += 1
    rates, lats = [], []
    for _ in range(windows):
        n = 0
        t0 = time.perf_counter()
        while True:
            t1 = time.perf_counter()
            call(seq)
            seq += 1
            lats.append(time.perf_counter() - t1)
            n += 1
            dt = time.perf_counter() - t0
            if dt >= window_s:
                break
        rates.append(n / dt)
    lats.sort()
    p50 = lats[len(lats) // 2] * 1e6
    return statistics.median(rates), p50


def _emit(config, metric, value, unit, baseline_key=None, **extra):
    base = BASELINES.get(baseline_key) if baseline_key else None
    line = {
        "config": config,
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / base, 4) if base else None,
    }
    line.update(extra)
    print(json.dumps(line), flush=True)
    return line


# ---------------------------------------------------------------------------
# config 1: simple model, HTTP, sync, concurrency 1
# ---------------------------------------------------------------------------

def bench_simple_http(http_url, window_s, windows):
    """Config 1 on the perfanalyzer profiler (the ad-hoc `_measure`
    loop this config used pre-PR-4 duplicated the percentile/window
    math that now lives in `perfanalyzer.metrics`): windowed
    measurement to 3-window stability, client percentiles, and the
    server queue/compute breakdown — same one-JSON-line schema."""
    import tritonclient.http as httpclient

    from perfanalyzer.client_backend import HttpBackend, build_input_pool
    from perfanalyzer.load_manager import ConcurrencyManager
    from perfanalyzer.profiler import InferenceProfiler

    # correctness smoke before any timing: the profiled path must be
    # computing real answers
    client = httpclient.InferenceServerClient(http_url)
    a = np.arange(16, dtype=np.int32).reshape(1, 16)
    b = np.full((1, 16), 2, dtype=np.int32)
    in0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
    in1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
    in0.set_data_from_numpy(a, binary_data=True)
    in1.set_data_from_numpy(b, binary_data=True)
    result = client.infer("simple", [in0, in1])
    assert (result.as_numpy("OUTPUT0") == a + b).all()
    client.close()

    backend = HttpBackend(http_url)
    manager = None
    try:
        # rule 1 lives in build_input_pool: 16 distinct input sets
        # rotated across dispatches
        pool = build_input_pool(
            backend.model_metadata("simple"),
            backend.model_config("simple"),
            pool_size=16, batch_size=1)
        manager = ConcurrencyManager(
            backend, "simple", backend.prepare("simple", pool))
        profiler = InferenceProfiler(
            backend, "simple", manager,
            measurement_interval_s=window_s,
            stability_windows=min(3, windows),
            max_trials=max(2 * windows, 3),
            warmup_s=0.3)
        res = profiler.profile_level(1)
    finally:
        if manager is not None:
            manager.stop()
        backend.close()
    return _emit(1, "simple_http_sync_conc1", res["throughput"],
                 "infer/sec", "simple_http",
                 p50_usec=round(res["p50_usec"], 1),
                 p50_vs_baseline=round(
                     res["p50_usec"] / BASELINES["simple_http_p50"], 4),
                 p90_usec=round(res["p90_usec"], 1),
                 p99_usec=round(res["p99_usec"], 1),
                 stable=res["stable"],
                 server_queue_usec=round(res["queue_usec"], 2),
                 server_compute_usec=round(res["compute_infer_usec"], 2),
                 client_overhead_pct=round(res["client_overhead_pct"], 1))


# ---------------------------------------------------------------------------
# configs 2/3: vision models over GRPC, in-band vs system shm vs XLA shm
# ---------------------------------------------------------------------------

def _vision_call_inband(client, grpcclient, model, imgs):
    """Rotates a pool of distinct pre-serialized inputs (rule 1); the
    response carries values in-band, so each call is self-fencing."""
    pool = []
    for img in imgs:
        inp = grpcclient.InferInput("INPUT", list(img.shape), "FP32")
        inp.set_data_from_numpy(img)
        pool.append(inp)
    out = grpcclient.InferRequestedOutput("OUTPUT")

    def call(i):
        client.infer(model, [pool[i % len(pool)]], outputs=[out])
    return call, lambda: None


def _vision_call_system_shm(client, grpcclient, model, imgs):
    """Each timed iteration writes a DISTINCT image into the region then
    infers — the honest system-shm workflow (host write + infer), not a
    parked constant.  Output returns in-band values (self-fencing); the
    input region is the data plane under test."""
    from tritonclient.utils import shared_memory as shm

    in_bytes = imgs[0].nbytes
    region_in = model + "_in"
    h_in = shm.create_shared_memory_region(
        region_in, "/" + region_in, in_bytes)
    client.register_system_shared_memory(region_in, "/" + region_in, in_bytes)
    inp = grpcclient.InferInput("INPUT", list(imgs[0].shape), "FP32")
    inp.set_shared_memory(region_in, in_bytes)
    out = grpcclient.InferRequestedOutput("OUTPUT")

    def call(i):
        shm.set_shared_memory_region(h_in, [imgs[i % len(imgs)]])
        client.infer(model, [inp], outputs=[out])

    def cleanup():
        client.unregister_system_shared_memory(region_in)
        shm.destroy_shared_memory_region(h_in)
    return call, cleanup



def _park_distinct_pool(xshm, h_in, rng, slots, img_shape, img_bytes):
    """Fresh distinct images into every input slot (untimed; rule 1)."""
    import jax.numpy as jnp

    pool = rng.rand(slots, *img_shape).astype(np.float32)
    for s in range(slots):
        xshm.set_shared_memory_region(
            h_in, [jnp.asarray(pool[s])], offset=s * img_bytes)
    return pool


def _fence_and_verify(xshm, h_out, out_shape, out_bytes, slots, sample_ids,
                      refs):
    """Window close (rule 2): value-fence the LAST slot (device
    executions retire in dispatch order, so its values prove the whole
    window completed on-device), then — when references are given —
    check sampled slots against their own input's in-band result and
    require bit-level distinctness between samples (a replayed/cached
    answer would be bit-identical)."""
    last = xshm.get_contents_as_numpy(
        h_out, np.float32, out_shape, offset=(slots - 1) * out_bytes)
    assert last.shape == tuple(out_shape)
    if refs is None:
        return
    checked = []
    for s in sample_ids:
        got = xshm.get_contents_as_numpy(
            h_out, np.float32, out_shape, offset=s * out_bytes)
        np.testing.assert_allclose(got, refs[s], rtol=2e-2, atol=2e-3)
        checked.append(got)
    for a, b in zip(checked, checked[1:]):
        assert (np.asarray(a) != np.asarray(b)).any(), \
            "distinct inputs produced bit-identical outputs"


def bench_vision_xla_shm(grpc_url, config, model, windows, infers_per_window,
                         concurrency=8, batch=1):
    """Hygienic XLA-shm vision bench (the north-star rows).

    Obeys all five hygiene rules from docs/benchmarking.md — the round-4
    numbers did not (one identical parked input re-dispatched, no value
    fence in the window) and were retracted:

    - **Rule 1/4 (distinct inputs)**: every timed dispatch reads a
      DISTINCT parked input — a fresh pool of ``infers_per_window``
      images is parked (untimed) before each window, never reused, so
      no (executable, values) pair ever repeats in the whole run.
    - **Rule 2 (value fence)**: each window's clock stops only after
      ``get_contents_as_numpy`` of the LAST request's output slot —
      device executions retire in dispatch order, so the last value
      fences the whole window.  After the clock, sampled slots are
      checked against in-band reference results computed before the
      window: values must match the slot's own input (content-cache or
      enqueue-rate inflation would fail here).
    - **Rule 5**: one full warmup window runs before timing.

    ``concurrency`` async requests ride in flight (perf_analyzer's
    async mode);
    ``batch`` images per parked slot fold into each dispatch.
    """
    import queue

    import jax.numpy as jnp
    import tritonclient.grpc as grpcclient
    from tritonclient.utils import xla_shared_memory as xshm

    baseline_key = "resnet50_grpc" if model == "resnet50" else "densenet_grpc"
    img_shape = (batch, 224, 224, 3)
    img_bytes = int(np.prod(img_shape)) * 4
    out_bytes = batch * 1000 * 4
    slots = max(1, infers_per_window // batch)
    region_in, region_out = (
        "{}_hxin_b{}".format(model, batch),
        "{}_hxout_b{}".format(model, batch),
    )
    client = grpcclient.InferenceServerClient(grpc_url)
    h_in = h_out = None
    rng = np.random.RandomState(1234)
    sample_ids = sorted({0, slots // 2, slots - 1})

    def park_pool():
        return _park_distinct_pool(
            xshm, h_in, rng, slots, img_shape, img_bytes)

    def reference_logits(pool):
        """In-band results for the sampled slots (untimed, pre-window):
        the ground truth the fenced shm outputs must reproduce."""
        refs = {}
        for s in sample_ids:
            inp = grpcclient.InferInput("INPUT", list(img_shape), "FP32")
            inp.set_data_from_numpy(pool[s])
            r = client.infer(model, [inp],
                             outputs=[grpcclient.InferRequestedOutput(
                                 "OUTPUT")])
            refs[s] = r.as_numpy("OUTPUT")
        return refs

    def run_window(timed):
        pool = park_pool()
        refs = reference_logits(pool) if timed else None
        done = queue.Queue()

        def issue(s):
            inp = grpcclient.InferInput("INPUT", list(img_shape), "FP32")
            inp.set_shared_memory(region_in, img_bytes,
                                  offset=s * img_bytes)
            out = grpcclient.InferRequestedOutput("OUTPUT")
            out.set_shared_memory(region_out, out_bytes,
                                  offset=s * out_bytes)
            client.async_infer(
                model, [inp],
                lambda result, error: done.put(error),
                outputs=[out])

        t0 = time.perf_counter()
        inflight = 0
        next_slot = 0
        while next_slot < slots and inflight < concurrency:
            issue(next_slot)
            next_slot += 1
            inflight += 1
        while inflight:
            err = done.get(timeout=300)
            assert err is None, repr(err)
            inflight -= 1
            if next_slot < slots:
                issue(next_slot)
                next_slot += 1
                inflight += 1
        _fence_and_verify(
            xshm, h_out, [batch, 1000], out_bytes, slots, sample_ids,
            refs if timed else None)
        return slots * batch / (time.perf_counter() - t0)

    try:
        # setup inside the try: a failed register must still release
        # the already-created segments and local registrations, or one
        # transient error poisons every later invocation's region names
        h_in = xshm.create_shared_memory_region(
            region_in, slots * img_bytes)
        h_out = xshm.create_shared_memory_region(
            region_out, slots * out_bytes)
        client.register_xla_shared_memory(
            region_in, xshm.get_raw_handle(h_in), 0, slots * img_bytes)
        client.register_xla_shared_memory(
            region_out, xshm.get_raw_handle(h_out), 0, slots * out_bytes)

        run_window(timed=False)  # warmup: compiles + first-use ops
        rates = [run_window(timed=True) for _ in range(windows)]

        # honest single-request latency: one dispatch, value-fenced
        pool = park_pool()
        lats = []
        for s in range(min(slots, 16)):
            inp = grpcclient.InferInput("INPUT", list(img_shape), "FP32")
            inp.set_shared_memory(region_in, img_bytes,
                                  offset=s * img_bytes)
            out = grpcclient.InferRequestedOutput("OUTPUT")
            out.set_shared_memory(region_out, out_bytes,
                                  offset=s * out_bytes)
            t0 = time.perf_counter()
            client.infer(model, [inp], outputs=[out])
            xshm.get_contents_as_numpy(
                h_out, np.float32, [batch, 1000], offset=s * out_bytes)
            lats.append(time.perf_counter() - t0)
        lats.sort()
        return _emit(
            config, "{}_grpc_xla_shm_hygienic_b{}_conc{}".format(
                model, batch, concurrency),
            statistics.median(rates), "infer/sec", baseline_key,
            p50_fenced_usec=round(lats[len(lats) // 2] * 1e6, 1),
            distinct_inputs_per_window=slots,
            value_fence="per-window drain + sampled in-band check")
    finally:
        try:
            client.unregister_xla_shared_memory(region_in)
            client.unregister_xla_shared_memory(region_out)
        except Exception:
            pass
        if h_in is not None:
            xshm.destroy_shared_memory_region(h_in)
        if h_out is not None:
            xshm.destroy_shared_memory_region(h_out)
        client.close()


def bench_vision(grpc_url, config, model, modes, window_s, windows):
    import tritonclient.grpc as grpcclient

    client = grpcclient.InferenceServerClient(grpc_url)
    imgs = [
        np.random.RandomState(s).rand(1, 224, 224, 3).astype(np.float32)
        for s in range(16)
    ]
    baseline_key = "resnet50_grpc" if model == "resnet50" else "densenet_grpc"
    makers = {
        "inband": _vision_call_inband,
        "system_shm": _vision_call_system_shm,
    }
    results = {}
    try:
        for mode in modes:
            try:
                call, cleanup = makers[mode](client, grpcclient, model, imgs)
            except Exception:
                # partial setup may have registered regions; drop them all
                client.unregister_system_shared_memory()
                client.unregister_xla_shared_memory()
                raise
            try:
                call(0)  # smoke + compile
                rate, p50 = _measure(call, window_s, windows, warmup=5)
            finally:
                cleanup()
            results[mode] = _emit(
                config, "{}_grpc_{}".format(model, mode), rate,
                "infer/sec", baseline_key, p50_usec=round(p50, 1))
    finally:
        client.close()
    return results


def bench_vision_concurrent(grpc_url, config, model, window_s, windows,
                            sweep=((1, 4), (1, 8), (1, 16), (1, 32),
                                   (4, 8), (8, 4))):
    """Async concurrency sweep for the vision configs.

    The reference's 165.8 infer/sec ResNet-50 number (benchmarking.md:121)
    is a local-network GPU box.  perf_analyzer's answer to per-request
    latency (and the reference's async examples') is pipelining: N
    in-flight async_infer requests overlap it, and the server's dynamic
    batcher folds them into one MXU-shaped dispatch.
    Sweeps (client_batch, concurrency) pairs; reports each plus the best.
    """
    import queue

    import tritonclient.grpc as grpcclient

    baseline_key = "resnet50_grpc" if model == "resnet50" else "densenet_grpc"
    best = None
    client = grpcclient.InferenceServerClient(grpc_url)
    try:
        for batch, conc in sweep:
            # rule 1: rotate distinct pre-serialized inputs; responses
            # carry values in-band, so each completion is self-fencing
            pool = []
            for s in range(16):
                img = np.random.RandomState(1000 + s).rand(
                    batch, 224, 224, 3).astype(np.float32)
                pin = grpcclient.InferInput(
                    "INPUT", list(img.shape), "FP32")
                pin.set_data_from_numpy(img)
                pool.append(pin)
            out = grpcclient.InferRequestedOutput("OUTPUT")
            done = queue.Queue()
            issued = [0]

            def issue():
                t0 = time.perf_counter()
                inp = pool[issued[0] % len(pool)]
                issued[0] += 1
                client.async_infer(
                    model, [inp],
                    lambda result, error, t0=t0: done.put(
                        (result, error, time.perf_counter() - t0)),
                    outputs=[out])

            # warmup burst at the target concurrency, so the batch
            # bucket this level actually lands in gets compiled now,
            # not inside a measured window
            for _ in range(conc):
                issue()
            for _ in range(conc):
                _, err, _ = done.get(timeout=600)
                assert err is None, repr(err)

            rates, lats = [], []
            for _ in range(windows):
                inflight = 0
                completed = 0
                t0 = time.perf_counter()
                while inflight < conc:
                    issue()
                    inflight += 1
                while True:
                    _, err, lat = done.get(timeout=300)
                    assert err is None, repr(err)
                    completed += batch
                    inflight -= 1
                    lats.append(lat)
                    dt = time.perf_counter() - t0
                    if dt >= window_s:
                        break
                    issue()
                    inflight += 1
                while inflight:
                    _, err, _ = done.get(timeout=300)
                    assert err is None, repr(err)
                    inflight -= 1
                rates.append(completed / dt)
            lats.sort()
            line = _emit(
                config,
                "{}_grpc_async_b{}_conc{}".format(model, batch, conc),
                statistics.median(rates), "infer/sec", baseline_key,
                p50_usec=round(lats[len(lats) // 2] * 1e6, 1))
            if best is None or line["value"] > best["value"]:
                best = dict(line, batch=batch, concurrency=conc)
    finally:
        client.close()
    if best is not None:
        print(json.dumps({
            "config": config,
            "metric": "{}_grpc_async_best".format(model),
            "value": best["value"], "unit": "infer/sec",
            "vs_baseline": best["vs_baseline"],
            "batch": best["batch"], "concurrency": best["concurrency"],
        }), flush=True)
    return best


# ---------------------------------------------------------------------------
# config 4: BERT ensemble, async GRPC streaming, pipelined
# ---------------------------------------------------------------------------

def bench_bert_stream(grpc_url, window_s, windows):
    """Pipelined streaming over a long-lived bidi stream."""
    import queue

    import tritonclient.grpc as grpcclient

    client = grpcclient.InferenceServerClient(grpc_url)
    done = queue.Queue()
    client.start_stream(lambda result, error: done.put((result, error)))
    words = ("alpha", "brown", "crane", "delta", "ember", "frost",
             "grove", "heron")

    def issue(i):
        # rule 1: every request carries a DISTINCT text (the index is
        # woven into the token stream), so no (executable, values)
        # pair repeats; responses return values in-band (self-fencing)
        text = "bench {} {} {}".format(
            i, words[i % len(words)], words[(i // len(words)) % len(words)]
        ).encode("utf-8")
        inp = grpcclient.InferInput("TEXT", [1], "BYTES")
        inp.set_data_from_numpy(np.array([text], dtype=np.object_))
        client.async_stream_infer("bert_ensemble", [inp])

    def issue_tokenizer(i):
        text = "stage {} {}".format(i, words[i % len(words)]).encode()
        inp = grpcclient.InferInput("TEXT", [1], "BYTES")
        inp.set_data_from_numpy(np.array([text], dtype=np.object_))
        client.async_stream_infer("bert_tokenizer", [inp])

    def issue_encoder(i):
        # distinct ids per request (rule 1); realistic token-id range
        ids = np.random.RandomState(i).randint(
            1000, 29000, (1, 128)).astype(np.int32)
        ids[0, 0] = 101
        mask = np.ones((1, 128), np.int32)
        i_ids = grpcclient.InferInput("INPUT_IDS", [1, 128], "INT32")
        i_ids.set_data_from_numpy(ids)
        i_mask = grpcclient.InferInput("ATTENTION_MASK", [1, 128], "INT32")
        i_mask.set_data_from_numpy(mask)
        client.async_stream_infer("bert_encoder", [i_ids, i_mask])

    def pipelined_rate(issue_fn, inflight_target, record_lat=None):
        inflight = 0
        completed = 0
        t0 = time.perf_counter()
        sent_at = {}
        seq = 0
        while True:
            while inflight < inflight_target:
                sent_at[seq] = time.perf_counter()
                issue_fn(seq)
                seq += 1
                inflight += 1
            result, error = done.get(timeout=300)
            assert error is None, repr(error)
            completed += 1
            inflight -= 1
            if record_lat is not None:
                record_lat.append(
                    time.perf_counter() - sent_at.pop(completed - 1, t0))
            dt = time.perf_counter() - t0
            if dt >= window_s:
                break
        while inflight:
            result, error = done.get(timeout=300)
            assert error is None, repr(error)
            inflight -= 1
        return completed / dt

    try:
        # prime/compile: the first request carries the XLA compile
        issue(0)
        result, error = done.get(timeout=600)
        assert error is None, repr(error)

        rates = []
        lat = []
        inflight_target = 8
        for _ in range(windows):
            rates.append(pipelined_rate(issue, inflight_target, lat))

        # stage accounting (round-4 verdict: config 4 had no bound
        # analysis).  Measure each composing model at the same inflight
        # over the same stream, plus the encoder roofline.
        issue_tokenizer(0)
        assert done.get(timeout=600)[1] is None
        tok_rate = pipelined_rate(issue_tokenizer, inflight_target)
        issue_encoder(0)
        assert done.get(timeout=600)[1] is None
        enc_rate = pipelined_rate(issue_encoder, inflight_target)
    finally:
        try:
            client.stop_stream(cancel_requests=True)
        except Exception:
            pass
        client.close()
    lat.sort()
    e2e = statistics.median(rates)
    line = _emit(4, "bert_ensemble_grpc_stream_pipelined", e2e,
                 "infer/sec", None,
                 p50_usec=round(lat[len(lat) // 2] * 1e6, 1))
    # bound analysis: encoder MFU at the measured stage rate, and which
    # stage the ensemble rate tracks
    from tpuserver.ops import perf

    spec = perf.chip_spec()
    enc_flops = perf.bert_encoder_flops()
    stage_mfu = (
        round(perf.mfu(enc_flops * enc_rate, 1.0, spec), 4)
        if spec else None
    )
    bounds = {"tokenizer": tok_rate, "encoder": enc_rate}
    bound = min(bounds, key=lambda k: bounds[k])
    if e2e < 0.6 * min(tok_rate, enc_rate):
        # the ensemble runs far below BOTH stages: per-request dispatch/
        # stream overhead dominates, not either stage's compute
        bound = "dispatch"
    print(json.dumps({
        "config": 4, "metric": "bert_ensemble_bound_analysis",
        "value": round(e2e, 2), "unit": "infer/sec", "vs_baseline": None,
        "tokenizer_only": round(tok_rate, 2),
        "encoder_only": round(enc_rate, 2),
        "encoder_mfu_at_stage_rate": stage_mfu,
        "bound": bound,
    }), flush=True)
    return line


# ---------------------------------------------------------------------------
# config 5: llama decoupled generation, tokens/sec, KV parked in XLA shm
# ---------------------------------------------------------------------------

def bench_llama_direct(cfg_name, windows, prefill_len=2048, chunk=32,
                       decode_ctx=512, max_seq=3072, attn_impl="pallas",
                       quantize=False):
    """Model-level llama numbers on the chip: prefill wall-clock + MFU,
    steady-state decode tokens/sec + MFU + MBU (roofline accounting in
    tpuserver/ops/perf.py).  This is the defensible form of the config-5
    claim: real model dims, one-dispatch prefill, scanned decode chunks
    (so dispatch latency is amortized ``chunk`` ways), and utilization
    reported against the chip's published peaks rather than bare rates.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from tpuserver.models import llama
    from tpuserver.ops import perf

    import dataclasses

    cfg = dataclasses.replace(
        getattr(llama, cfg_name)(), attn_impl=attn_impl)
    spec = perf.chip_spec()
    if quantize:
        # init + quantize on host: the 8B preset's bf16 form (16 GB)
        # must never exist in HBM; its int8 form (~8 GB) fits one v5e.
        # Needs JAX_PLATFORMS unset or "tpu,cpu" (plain "tpu" raises)
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            params = llama.quantize_params(
                llama.init_params(jax.random.PRNGKey(0), cfg))
        params = jax.device_put(params, jax.devices()[0])
    else:
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    n_params = perf.param_count(cfg)
    weight_bytes = 1 if quantize else 2

    prefill_j = jax.jit(functools.partial(llama.prefill, cfg=cfg))
    decode_j = jax.jit(
        functools.partial(llama.decode_chunk, cfg=cfg, chunk=chunk),
        donate_argnums=(1,),
    )

    # Measurement hygiene: (1) every timed iteration uses DISTINCT
    # inputs, and (2) the clock stops only after fetching result VALUES
    # to the host (np.asarray) — dispatch is asynchronous, and values
    # cannot lie.  An MFU/MBU above 1.0 is physically impossible; emit
    # would mean the guards failed.
    key = jax.random.PRNGKey(42)

    # prefill: K chained dispatches with distinct prompts; each prompt's
    # first token depends on the previous prefill's logits, so one value
    # fence at the end proves every dispatch completed, amortizing the
    # host<->device sync across all K
    cache = llama.init_kv_cache(cfg, 1, max_seq)
    tokens0 = jax.random.randint(
        key, (1, prefill_len), 0, cfg.vocab, jnp.int32)
    logits, cache = prefill_j(params, cache, tokens0)  # compile
    np.asarray(logits)
    n_prefills = max(windows, 3)
    prompts = [
        jnp.asarray(
            np.random.RandomState(i).randint(
                0, cfg.vocab, (1, prefill_len)).astype(np.int32))
        for i in range(n_prefills)
    ]
    c2 = llama.init_kv_cache(cfg, 1, max_seq)
    lg = logits
    # warm the chain's eager helper ops (argmax/at-set/%): each cold
    # first-use compile would otherwise land inside the timed window
    warm = tokens0.at[0, 0].set(
        jnp.argmax(lg[0]).astype(jnp.int32) % cfg.vocab)
    lg, c2 = prefill_j(params, c2, warm)
    np.asarray(lg)
    jax.block_until_ready(c2)
    t0 = time.perf_counter()
    for toks_i in prompts:
        chained = toks_i.at[0, 0].set(
            jnp.argmax(lg[0]).astype(jnp.int32) % cfg.vocab)
        lg, c2 = prefill_j(params, c2, chained)
    np.asarray(lg)  # single value fence for the chain
    t_prefill = (time.perf_counter() - t0) / n_prefills
    del c2
    pf = perf.prefill_flops(cfg, prefill_len)
    mfu_val = perf.mfu(pf, t_prefill, spec) if spec else None
    _emit(5, "{}_prefill_T{}".format(cfg_name, prefill_len),
          t_prefill * 1e3, "ms", None,
          mfu=round(mfu_val, 4) if mfu_val is not None else None,
          suspect=bool(mfu_val and mfu_val > 1.0),
          attn=cfg.attn_impl,
          params=n_params, chip=spec.name if spec else None)

    # steady-state decode from decode_ctx: chain MANY chunked scans and
    # stop the clock once on the final tokens — the cache/logits chain
    # forces every intermediate dispatch to have completed
    cache = llama.init_kv_cache(cfg, 1, max_seq)
    prompt = jax.random.randint(
        jax.random.PRNGKey(7), (1, decode_ctx), 0, cfg.vocab, jnp.int32)
    logits, cache = prefill_j(params, cache, prompt)
    toks, lps, logits, cache = decode_j(params, cache, logits, decode_ctx)
    np.asarray(toks)  # compile + settle
    pos = decode_ctx + chunk
    n_chunks = max(2 * windows, 4)
    n_chunks = min(n_chunks, (max_seq - pos) // chunk)
    if n_chunks < 1:
        raise ValueError(
            "max_seq {} leaves no room to decode any {}-token chunk past "
            "context {}".format(max_seq, chunk, pos))
    t0 = time.perf_counter()
    for _ in range(n_chunks):
        toks, lps, logits, cache = decode_j(params, cache, logits, pos)
        pos += chunk
    np.asarray(toks)  # single value fence for the whole chain
    dt = time.perf_counter() - t0
    rate = n_chunks * chunk / dt
    ctx_mid = decode_ctx + chunk * (n_chunks // 2)
    fpt = perf.decode_flops_per_token(cfg, ctx_mid)
    bpt = perf.decode_bytes_per_token(
        cfg, ctx_mid, weight_bytes_per_param=weight_bytes)
    mbu_val = perf.mbu(bpt * rate, 1.0, spec) if spec else None
    _emit(5, "{}_decode_ctx{}".format(cfg_name, ctx_mid), rate,
          "tokens/sec", None,
          mfu=round(perf.mfu(fpt * rate, 1.0, spec), 4) if spec else None,
          mbu=round(mbu_val, 4) if mbu_val is not None else None,
          suspect=bool(mbu_val and mbu_val > 1.0),
          chunk=chunk, params=n_params,
          weights="int8" if quantize else "bf16",
          chip=spec.name if spec else None)

def bench_llama_stream(grpc_url, windows, max_tokens=64):
    import queue

    import tritonclient.grpc as grpcclient
    from tritonclient.utils import xla_shared_memory as xshm

    client = grpcclient.InferenceServerClient(grpc_url)
    kv = xshm.create_shared_memory_region("bench_kv", 8 << 20)
    client.register_xla_shared_memory(
        "bench_kv", xshm.get_raw_handle(kv), 0, 8 << 20)

    responses = queue.Queue()
    client.start_stream(lambda result, error: responses.put((result, error)))
    m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
    m_in.set_data_from_numpy(np.array([max_tokens], dtype=np.int32))

    def generate(park, seed, timeout_s=300):
        # rule 1/4: a distinct prompt per call — an identical prompt
        # would make the whole greedy generation an identical
        # (executable, values) replay a transport could cache
        prompt = np.random.RandomState(seed).randint(
            1, 2000, (8,)).astype(np.int32)
        p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)], "INT32")
        p_in.set_data_from_numpy(prompt)
        params = {"kv_cache_region": "bench_kv"} if park else None
        t0 = time.perf_counter()
        first = None
        n = 0
        client.async_stream_infer(
            "llama_generate", [p_in, m_in],
            enable_empty_final_response=True, parameters=params)
        while True:
            result, error = responses.get(timeout=timeout_s)
            assert error is None, error
            resp = result.get_response()
            if resp.parameters.get(
                    "triton_final_response") and resp.parameters[
                    "triton_final_response"].bool_param:
                break
            if first is None:
                first = time.perf_counter() - t0
            n += 1
        return n / (time.perf_counter() - t0), first

    try:
        # warmup: big presets lazily init+quantize on ONE host core
        # before their first compile — minutes before the first token
        generate(False, 0, timeout_s=1800)
        rates, ttfts = [], []
        for w in range(windows):
            r, ttft = generate(True, 1 + w)
            rates.append(r)
            ttfts.append(ttft)
    finally:
        try:
            client.stop_stream(cancel_requests=True)
            client.unregister_xla_shared_memory("bench_kv")
        except Exception:
            pass
        xshm.destroy_shared_memory_region(kv)
        client.close()
    return _emit(5, "llama_decoupled_stream", statistics.median(rates),
                 "tokens/sec", None,
                 ttft_ms=round(statistics.median(ttfts) * 1e3, 1),
                 max_tokens=max_tokens)


def bench_llama_multistream(grpc_url, cfg_name, windows, stream_counts,
                            max_tokens=64, quantize=False):
    """Config-5 continuous-batching rows: sustained generation over N
    CONCURRENT decoupled streams (each its own gRPC connection), against
    a server running the scheduler (``--llama-slots >= max(streams)``).

    Reports per concurrency level: **aggregate tok/s** (total tokens
    over the round's wall clock — the serving-throughput number the
    scheduler exists to lift), per-stream p50 tok/s (what one client
    feels), median TTFT, and MBU with the weight stream amortized over
    the batch (one batched decode step reads the weights ONCE for all
    active slots: bytes/step = weights + N * kv_row, steps/sec =
    aggregate / N).

    Hygiene: every stream in every round carries a DISTINCT prompt
    (rule 1/4); token counts are exact (value-fenced by construction —
    each counted token arrived as a decoupled response's VALUES); one
    full warmup round at max concurrency runs before timing (rule 5).
    """
    import queue
    import threading

    import tritonclient.grpc as grpcclient

    from tpuserver.models import llama as llama_mod
    from tpuserver.ops import perf

    cfg = (
        getattr(llama_mod, cfg_name)()
        if cfg_name != "tiny" else llama_mod.tiny(vocab=2048)
    )
    spec = perf.chip_spec()
    seed_counter = [0]

    def one_stream(seed, n_tokens, out, barrier):
        client = grpcclient.InferenceServerClient(grpc_url)
        done = queue.Queue()
        client.start_stream(lambda result, error: done.put((result, error)))
        try:
            prompt = np.random.RandomState(seed).randint(
                1, 2000, (8,)).astype(np.int32)
            p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)],
                                         "INT32")
            p_in.set_data_from_numpy(prompt)
            m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
            m_in.set_data_from_numpy(np.array([n_tokens], dtype=np.int32))
            barrier.wait(timeout=600)
            t0 = time.perf_counter()
            client.async_stream_infer(
                "llama_generate", [p_in, m_in],
                enable_empty_final_response=True)
            n, first = 0, None
            while True:
                result, error = done.get(timeout=1800)
                assert error is None, repr(error)
                resp = result.get_response()
                final = resp.parameters.get("triton_final_response")
                if final and final.bool_param:
                    break
                if first is None:
                    first = time.perf_counter() - t0
                n += 1
            out.append((n, time.perf_counter() - t0, first))
        finally:
            client.stop_stream(cancel_requests=True)
            client.close()

    def run_round(conc, n_tokens):
        out = []
        barrier = threading.Barrier(conc + 1)
        # seeds assigned BEFORE spawning: rule 1's distinct-prompt
        # guarantee must not depend on thread interleaving
        threads = []
        for _ in range(conc):
            seed_counter[0] += 1
            threads.append(threading.Thread(
                target=one_stream,
                args=(seed_counter[0], n_tokens, out, barrier)))
        for t in threads:
            t.start()
        barrier.wait(timeout=600)
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert len(out) == conc, "a stream died"
        total = sum(n for n, _, _ in out)
        assert total == conc * n_tokens, (total, conc, n_tokens)
        return total / wall, out

    lines = []
    # warmup at max concurrency: compiles (prefill at this prompt len,
    # the batched step) land before any timed round
    run_round(max(stream_counts), min(8, max_tokens))
    for conc in stream_counts:
        rates, per_stream, ttfts = [], [], []
        for _ in range(windows):
            agg, out = run_round(conc, max_tokens)
            rates.append(agg)
            per_stream.extend(n / dt for n, dt, _ in out)
            ttfts.extend(f for _, _, f in out if f is not None)
        per_stream.sort()
        ttfts.sort()
        agg = statistics.median(rates)
        mbu_val = None
        if spec is not None:
            # one batched step serves `conc` tokens: weights stream once
            wb = 1 if quantize else 2
            ctx = 8 + max_tokens // 2
            kv_per_tok = perf.decode_bytes_per_token(
                cfg, ctx, weight_bytes_per_param=wb
            ) - perf.matmul_params(cfg) * wb
            bytes_per_sec = (
                agg / conc * perf.matmul_params(cfg) * wb
                + agg * kv_per_tok
            )
            mbu_val = perf.mbu(bytes_per_sec, 1.0, spec)
        lines.append(_emit(
            5, "llama_multistream_conc{}".format(conc), agg,
            "tokens/sec", None,
            streams=conc,
            per_stream_p50=round(per_stream[len(per_stream) // 2], 2),
            ttft_ms=round(ttfts[len(ttfts) // 2] * 1e3, 1)
            if ttfts else None,
            mbu=round(mbu_val, 4) if mbu_val is not None else None,
            max_tokens=max_tokens,
        ))
    if len(lines) > 1:
        print(json.dumps({
            "config": 5, "metric": "llama_multistream_scaling",
            "value": round(lines[-1]["value"] / lines[0]["value"], 3),
            "unit": "x", "vs_baseline": None,
            "streams": "{}->{}".format(
                lines[0]["streams"], lines[-1]["streams"]),
        }), flush=True)
    return lines


def bench_vision_core(window_s, windows, infers_per_window=128):
    """Config-2 data-plane comparison at the server core (no sockets):
    in-band numpy input vs device-parked XLA-shm inputs with shm-
    delivered outputs.  This isolates the host<->device traffic the
    XLA plane exists to remove.  Hygiene: distinct inputs per iteration on both
    arms; the in-band arm materializes result values per request
    (self-fencing), the shm arm drains each window through a value
    fence on the last slot + sampled correctness checks."""
    import jax.numpy as jnp

    from tpuserver.core import InferenceServer, InferRequest, RequestedOutput
    from tpuserver.models import serving_models
    from tritonclient.utils import xla_shared_memory as xshm

    core = InferenceServer(
        serving_models(include_bert=False, include_llama=False))
    imgs = [
        np.random.RandomState(s).rand(1, 224, 224, 3).astype(np.float32)
        for s in range(16)
    ]
    reqs = [InferRequest("resnet50", inputs={"INPUT": im}) for im in imgs]
    rate_in, p50_in = _measure(
        lambda i: core.infer(reqs[i % len(reqs)]),
        window_s, windows, warmup=5)
    _emit(2, "resnet50_core_inband", rate_in, "infer/sec", None,
          p50_usec=round(p50_in, 1))

    slots = infers_per_window
    img_bytes, out_bytes = imgs[0].nbytes, 4000
    h_in = xshm.create_shared_memory_region("core_xin", slots * img_bytes)
    h_out = xshm.create_shared_memory_region("core_xout", slots * out_bytes)
    core.register_xla_shm(
        "core_xin", xshm.get_raw_handle(h_in), 0, slots * img_bytes)
    core.register_xla_shm(
        "core_xout", xshm.get_raw_handle(h_out), 0, slots * out_bytes)
    rng = np.random.RandomState(77)
    try:
        def run_window(timed):
            pool = _park_distinct_pool(
                xshm, h_in, rng, slots, (1, 224, 224, 3), img_bytes)
            sample = sorted({0, slots // 2, slots - 1})
            refs = {
                s: np.asarray(
                    core.infer(InferRequest(
                        "resnet50", inputs={"INPUT": pool[s]})
                    ).outputs[0][1])
                for s in sample
            } if timed else None
            shm_reqs = []
            for s in range(slots):
                arr = core.read_shm_input(
                    "core_xin", img_bytes, s * img_bytes, "FP32",
                    [1, 224, 224, 3])
                shm_reqs.append(InferRequest(
                    "resnet50", inputs={"INPUT": arr},
                    requested_outputs=[RequestedOutput(
                        "OUTPUT", shm_region="core_xout",
                        shm_byte_size=out_bytes,
                        shm_offset=s * out_bytes)]))
            t0 = time.perf_counter()
            for req in shm_reqs:
                core.infer(req)
            _fence_and_verify(
                xshm, h_out, [1, 1000], out_bytes, slots, sample, refs)
            return slots / (time.perf_counter() - t0)

        run_window(timed=False)
        rates = [run_window(timed=True) for _ in range(windows)]
        rate_shm = statistics.median(rates)
        _emit(2, "resnet50_core_xla_shm", rate_shm, "infer/sec", None,
              distinct_inputs_per_window=slots,
              value_fence="window drain + sampled check")
        print(json.dumps({
            "config": 2, "metric": "resnet50_core_xla_vs_inband",
            "value": round(rate_shm / rate_in, 4), "unit": "ratio",
            "vs_baseline": None,
        }), flush=True)
    finally:
        core.unregister_xla_shm()
        xshm.destroy_shared_memory_region(h_in)
        xshm.destroy_shared_memory_region(h_out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4,5")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--llama-attn", default="pallas", choices=["xla", "pallas"],
        help="config-5 prefill attention (pallas = the flash kernel, "
             "~10x the dense prefill at T=2048 on v5e)")
    ap.add_argument(
        "--llama-stream-only", action="store_true",
        help="config 5: skip the model-level direct bench (rerun only "
             "the served decoupled-stream measurement)")
    ap.add_argument(
        "--llama-quantize", action="store_true",
        help="config-5 int8 weight-only quantization (what fits the "
             "8B preset on one 16 GB v5e chip)")
    ap.add_argument(
        "--llama-config", default="llama3_3b",
        help="config-5 model preset (llama3_3b = the largest that fits "
             "one v5e chip's 16 GB HBM in bf16; llama3_1b / tiny for "
             "smoke runs)")
    ap.add_argument(
        "--llama-slots", type=int, default=1,
        help="config-5 continuous-batching slots (1 = the original "
             "single-stream path, byte-for-byte; >1 serves generations "
             "through the batched decode scheduler and adds the "
             "multi-stream sustained-generation rows at 1/4/8 "
             "concurrent streams)")
    ap.add_argument(
        "--core-only", action="store_true",
        help="config-2 data-plane comparison at the server core "
             "(no sockets; isolates the host<->device traffic)")
    args = ap.parse_args()
    # every row here is a device number: no chip, no run
    tpuserver.require_tpu()
    tpuserver.enable_compile_cache()
    if args.core_only:
        bench_vision_core(0.5 if args.quick else 2.0,
                          2 if args.quick else 5)
        sys.stdout.flush()
        os._exit(0)
    wanted = {int(c) for c in args.configs.split(",")}
    window_s = 0.5 if args.quick else 2.0
    windows = 2 if args.quick else 5

    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import default_models, serving_models

    failures = []
    if 5 in wanted and not args.llama_stream_only:
        # model-level numbers first: the params/cache used here are
        # freed before the serving zoo loads its own copy
        try:
            bench_llama_direct(
                args.llama_config, 2 if args.quick else 5,
                prefill_len=256 if args.quick else 2048,
                chunk=8 if args.quick else 32,
                decode_ctx=64 if args.quick else 512,
                max_seq=512 if args.quick else 3072,
                attn_impl=args.llama_attn,
                quantize=args.llama_quantize)
        except Exception as e:
            failures.append((5, e))
        import gc
        gc.collect()

    need_zoo = wanted & {2, 3, 4, 5}
    models = default_models()
    if need_zoo:
        from tpuserver.models import llama as llama_mod

        import dataclasses as _dc

        llama_cfg = (
            getattr(llama_mod, args.llama_config)()
            if args.llama_config != "tiny" else llama_mod.tiny(vocab=2048)
        )
        llama_cfg = _dc.replace(llama_cfg, attn_impl=args.llama_attn)
        models += serving_models(
            include_vision=bool(wanted & {2, 3}),
            include_bert=4 in wanted,
            include_llama=5 in wanted,
            llama_cfg=llama_cfg,
            llama_decode_chunk=8 if args.quick else 32,
            llama_quantize=args.llama_quantize,
            llama_max_slots=args.llama_slots,
        )
    core = InferenceServer(models)
    if 5 in wanted:
        # the llama serving model lazily inits (and for --llama-quantize,
        # quantizes on the single host core — tens of minutes for the 8B
        # preset) inside its FIRST request; warm it eagerly so the
        # stream bench's response timeout covers only compiles
        for m in models:
            if getattr(m, "name", "") == "llama_generate":
                m.warmup()
    http = HttpFrontend(core, port=0).start()
    grpc_f = GrpcFrontend(core, port=0).start()
    grpc_url = "127.0.0.1:{}".format(grpc_f.port)
    http_url = http.url.replace("http://", "")
    try:
        if 1 in wanted:
            try:
                bench_simple_http(http_url, window_s, windows)
            except Exception as e:
                failures.append((1, e))
        ipw = 32 if args.quick else 192
        if 2 in wanted:
            try:
                bench_vision(grpc_url, 2, "resnet50",
                             ["inband", "system_shm"],
                             window_s, windows)
            except Exception as e:  # keep later configs running
                failures.append((2, e))
            for batch, conc in ((1, 8), (4, 8)) if not args.quick else (
                    (1, 4),):
                try:
                    bench_vision_xla_shm(
                        grpc_url, 2, "resnet50", windows, ipw,
                        concurrency=conc, batch=batch)
                except Exception as e:
                    failures.append((2, e))
            try:
                bench_vision_concurrent(grpc_url, 2, "resnet50",
                                        window_s, windows)
            except Exception as e:
                failures.append((2, e))
        if 3 in wanted:
            for batch, conc in ((1, 8), (4, 8)) if not args.quick else (
                    (1, 4),):
                try:
                    bench_vision_xla_shm(
                        grpc_url, 3, "densenet121", windows, ipw,
                        concurrency=conc, batch=batch)
                except Exception as e:
                    failures.append((3, e))
            try:
                bench_vision_concurrent(grpc_url, 3, "densenet121",
                                        window_s, windows,
                                        sweep=((1, 8), (1, 16), (8, 4)))
            except Exception as e:
                failures.append((3, e))
        if 4 in wanted:
            try:
                bench_bert_stream(grpc_url, window_s, windows)
            except Exception as e:
                failures.append((4, e))
        if 5 in wanted:
            try:
                bench_llama_stream(grpc_url, windows,
                                   max_tokens=16 if args.quick else 64)
            except Exception as e:
                failures.append((5, e))
            if args.llama_slots > 1:
                # continuous-batching rows: aggregate tok/s at 1/4/8
                # concurrent streams (clipped to the slot count)
                try:
                    bench_llama_multistream(
                        grpc_url, args.llama_config,
                        2 if args.quick else 3,
                        stream_counts=[
                            c for c in (1, 4, 8) if c <= args.llama_slots
                        ],
                        max_tokens=16 if args.quick else 64,
                        quantize=args.llama_quantize)
                except Exception as e:
                    failures.append((5, e))
    finally:
        grpc_f.stop()
        http.stop()
    for config, err in failures:
        print(json.dumps({
            "config": config,
            "error": "".join(
                traceback.format_exception(type(err), err,
                                           err.__traceback__)
            ),
        }), file=sys.stderr, flush=True)
    sys.stdout.flush()
    sys.stderr.flush()
    # hard exit: long runs can leave stray library threads (grpc/jax
    # teardown) that would stall interpreter shutdown after all results
    # are already flushed
    os._exit(1 if failures else 0)


if __name__ == "__main__":
    main()
