"""Continuous-batching decode scheduler tests (tpuserver/scheduler.py).

The contract under test: with greedy decoding, N concurrent served
streams produce TOKEN-IDENTICAL output to N sequential single-stream
runs — through mid-flight admission (more requests than slots), early
EOS retirement with slot reuse, KV park/resume, both frontends, and the
tp-mesh case alongside tests/test_tp_served_server.py.
"""

import json
import queue
import threading

import jax
import numpy as np
import pytest

from tpuserver.core import InferenceServer, InferRequest
from tpuserver.models import llama
from tpuserver.models.llama_serving import LlamaGenerateModel
from tpuserver.parallel import MeshConfig, make_mesh

CFG = llama.tiny(vocab=512)
MAX_SEQ = 64
PROMPTS = [
    np.array([3, 1, 4, 1, 5], dtype=np.int32),
    np.array([9, 8, 7], dtype=np.int32),
    np.array([2, 7, 1, 8, 2, 8], dtype=np.int32),
    np.array([1, 2, 3, 4], dtype=np.int32),
    np.array([42, 17], dtype=np.int32),
]
# varying budgets force retirement at different steps, so later requests
# are admitted mid-flight into freed slots
MAX_TOKENS = [10, 7, 12, 6, 9]


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


def _generate_concurrently(core, prompts, budgets, parameters=None):
    results = [None] * len(prompts)
    errors = []

    def worker(i):
        try:
            results[i] = _generate(core, prompts[i], budgets[i], parameters)
        except Exception as e:  # noqa: BLE001 — surfaced by the assert
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results


@pytest.fixture(scope="module")
def sequential_core():
    """The max_slots=1 degenerate case: the original single-stream path."""
    return InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, decode_chunk=4)
    ])


@pytest.fixture(scope="module")
def scheduled_core():
    """3 slots for 5 requests: admission must happen mid-flight."""
    return InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=3)
    ])


@pytest.fixture(scope="module")
def reference_tokens(sequential_core):
    return [
        _generate(sequential_core, p, n)
        for p, n in zip(PROMPTS, MAX_TOKENS)
    ]


def test_concurrent_streams_match_sequential(
        scheduled_core, reference_tokens):
    """5 concurrent streams over 3 slots == 5 sequential runs, token for
    token (greedy): interleaved batched decode must not change numerics,
    and mid-flight admission must prefill into a freed slot without
    disturbing the other slots' caches."""
    results = _generate_concurrently(scheduled_core, PROMPTS, MAX_TOKENS)
    assert results == reference_tokens
    for toks, budget in zip(results, MAX_TOKENS):
        assert len(toks) == budget


def test_eos_early_retirement_and_slot_reuse(
        scheduled_core, sequential_core, reference_tokens):
    """A stream hitting its eos_id emits that token, stops, and frees its
    slot for a waiting request — and the truncation point is identical
    to the single-stream path's."""
    eos = reference_tokens[0][3]  # greedy token 4 of prompt 0
    seq = _generate(sequential_core, PROMPTS[0], MAX_TOKENS[0],
                    {"eos_id": eos})
    assert seq == reference_tokens[0][:4]

    # concurrently: prompt 0 retires early on EOS while the others run
    # to budget; everyone still matches their sequential tokens
    params = {"eos_id": eos}
    expected = []
    for i, ref in enumerate(reference_tokens):
        cut = [t for t in ref]
        if eos in cut:
            cut = cut[: cut.index(eos) + 1]
        expected.append(cut)
    results = _generate_concurrently(
        scheduled_core, PROMPTS, MAX_TOKENS, params)
    assert results == expected


def test_scheduled_kv_park_and_resume(scheduled_core, sequential_core):
    """Park a slot's cache rows in an XLA shm region at retirement, then
    resume mid-sequence — identical to the single-stream park/resume."""
    from tritonclient.utils import xla_shared_memory as xshm

    outcomes = {}
    for name, core in (("seq", sequential_core), ("sch", scheduled_core)):
        region = "cb_park_" + name
        handle = xshm.create_shared_memory_region(region, 1 << 20)
        try:
            core.register_xla_shm(
                region, xshm.get_raw_handle(handle), 0, 1 << 20)
            first = _generate(
                core, PROMPTS[0], 4, {"kv_cache_region": region})
            assert handle.get_jax_segment(0) is not None
            second = _generate(
                core, np.array(first[-1:], np.int32), 3,
                {
                    "kv_cache_region": region,
                    "kv_cache_resume": True,
                    "kv_cache_position": len(PROMPTS[0]) + 4,
                },
            )
            outcomes[name] = (first, second)
        finally:
            core.unregister_xla_shm(region)
            xshm.destroy_shared_memory_region(handle)
    assert outcomes["sch"] == outcomes["seq"]


def test_scheduler_rejects_overflow(scheduled_core):
    from tpuserver.core import ServerError

    with pytest.raises(ServerError, match="exceeds"):
        _generate(scheduled_core, np.arange(40, dtype=np.int32), 40)


def test_prefill_bucket_preserves_kernel_choice():
    """Admission prompts bucket to powers of two — except where padding
    would flip a pallas-configured model's prefill between dense and the
    flash kernel (different accumulation order could flip a near-tie
    greedy argmax and break token identity with the single-stream
    path)."""
    import dataclasses

    # dense-attention config: everything buckets freely
    assert llama.prefill_bucket(CFG, 512, 3) == 8
    assert llama.prefill_bucket(CFG, 512, 100) == 128
    assert llama.prefill_bucket(CFG, 512, 500) == 512  # capped at max_seq
    # pallas config: T=100 runs dense but its bucket 128 is tileable —
    # padding would switch kernels, so the exact length compiles instead
    pcfg = dataclasses.replace(CFG, attn_impl="pallas")
    assert llama.prefill_bucket(pcfg, 512, 100) == 100
    # short prompts stay dense on both sides of the pad: bucket applies
    assert llama.prefill_bucket(pcfg, 512, 5) == 8


def test_cancelled_stream_frees_slot_and_stops_decoding(monkeypatch):
    """Abandoning a token iterator (client cancel/disconnect) must
    retire its slot within a few steps instead of decoding the full
    budget into a queue nobody reads."""
    import jax

    from tpuserver import scheduler as scheduler_mod
    from tpuserver.scheduler import DecodeScheduler

    params = llama.init_params(jax.random.PRNGKey(0), CFG)
    fns = llama.make_scheduler_fns(CFG, MAX_SEQ, max_slots=2)
    calls = [0]
    dispatch = scheduler_mod._ControlledStep.__call__

    def counting_step(self, *args):
        calls[0] += 1
        return dispatch(self, *args)

    monkeypatch.setattr(scheduler_mod._ControlledStep, "__call__",
                        counting_step)
    sched = DecodeScheduler(fns, params, 2, MAX_SEQ)
    try:
        big_budget = 50
        stream = sched.submit(PROMPTS[0], big_budget)
        next(stream)  # generation is live
        stream.close()  # consumer walks away
        toks = [t for t, _ in sched.submit(PROMPTS[1], 5)]
        assert len(toks) == 5
        # reaping bounds the wasted steps: well under the abandoned
        # stream's 50-token budget (a handful for it + 5-ish for the
        # second request + pipeline slack)
        assert calls[0] < 30, calls[0]
    finally:
        sched.close()


def test_scheduler_closes_cleanly():
    from tpuserver.core import ServerError

    model = LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2)
    core = InferenceServer([model])
    toks = _generate(core, PROMPTS[1], 3)
    assert len(toks) == 3
    core.close()
    # SchedulerClosed surfaces through infer_stream's ServerError wrap
    with pytest.raises(ServerError, match="shut down"):
        _generate(core, PROMPTS[1], 3)


def test_nan_poisoned_neighbor_leaves_cobatched_tokens_identical(
        scheduled_core, reference_tokens):
    """Quarantine determinism: greedy tokens of co-batched streams are
    byte-identical with and without a NaN-poisoned neighbor.  The
    poisoned slot fails alone with the typed SlotQuarantined (422); the
    batched step's row-independent math means the survivors never see
    the poison."""
    from tpuserver import faults
    from tpuserver.scheduler import SlotQuarantined

    model = scheduled_core._models["llama_generate"]
    # warm: the scheduler exists and slot 0 is free
    _generate(scheduled_core, PROMPTS[3], 2)
    sched = model._scheduler
    victim = sched.submit(PROMPTS[0], MAX_TOKENS[0])
    next(victim)  # victim is live in slot 0
    try:
        # poison slot 0's logits row on the next step
        faults.install("scheduler.step", mode="nan", times=1, delay=0)
        survivors = _generate_concurrently(
            scheduled_core, PROMPTS[1:3], MAX_TOKENS[1:3])
        assert survivors == reference_tokens[1:3]
        with pytest.raises(SlotQuarantined):
            list(victim)
    finally:
        faults.clear("scheduler.step")
    # the loop survived: no restart, healthy, slot reusable with
    # identical numerics
    stats = sched.stats()
    assert stats["restarts"] == 0 and stats["quarantined"] == 1
    assert model.healthy()
    assert _generate(
        scheduled_core, PROMPTS[0], MAX_TOKENS[0]) == reference_tokens[0]


# -- through the real frontends ----------------------------------------------


def test_grpc_single_stream_interleaves_generations(reference_tokens):
    """Several generations submitted on ONE bidi gRPC stream decode
    interleaved (concurrent_decoupled routes them off the ordered path)
    and demultiplex by request id to the sequential tokens."""
    import tritonclient.grpc as grpcclient

    from tpuserver.grpc_frontend import GrpcFrontend

    core = InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=4)
    ])
    frontend = GrpcFrontend(core, port=0).start()
    try:
        client = grpcclient.InferenceServerClient(
            "127.0.0.1:{}".format(frontend.port))
        done = queue.Queue()
        client.start_stream(lambda result, error: done.put((result, error)))
        try:
            n_req = 3
            for i in range(n_req):
                p_in = grpcclient.InferInput(
                    "PROMPT_IDS", [len(PROMPTS[i])], "INT32")
                p_in.set_data_from_numpy(PROMPTS[i])
                m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
                m_in.set_data_from_numpy(
                    np.array([MAX_TOKENS[i]], dtype=np.int32))
                client.async_stream_infer(
                    "llama_generate", [p_in, m_in], request_id=str(i),
                    enable_empty_final_response=True)
            tokens = {str(i): [] for i in range(n_req)}
            finals = 0
            while finals < n_req:
                result, error = done.get(timeout=120)
                assert error is None, repr(error)
                resp = result.get_response()
                final = resp.parameters.get("triton_final_response")
                if final and final.bool_param:
                    finals += 1
                    continue
                tokens[resp.id].append(int(result.as_numpy("TOKEN")[0]))
        finally:
            client.stop_stream()
            client.close()
    finally:
        frontend.stop()
    for i in range(n_req):
        assert tokens[str(i)] == reference_tokens[i][:MAX_TOKENS[i]], i


def test_http_generate_stream_matches_sequential(reference_tokens):
    """/generate_stream chunks one SSE event per token; /generate folds
    the burst into one JSON body — both match the sequential tokens."""
    import http.client

    from tpuserver.http_frontend import HttpFrontend

    core = InferenceServer([
        LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2)
    ])
    frontend = HttpFrontend(core, port=0).start()
    try:
        body = json.dumps({
            "inputs": [
                {"name": "PROMPT_IDS", "datatype": "INT32",
                 "shape": [len(PROMPTS[0])],
                 "data": PROMPTS[0].tolist()},
                {"name": "MAX_TOKENS", "datatype": "INT32", "shape": [1],
                 "data": [6]},
            ]
        })
        conn = http.client.HTTPConnection("127.0.0.1", frontend.port)
        try:
            conn.request(
                "POST", "/v2/models/llama_generate/generate", body,
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            merged = json.loads(resp.read())
            token_out = next(
                o for o in merged["outputs"] if o["name"] == "TOKEN")
            assert token_out["data"] == reference_tokens[0][:6]

            conn.request(
                "POST", "/v2/models/llama_generate/generate_stream", body,
                {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type") == "text/event-stream"
            tokens = []
            ids = []
            for line in resp.read().decode("utf-8").split("\n"):
                if line.startswith("id: "):
                    ids.append(line[len("id: "):])
                if not line.startswith("data: "):
                    continue
                payload = json.loads(line[len("data: "):])
                assert "error" not in payload, payload
                for out in payload.get("outputs", []):
                    if out["name"] == "TOKEN":
                        tokens.append(out["data"][0])
            assert tokens == reference_tokens[0][:6]
            # resumable-stream contract: every event carries an SSE id
            # "<generation_id>/<seq>" with contiguous 0-based seqs
            assert len(ids) == len(tokens)
            gen_ids = {i.rsplit("/", 1)[0] for i in ids}
            assert len(gen_ids) == 1
            assert [int(i.rsplit("/", 1)[1]) for i in ids] == list(
                range(len(tokens)))
        finally:
            conn.close()
    finally:
        frontend.stop()


# -- tensor-parallel (alongside tests/test_tp_served_server.py) --------------


@pytest.fixture(scope="module")
def tp_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return make_mesh(MeshConfig(dp=1, sp=1, tp=4), jax.devices()[:4])


def test_tp_scheduled_matches_tp_sequential(tp_mesh):
    """Continuous batching over a tp mesh (kv-head-sharded slotted cache)
    reproduces the tp single-stream path token for token.  The reference
    is the SAME mesh's sequential model — sharded collectives may
    reorder float accumulation vs single-device, so tp-vs-tp is the
    apples-to-apples identity this test pins."""
    seq_core = InferenceServer([
        LlamaGenerateModel(
            cfg=CFG, max_seq=MAX_SEQ, decode_chunk=4, mesh=tp_mesh)
    ])
    budgets = [8, 8, 8, 8]
    ref = [
        _generate(seq_core, p, n)
        for p, n in zip(PROMPTS[:4], budgets)
    ]
    sch_core = InferenceServer([
        LlamaGenerateModel(
            cfg=CFG, max_seq=MAX_SEQ, max_slots=3, mesh=tp_mesh)
    ])
    results = _generate_concurrently(sch_core, PROMPTS[:4], budgets)
    assert results == ref
