"""The AFMoE block (the Trinity family) through the one decoder family:
sigmoid-routed experts with a shared expert, gated QK-normed attention,
window layers with rotary positions beside position-free full layers,
served from a page pool of two classes.

Everything runs the tiny preset (``llama.tiny_afmoe``: a dense window
layer, then routed layers window / full / window; 16 experts, top-4;
window 32) on the CPU with interpreted kernels.  The served path is
compared with the plain reference (``tests/reference_afmoe.py``) in
float32: in bf16 at these toy widths a near-tie in the router flips an
expert and moves a logit by tenths, which says nothing of the equations.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference_afmoe as ref  # noqa: E402
from tpuserver.models import llama  # noqa: E402
from tpuserver.models.llama_serving import LlamaGenerateModel  # noqa: E402
from tpuserver.ops import flash_attention, paged_decode_attention  # noqa: E402
from tpuserver.scheduler import DecodeScheduler  # noqa: E402

PAGE = 16
MAX_SEQ = 384       # three 128-token kernel blocks; the ring holds two


def f32(cfg):
    return dataclasses.replace(cfg, dtype=jnp.float32, attn_impl="pallas",
                               decode_impl="pallas")


def f32_params(cfg, key=1):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        llama.init_params(jax.random.PRNGKey(key), cfg))


def shape_of(cfg):
    m = cfg.moe
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        window=cfg.window, layer_types=cfg.layer_types,
        ffn_types=cfg.ffn_types, top_k=m.top_k, route_norm=m.route_norm,
        route_scale=m.route_scale, first=m.first,
        embed_scale=cfg.embed_scale)


CFG = f32(llama.tiny_afmoe(vocab=512))
PARAMS = f32_params(llama.tiny_afmoe(vocab=512))


@pytest.fixture(scope="module")
def served():
    """One served model for the module: the scheduler path, handed its
    weights through ``params=``."""
    model = LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=3,
                               page_size=PAGE, params=PARAMS)
    with jax.default_matmul_precision("highest"):
        model.warmup()
        yield model
    model.close()


def reference_logprobs(prompt, tokens):
    row = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(ref.logits(PARAMS, row, shape_of(CFG)))
    logits = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return logits, jax.nn.log_softmax(logits, -1)


def generate(model, requests):
    streams = [model._scheduler.submit(p, n) for p, n in requests]
    return [list(s) for s in streams]


def test_forward_matches_reference():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG.vocab, (1, 80), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(llama.forward(PARAMS, jnp.asarray(tokens), CFG))[0]
    want = np.asarray(ref.logits(PARAMS, tokens[0], shape_of(CFG)))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_served_prefill_and_paged_decode_match_reference(served):
    """Prefill (flash kernel with a window at 128 and 256 tokens, dense at
    16) then paged decode through both page classes, across the window
    and across the ring's wrap at 256, co-batched: every served token is
    the reference's argmax and its logprob the reference's."""
    rng = np.random.default_rng(1)
    requests = [(rng.integers(0, CFG.vocab, (n,), dtype=np.int32), m)
                for n, m in ((256, 40), (16, 40), (128, 24))]
    with jax.default_matmul_precision("highest"):
        outs = generate(served, requests)
    for (prompt, n), out in zip(requests, outs):
        tokens = [t for t, _ in out]
        assert len(tokens) == n
        logits, logp = reference_logprobs(prompt, tokens)
        assert list(logits.argmax(-1)) == tokens
        np.testing.assert_allclose(
            [lp for _, lp in out], logp[np.arange(n), tokens], atol=2e-4)
    stats = served.scheduler_stats()
    assert stats["pages_free"] == stats["pages_total"]
    assert stats["window_pages_free"] == stats["window_pages_total"] > 0


def test_long_decode_reuses_ring_entries_in_place(served):
    """A short prompt decoding past one ring (256 tokens; 16 + 264 = 18
    pages): logical page p + ring lands in page p's entry, which the
    sequence kept."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, CFG.vocab, (16,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        (out,) = generate(served, [(prompt, 264)])
    tokens = [t for t, _ in out]
    logits, logp = reference_logprobs(prompt, tokens)
    assert list(logits.argmax(-1)) == tokens
    np.testing.assert_allclose(
        [lp for _, lp in out], logp[np.arange(264), tokens], atol=2e-4)


def test_generate_stream_over_grpc(served):
    """The same model behind the real frontend: ``generate_stream`` of the
    public client returns the scheduler's tokens."""
    import tritonclient.grpc as grpcclient

    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG.vocab, (40,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        (want,) = generate(served, [(prompt, 6)])
        core = InferenceServer([served])
        frontend = GrpcFrontend(core, port=0).start()
        try:
            client = grpcclient.InferenceServerClient(frontend.url)
            p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)], "INT32")
            p_in.set_data_from_numpy(prompt)
            m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
            m_in.set_data_from_numpy(np.array([6], np.int32))
            stream = client.generate_stream(
                served.name, [p_in, m_in], resume=False)
            got = [int(r.as_numpy("TOKEN")[0]) for r in stream]
            stream.close()
            client.close()
        finally:
            frontend.stop()
    assert got == [t for t, _ in want]


def test_shares_add_up_to_the_uncut_layer():
    """Expert parallelism: the routed parts that 8 shares of 2 experts
    compute, with the shared expert counted once, add up to what the
    uncut reference gives for the whole layer."""
    whole = f32(llama.tiny_afmoe(vocab=512))
    layer = PARAMS["layers"][1]
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(2, 24, whole.d_model)), jnp.float32)
    want = np.stack([np.asarray(ref.routed_ffn(layer, row, shape_of(whole)))
                     for row in y])
    shared = np.stack([np.asarray(ref.swiglu(
        row, layer["ws_gate"], layer["ws_up"], layer["ws_down"]))
        for row in y])
    total = shared.copy()
    with jax.default_matmul_precision("highest"):
        for share in range(8):
            cfg = f32(llama.tiny_afmoe(vocab=512, first=2 * share, count=2))
            held = dict(layer)
            for leaf in ("we_gate", "we_up", "we_down"):
                held[leaf] = layer[leaf][2 * share:2 * share + 2]
            stats = []
            part = np.asarray(llama._moe_ffn(held, y, cfg, stats=stats))
            total += part - shared
            # the share's own reference agrees with the share
            s = dict(shape_of(cfg))
            np.testing.assert_allclose(part, np.stack([np.asarray(
                ref.routed_ffn(held, row, s)) for row in y]), atol=2e-5)
            pairs, hit = (int(v) for v in stats[0])
            assert 0 <= hit <= min(2, pairs)
    np.testing.assert_allclose(total, want, atol=5e-5)
    # a share of the same seed holds the same experts the whole layer has
    share = llama.init_params(
        jax.random.PRNGKey(1), llama.tiny_afmoe(vocab=512, first=6, count=2))
    np.testing.assert_array_equal(
        np.asarray(share["layers"][1]["we_up"], np.float32),
        np.asarray(layer["we_up"][6:8]))


def dense_window_attention(q, k, v, window):
    """q [T, H, D] against k/v [T, Hkv, D], causal, 0 <= i - j < window."""
    t, h, d = q.shape
    n_rep = h // k.shape[1]
    k, v = np.repeat(k, n_rep, 1), np.repeat(v, n_rep, 1)
    s = np.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    s = np.where((j <= i) & (i - j < window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("window,block_q,block_k", [
    (32, 64, 64), (100, 64, 128), (256, 128, 64), (1000, 64, 64),
    (100, 256, 64), (48, 32, 128), (66, 64, 64)])
def test_flash_attention_window_matches_dense(window, block_q, block_k):
    rng = np.random.default_rng(5)
    t, h, d = 256, 4, 32
    q, k, v = (rng.normal(size=(1, t, h, d)).astype(np.float32)
               for _ in range(3))
    got = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
        block_q=block_q, block_k=block_k, window=window))[0]
    np.testing.assert_allclose(
        got, dense_window_attention(q[0], k[0], v[0], window), atol=2e-5)


@pytest.mark.parametrize("window", [32, 100, 200])
def test_paged_decode_attention_window_matches_dense(window):
    """The decode kernel over a RING table: rows at lengths inside the
    first window, past it, and past the ring's wrap."""
    rng = np.random.default_rng(6)
    h, hkv, d, block = 8, 4, 16, 128
    ring = (-(-window // block) + 1) * (block // PAGE)   # pages
    lengths = np.array([1, 20, window + 5, 300, 517, 640], np.int32)
    rows, n_pages = len(lengths), 200
    keys = rng.normal(size=(rows, 640, hkv, d)).astype(np.float32)
    vals = rng.normal(size=(rows, 640, hkv, d)).astype(np.float32)
    q = rng.normal(size=(rows, h, d)).astype(np.float32)
    pool = np.zeros((1, 2, n_pages, PAGE, hkv, d), np.float32)
    tables = np.zeros((rows, ring), np.int32)
    free = list(rng.permutation(n_pages))
    for r, n in enumerate(lengths):
        start = max(0, n - window)
        for p in range(start // PAGE, (n - 1) // PAGE + 1):
            pid = free.pop()
            tables[r, p % ring] = pid
            pool[0, 0, pid] = keys[r, p * PAGE:(p + 1) * PAGE]
            pool[0, 1, pid] = vals[r, p * PAGE:(p + 1) * PAGE]
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), 0, jnp.asarray(tables),
        jnp.asarray(lengths), block_k=block,
        starts=jnp.asarray(np.maximum(lengths - window, 0))))
    for r, n in enumerate(lengths):
        lo = max(0, n - window)
        want = dense_window_attention(
            np.concatenate([np.zeros((n - 1, h, d), np.float32), q[r:r + 1]]),
            keys[r, :n], vals[r, :n], window)[-1]
        np.testing.assert_allclose(got[r], want, atol=2e-5, err_msg=str(n))
        assert lo < n


def test_window_pages_return_during_decode_and_none_leak(served):
    """A 256-token prompt admits only its last window into the window
    class; as decode moves the window on, pages behind it go back to the
    allocator before the stream ends, and the end returns the rest."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, CFG.vocab, (256,), dtype=np.int32)
    sched = served._scheduler
    total = sched.stats()["window_pages_total"]
    held = []
    with jax.default_matmul_precision("highest"):
        for _ in sched.submit(prompt, 60):
            s = sched.stats()
            held.append((total - s["window_pages_free"],
                         s["pages_total"] - s["pages_free"]))
    window_held, full_held = zip(*held)
    # full class: the whole span, 256 + 60 tokens = 20 pages, throughout
    assert max(full_held) == 20
    # window class: the last window of the prompt and what decode adds:
    # logical pages 14..19 (6), never the prompt's 16
    assert max(window_held) == 6
    # ... and fewer before the end: pages 14 and 15 fell behind
    assert min(window_held[:-1]) <= 4
    s = sched.stats()
    assert s["window_pages_free"] == total
    assert s["pages_free"] == s["pages_total"]
    assert s["window_skipped_tokens"] > 0
    assert 0 < s["moe_experts_hit"] <= s["moe_local_pairs"]
    assert s["moe_experts_hit"] <= s["moe_layer_steps"] * CFG.moe.held


def test_window_class_exhaustion_is_a_typed_shed():
    from tpuserver.scheduler import AdmissionQueueFull

    model = LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=2,
                               page_size=PAGE, params=PARAMS,
                               kv_window_pages=16)
    model.warmup()
    try:
        sched = model._scheduler
        prompt = np.arange(1, 200, dtype=np.int32)
        first = sched.submit(prompt, 150)      # takes the whole ring
        next(first)
        with pytest.raises(AdmissionQueueFull, match="window-class"):
            list(sched.submit(prompt[:100], 50))
        first.close()
    finally:
        model.close()
    stats = sched.stats()
    assert stats["window_pages_free"] == stats["window_pages_total"] == 16


REFUSED = {
    "park": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, on_finish=lambda cache: None),
    "resume": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, resume_cache=np.zeros(1), resume_pos=3),
    "kv_export": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, kv_export=True, generation_id="g"),
    "kv_attach": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, attach_cache=np.zeros(1), attach_pos=2),
    "int8": lambda fns: LlamaGenerateModel(cfg=CFG, quantize=True),
    "tensor_parallel": lambda fns: llama.param_specs(CFG),
    "no_kernel_block": lambda fns: llama.make_scheduler_fns(
        CFG, 64, 2, page_size=PAGE),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_two_page_classes_refuse_by_name(what):
    """What still assumes one page table a sequence (park / resume,
    KV export / attach) and what was written for the plain block
    (int8, tensor parallelism) is refused with a typed error where it is
    asked for, never served wrong."""
    fns = llama.make_scheduler_fns(CFG, MAX_SEQ, 2, page_size=PAGE)
    assert fns["window_class"]["ring"] == 16
    assert not fns["span_safe"]          # no radix sharing, no chunks
    assert "gather" not in fns
    with pytest.raises(llama.UnsupportedArchitecture):
        REFUSED[what](fns)


def test_plain_block_lowers_without_the_new_structure():
    """Mistral's / Llama's configuration has no window layer and no
    routed layer: its step takes ONE pool array and one table, returns
    four results, and its lowering names none of the new scopes or
    kernels (the bitwise A/B tests of tests/test_paged_kv.py hold the
    values)."""
    cfg = dataclasses.replace(llama.tiny(vocab=512), decode_impl="pallas")
    assert cfg.plain and not cfg.window_layers
    fns = llama.make_scheduler_fns(cfg, 128, 2, page_size=PAGE)
    assert fns["window_class"] is None
    pages = jax.eval_shape(fns["init_cache"])
    assert not isinstance(pages, dict)
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    b8 = jax.ShapeDtypeStruct((2,), jnp.bool_)
    args = (params, pages, jax.eval_shape(fns["init_logits"]), i32(2, 8),
            i32(2), b8, i32(2), b8)
    assert len(jax.tree_util.tree_leaves(
        fns["step"].lower(*args).out_info)) == 4
    # the step's Pallas kernels: the decode kernel, not the experts'
    text = str(jax.make_jaxpr(fns["step"])(*args))
    assert "pallas_call[" in text
    assert "name=paged_decode_attention" in text
    assert "moe_grouped_matmul" not in text


def test_the_two_copies_of_the_reference_agree():
    """``tests/reference_afmoe.py`` and ``benchmark/reference_afmoe.py``
    are the same equations: one layer of each kind, same weights, same
    input, float32 and the int8 control."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark"))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_reference_afmoe", os.path.join(sys.path[0],
                                              "reference_afmoe.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(48, CFG.d_model)), jnp.float32)
    s = shape_of(CFG)
    for i, w in enumerate(PARAMS["layers"]):
        window = CFG.layer_window(i)
        for precision in ("f32", "int8"):
            a = ref.layer(w, x, s, window, CFG.layer_moe(i), precision)
            b = bench.layer(w, x, s, window, CFG.layer_moe(i), precision)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
