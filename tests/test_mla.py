"""Multi-head latent attention over a latent page class, and the
group-limited router (the DeepSeek-V3 family, ``llama.tiny_deepseek``):
the program against the benchmark's plain reference
(``benchmark/reference_deepseek.py``: expanded form, float32, no kernel,
no cache), on seeded weights made by ``benchmark/weights_deepseek.py``.

Everything compares in float32 at ``highest`` matmul precision: at toy
widths a bf16 rounding flips a router near-tie and moves a logit by
tenths.  The tolerances are float32's own: the absorbed form (decode)
and the expanded form (prefill, reference) are the same sums in another
order, exact in real arithmetic; over 3 layers of sums of 16-64 terms
the logits (of size ~1) differ by a few 1e-6, and 2e-4 leaves the room
the AFMoE and SDAR tests leave while a wrong scale, a missing norm or a
rotated pair the wrong way round moves them by 1e-2 and more.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(os.path.join(REPO, "benchmark"))

import reference_deepseek as ref  # noqa: E402
import weights_deepseek  # noqa: E402
from tpuserver.models import llama  # noqa: E402
from tpuserver.models.llama_serving import LlamaGenerateModel  # noqa: E402
from tpuserver.ops import flash_attention, latent_decode_attention  # noqa: E402
from tpuserver.scheduler import DecodeScheduler  # noqa: E402

PAGE, MAX_SEQ, SEED = 16, 384, 5
CFG = dataclasses.replace(
    llama.tiny_deepseek(vocab=512), dtype=jnp.float32, attn_impl="pallas",
    decode_impl="pallas")
PUBLISHED = llama.MLAConfig(
    q_lora=1536, kv_lora=512, d_nope=128, d_rope=64, d_v=128,
    rope_factor=40.0, rope_orig_max=4096, beta_fast=32.0, beta_slow=1.0,
    mscale=1.0, mscale_all_dim=1.0)


def sizes_of(cfg):
    """``cfg`` as the benchmark's builder states a configuration
    (``models/deepseek_generate.sizes_of``)."""
    m, a = cfg.moe, cfg.mla
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": m.d_expert,
        "num_attention_heads": cfg.n_heads, "q_lora_rank": a.q_lora,
        "kv_lora_rank": a.kv_lora, "qk_nope_head_dim": a.d_nope,
        "qk_rope_head_dim": a.d_rope, "v_head_dim": a.d_v,
        "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "n_routed_experts": m.held, "n_shared_experts": m.n_shared,
        "num_experts_per_tok": m.top_k, "n_group": m.n_group,
        "topk_group": m.topk_group, "norm_topk_prob": m.route_norm,
        "routed_scaling_factor": m.route_scale,
        "rope_factor": a.rope_factor, "rope_orig_max": a.rope_orig_max,
        "beta_fast": a.beta_fast, "beta_slow": a.beta_slow,
        "mscale": a.mscale, "mscale_all_dim": a.mscale_all_dim,
        "ffn_types": list(cfg.ffn_types), "router_experts": m.n_experts,
        "expert_first": m.first,
    }


def seeded_params(cfg, seed=SEED):
    """The served tree in float32 from the benchmark's generator, its
    router biases solved as the benchmark solves them."""
    sizes = sizes_of(cfg)
    key = weights_deepseek.root_key(seed)
    biases = ref.router_biases(seed, sizes)
    # jitted, as the reference makes them: compiled, the division by a
    # root that is no power of two is a multiplication, and a value on a
    # bf16 rounding tie falls the other way than it does op by op
    tree = jax.jit(lambda: weights_deepseek.ends(key, sizes, jnp.float32))()
    tree["layers"] = [
        jax.jit(lambda b, i=i: weights_deepseek.layer(
            key, sizes, i, jnp.float32, b))(b)
        for i, b in enumerate(biases)]
    return tree


SIZES = sizes_of(CFG)


@pytest.fixture(scope="module")
def params():
    with jax.default_matmul_precision("highest"):
        return seeded_params(CFG)


def init_f32(cfg, key=1):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        llama.init_params(jax.random.PRNGKey(key), cfg))


# -- (5) YaRN ------------------------------------------------------------------


def test_yarn_frequencies_and_scale_of_the_published_parameters():
    """Hand-computed: the ramp runs between dimensions 10 and 23 of the
    32 (``64 ln(4096 / (2 pi r)) / (2 ln 10000)`` = 10.47 at r = 32 and
    22.52 at r = 1), so frequency 0 is the extrapolated 1 and frequency
    31 the interpolated ``10000 ** (-62/64) / 40``; m = 0.1 ln 40 + 1 =
    1.36889, m^2 = 1.8738, the scale 192 ** -0.5 * m^2 = 0.135234."""
    inv = llama.yarn_inv_freq(PUBLISHED, 10000.0)
    assert inv.shape == (32,)
    assert inv[0] == 1.0
    np.testing.assert_allclose(inv[31], 10000.0 ** (-62 / 64) / 40, rtol=1e-12)
    np.testing.assert_allclose(inv[31], 3.33380e-6, rtol=1e-5)
    # below the ramp extrapolated, above it interpolated, between blended
    base = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-12)
    ramp = (16 - 10) / (23 - 10)
    np.testing.assert_allclose(
        inv[16], base[16] / 40 * ramp + base[16] * (1 - ramp), rtol=1e-12)
    m = llama.yarn_mscale(40.0, 1.0)
    np.testing.assert_allclose(m, 1.36889, rtol=1e-5)
    np.testing.assert_allclose(m * m, 1.8738, rtol=1e-4)
    np.testing.assert_allclose(
        llama.mla_softmax_scale(PUBLISHED), 0.135234, rtol=1e-5)
    # the reference's own arithmetic, written apart, agrees
    s = ref.shape_of(dict(SIZES, qk_rope_head_dim=64, qk_nope_head_dim=128))
    np.testing.assert_allclose(ref.yarn_frequencies(s), inv, rtol=1e-12)
    np.testing.assert_allclose(ref.softmax_scale(s), 0.135234, rtol=1e-5)
    # a row holds the 576 values in 640 lanes
    assert (PUBLISHED.width, PUBLISHED.row) == (576, 640)


# -- (3) the router ------------------------------------------------------------


def crafted(logits, m):
    """Router inputs under which the scores are ``sigmoid(logits)``: an
    identity router and no bias."""
    e = m.n_experts
    return ({"router": jnp.eye(e, dtype=jnp.float32),
             "router_bias": jnp.zeros((e,), jnp.float32)},
            jnp.asarray(logits, jnp.float32))


def test_route_keeps_groups_by_their_top_two_and_ties_go_low():
    m = llama.MoEConfig(n_experts=16, top_k=4, route_scale=2.5, n_group=4,
                        topk_group=2)
    low = -5.0
    logits = np.full((3, 16), low, np.float32)
    # row 0: group 0 holds the single best expert, groups 1 and 2 the
    # best pairs: kept by the top-2 SUM (1.64, 1.54 against 0.96)
    logits[0, 0] = 3.0
    logits[0, 4:6] = 1.5
    logits[0, 8:10] = 1.2
    # row 1: all alike: groups 0 and 1, experts 0..3
    logits[1] = 0.3
    # row 2: groups 2 and 3 tie for the second place: the lower wins
    logits[2, 12:14] = 2.0
    logits[2, 4:6] = 1.0
    logits[2, 8:10] = 1.0
    w, x = crafted(logits, m)
    chosen, weights = llama._route(w, x, m)
    chosen = np.sort(np.asarray(chosen), axis=1)
    assert chosen[0].tolist() == [4, 5, 8, 9]
    assert chosen[1].tolist() == [0, 1, 2, 3]
    assert chosen[2].tolist() == [4, 5, 12, 13]
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-6)
    # the reference's router, written apart, chooses and weighs alike
    s = {"top_k": 4, "n_group": 4, "topk_group": 2, "route_norm": True,
         "route_scale": 2.5}
    r_chosen, r_weights = ref.routing(w, x, s)
    order = np.argsort(np.asarray(r_chosen), axis=1)
    assert np.take_along_axis(
        np.asarray(r_chosen), order, 1).tolist() == chosen.tolist()
    np.testing.assert_allclose(
        np.sort(np.asarray(r_weights), 1), np.sort(np.asarray(weights), 1),
        rtol=1e-6)


@pytest.mark.parametrize("score_func", ["sigmoid", "softmax"])
def test_one_group_is_bit_equal_to_the_ungrouped_route(score_func):
    """``n_group = topk_group = 1`` is the router the accepted
    configurations trace: the same choice and weights, bit for bit, as
    the formula written out."""
    m = llama.MoEConfig(n_experts=16, top_k=4, route_scale=2.448,
                        score_func=score_func)
    rng = np.random.default_rng(2)
    w = {"router": jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
         "router_bias": jnp.asarray(0.1 * rng.normal(size=(16,)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32)
    chosen, weights = llama._route(w, x, m)
    scores = jnp.dot(x, w["router"], precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(scores, -1) if score_func == "softmax"
              else jax.nn.sigmoid(scores))
    _, want = lax.top_k(scores + w["router_bias"], 4)
    picked = jnp.take_along_axis(scores, want, 1)
    picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * 2.448
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    assert np.array_equal(np.asarray(weights), np.asarray(picked))


# -- (4) the share ties to the model ---------------------------------------------


def test_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 of the 16 experts: their partial results, the
    shared expert counted once, add up to the uncut reference's routed
    feed-forward (every expert's values depend on its own id alone)."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(2, 24, 64)), jnp.float32)
    whole = dataclasses.replace(llama.tiny_deepseek(), dtype=jnp.float32)
    w = init_f32(whole)["layers"][1]
    s = ref.shape_of(sizes_of(whole))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.routed_ffn(w, row, s) for row in h])
        shared = jnp.stack([ref.swiglu(row, w["ws_gate"], w["ws_up"],
                                       w["ws_down"]) for row in h])
        total, pairs = -3 * shared, 0
        for first in (0, 4, 8, 12):
            cut = dataclasses.replace(
                llama.tiny_deepseek(first=first, count=4), dtype=jnp.float32)
            stats = []
            total = total + llama._moe_ffn(
                init_f32(cut)["layers"][1], h, cut, stats=stats)
            pairs += int(stats[0][0])
    assert pairs == 2 * 24 * 4       # every pair is held by one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)


# -- (6) (7) the kernels ---------------------------------------------------------


def test_latent_decode_kernel_matches_dense_over_unequal_rows():
    """One call, rows of 1 position, of exactly one block, of a length
    whose last page is partly filled, and of three blocks: each page is
    read once and serves as key (all lanes) and value (the leading
    lanes)."""
    rng = np.random.default_rng(4)
    heads, row, d_v, block, n_pages = 8, 128, 32, 128, 90
    lengths = np.array([1, 128, 37, 300, 384], np.int32)
    rows = len(lengths)
    latents = rng.normal(size=(rows, MAX_SEQ, row)).astype(np.float32)
    latents[:, :, 40:] = 0.0                       # the row's padding
    q = rng.normal(size=(rows, heads, row)).astype(np.float32)
    pool = rng.normal(size=(2, n_pages, PAGE, row)).astype(np.float32)
    tables = np.zeros((rows, MAX_SEQ // PAGE), np.int32)
    free = list(rng.permutation(n_pages))
    for r, n in enumerate(lengths):
        for p in range(-(-n // PAGE)):
            tables[r, p] = free.pop()
            pool[1, tables[r, p]] = latents[r, p * PAGE:(p + 1) * PAGE]
    got = np.asarray(latent_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(tables),
        jnp.asarray(lengths), d_v=d_v, scale=0.21, block_k=block))
    assert got.shape == (rows, heads, d_v)
    for r, n in enumerate(lengths):
        s = q[r] @ latents[r, :n].T * 0.21
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ latents[r, :n, :d_v]
        np.testing.assert_allclose(got[r], want, atol=2e-5, err_msg=str(n))


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 256),
                                             (256, 128), (64, 256)])
def test_flash_attention_with_a_value_size_of_its_own(block_q, block_k):
    """Keys of 24, values of 16 (the expanded form's 192 / 128)."""
    rng = np.random.default_rng(5)
    q, k = (jnp.asarray(rng.normal(size=(2, 256, 4, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, scale=0.3, block_q=block_q,
                          block_k=block_k)
    want = llama._dense_causal(q, k, v, 1, scale=0.3)
    assert got.shape == (2, 256, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- (1) absorbed = expanded ------------------------------------------------------


def test_absorbed_decode_equals_expanded_attention(params):
    """One layer, one decode position a row: the query carried into the
    latent space, the kernel over the paged latents and the value
    up-projection after it (what the step runs) against the expanded
    dense form over the same latents (what the reference runs)."""
    layer = params["layers"][1]
    rng = np.random.default_rng(6)
    lengths = np.array([200, 77], np.int32)
    h = jnp.asarray(rng.normal(size=(2, MAX_SEQ, 64)), jnp.float32)
    pos = jnp.tile(jnp.arange(MAX_SEQ)[None], (2, 1))
    with jax.default_matmul_precision("highest"):
        q_all, latents = llama._mla_project(layer, h, pos, CFG, CFG.n_heads)
        q = jnp.stack([q_all[r, n - 1] for r, n in enumerate(lengths)])[:, None]
        k, v = llama._mla_expand(layer, latents, CFG)
        want = llama._attend_cached(
            q, k, v, jnp.asarray(lengths - 1)[:, None], jnp.asarray(lengths),
            1, scale=llama.mla_softmax_scale(CFG.mla))
        pool = latents.reshape(1, 2 * MAX_SEQ // PAGE, PAGE, -1)
        tables = jnp.arange(2 * MAX_SEQ // PAGE, dtype=jnp.int32).reshape(2, -1)
        u = latent_decode_attention(
            llama._mla_absorb_q(layer, q, CFG)[:, 0], pool, 0, tables,
            jnp.asarray(lengths), d_v=CFG.mla.kv_lora,
            scale=llama.mla_softmax_scale(CFG.mla), block_k=128)
        got = llama._mla_absorb_out(layer, u[:, None], CFG)
    assert latents.shape[-1] == CFG.mla.row == 128
    assert not np.asarray(latents[..., CFG.mla.width:]).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- (2) the served path against the reference -----------------------------------


def reference_logits(prompt, tokens):
    row = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None]
    row = np.pad(row, ((0, 0), (0, MAX_SEQ - row.shape[1])))
    return ref.decoder_logits(SEED, SIZES, row, [len(prompt) - 1],
                              len(tokens))[0]


def test_forward_matches_reference(params):
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, CFG.vocab, (1, 128), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(llama.forward(params, jnp.asarray(tokens), CFG))[0]
    want = ref.decoder_logits(SEED, SIZES, tokens, [0], 128)[0]
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_prefill_then_paged_decode_match_reference(params):
    """Through the paged scheduler functions: a prefill (the flash kernel
    at the expanded sizes at 128 tokens, dense at 40), its admission
    into the latent page class, then decode steps of both rows at once
    through the latent kernel: LOGITS against the reference's one
    teacher-forced forward, not tokens."""
    fns = llama.make_scheduler_fns(CFG, MAX_SEQ, 3, page_size=PAGE)
    assert fns["decode_attention"] == "paged_kernel"
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, CFG.vocab, (n,), dtype=np.int32)
               for n in (128, 40)]
    fed = [rng.integers(0, CFG.vocab, (12,), dtype=np.int32) for _ in prompts]
    pages, logits = fns["init_cache"](), fns["init_logits"]()
    assert pages.shape == (3, fns["n_pages"], PAGE, 128)
    ppseq, sentinel = fns["pages_per_seq"], fns["n_pages"]
    tables = np.full((3, ppseq), sentinel, np.int32)
    got = [[], []]
    with jax.default_matmul_precision("highest"):
        for slot, prompt in enumerate(prompts):
            bucket = fns["prefill_bucket"](len(prompt))
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :len(prompt)] = prompt
            row_logits, slot_cache = fns["prefill"](
                params, fns["init_slot_cache"](), jnp.asarray(padded),
                len(prompt))
            got[slot].append(np.asarray(row_logits[0]))
            tables[slot] = np.arange(ppseq) + slot * ppseq
            pages, logits = fns["admit"](
                pages, logits, slot_cache, row_logits,
                jnp.asarray(tables[slot]), slot)
        pos = np.array([len(p) for p in prompts] + [MAX_SEQ], np.int32)
        active = np.array([True, True, False])
        for k in range(12):
            forced = np.array([fed[0][k], fed[1][k], 0], np.int32)
            _, _, logits, pages, moe = fns["step"](
                params, pages, logits, jnp.asarray(tables), jnp.asarray(pos),
                jnp.asarray(active), jnp.asarray(forced), jnp.asarray(active))
            for slot in (0, 1):
                got[slot].append(np.asarray(logits[slot]))
            pos[:2] += 1
            assert int(moe[0]) == 2 and int(moe[1]) == 2 * 2 * 4
    for slot, prompt in enumerate(prompts):
        want = reference_logits(prompt, np.concatenate([fed[slot], [0]]))
        np.testing.assert_allclose(np.stack(got[slot]), want, atol=2e-4)


def test_single_stream_prefill_and_decode_match_forward(params):
    """The contiguous latent cache of the single-stream path (``prefill``,
    then ``decode_step`` at a shared position): dense absorbed attention
    over the cache, against the expanded dense forward."""
    rng = np.random.default_rng(10)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab, (1, 24), dtype=np.int32))
    dense = dataclasses.replace(CFG, attn_impl="xla", decode_impl="xla")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(llama.forward(params, tokens, dense))[0]
        cache = llama.init_kv_cache(dense, 1, 64)
        assert cache.shape == (3, 1, 64, 128)
        logits, cache = llama.prefill(params, cache, tokens[:, :20], dense)
        got = [np.asarray(logits[0])]
        for k in range(20, 24):
            logits, cache = llama.decode_step(params, cache, tokens[:, k], k,
                                              dense)
            got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.stack(got), want[19:], atol=2e-4)


@pytest.fixture(scope="module")
def served(params):
    model = LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=3,
                               page_size=PAGE, params=params)
    with jax.default_matmul_precision("highest"):
        model.warmup()
        yield model
    model.close()


def test_served_streams_are_the_reference_argmax(served):
    """The scheduler loop end to end, two rows co-batched: every served
    token is the reference's argmax, its logprob the reference's, the
    pages come back, and the counters say what a step's attention read:
    a latent row of 128 lanes of float32 a token a layer."""
    rng = np.random.default_rng(9)
    requests = [(rng.integers(0, CFG.vocab, (n,), dtype=np.int32), m)
                for n, m in ((128, 20), (24, 30))]
    with jax.default_matmul_precision("highest"):
        streams = [served._scheduler.submit(p, n) for p, n in requests]
        outs = [list(s) for s in streams]
    for (prompt, n), out in zip(requests, outs):
        tokens = [t for t, _ in out]
        assert len(tokens) == n
        logits = reference_logits(prompt, tokens)
        assert list(logits.argmax(-1)) == tokens
        logp = jax.nn.log_softmax(logits, -1)
        np.testing.assert_allclose(
            [lp for _, lp in out], logp[np.arange(n), tokens], atol=2e-4)
    stats = served.scheduler_stats()
    assert stats["pages_free"] == stats["pages_total"]
    assert stats["context_tokens"] > 0
    assert stats["context_bytes"] == stats["context_tokens"] * 128 * 4
    assert 0 < stats["moe_experts_hit"] <= stats["moe_local_pairs"]


def test_context_bytes_of_a_kv_class_are_its_rows():
    """The same counter over a K/V class: 2 x 4 heads x 8 lanes of bf16
    a token a layer, read off the pool's array."""
    cfg = llama.tiny(vocab=512)
    model = LlamaGenerateModel(cfg=cfg, max_seq=64, max_slots=2, page_size=PAGE)
    model.warmup()
    try:
        list(model._scheduler.submit(np.arange(1, 20, dtype=np.int32), 5))
        stats = model.scheduler_stats()
    finally:
        model.close()
    assert stats["context_tokens"] > 0
    assert stats["context_bytes"] == stats["context_tokens"] * 2 * 4 * 8 * 2


# -- (8) typed refusals ------------------------------------------------------------

REFUSED = {
    "park": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, on_finish=lambda rows: None),
    "resume_cache": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, resume_cache=np.zeros(1), resume_pos=2),
    "kv_export": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, kv_export=True),
    "kv_attach": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, attach_cache=np.zeros(1), attach_pos=2),
    "int8": lambda fns: LlamaGenerateModel(cfg=CFG, quantize=True),
    "tensor_parallel": lambda fns: llama.param_specs(CFG),
    "window_layers": lambda fns: llama.make_scheduler_fns(
        dataclasses.replace(CFG, layer_types=("full", "window", "full"),
                            window=32), MAX_SEQ, 2, page_size=PAGE),
    "blocks": lambda fns: llama.make_scheduler_fns(
        dataclasses.replace(CFG, block_len=4), MAX_SEQ, 2, page_size=PAGE),
    "slotted_step": lambda fns: llama.batched_decode_step(
        None, jnp.zeros((3, 2, 64, 128)), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), CFG),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_the_latent_class_refuses_by_name(what):
    """What copies K and V rows out of the pool (park / resume, KV
    export / attach), what was written for the plain block (int8, tensor
    parallelism) and what the latent class is not (window layers,
    blocks, the slotted step) is refused with a typed error where it is
    asked for, never served wrong; shared prefixes and chunked prefill
    are off (``span_safe``), so every prompt is prefilled whole."""
    fns = llama.make_scheduler_fns(CFG, MAX_SEQ, 2, page_size=PAGE)
    assert fns["latent_class"] == {"width": 40, "row": 128}
    assert not fns["span_safe"]
    assert "gather" not in fns and "prefill_span" not in fns
    with pytest.raises(llama.UnsupportedArchitecture):
        REFUSED[what](fns)


def test_a_chunked_or_shared_prefill_is_never_taken():
    """Even where the dense prefill path would allow spans
    (``attn_impl="xla"``), the latent class keeps ``span_safe`` false."""
    fns = llama.make_scheduler_fns(
        dataclasses.replace(CFG, attn_impl="xla"), MAX_SEQ, 2, page_size=PAGE)
    assert not fns["span_safe"] and "prefill_span" not in fns


def test_plain_configurations_trace_no_latent_structure():
    """No ``MLAConfig`` and one router group: the plain block's step
    lowers without any of the new scopes or the new kernel."""
    cfg = llama.tiny(vocab=512)
    fns = llama.make_scheduler_fns(
        dataclasses.replace(cfg, decode_impl="pallas"), 128, 2, page_size=PAGE)
    assert "latent_class" not in fns and "gather" in fns
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)  # noqa: E731
    args = (params, jax.eval_shape(fns["init_cache"]),
            jax.eval_shape(fns["init_logits"]), i32(2, 8), i32(2), b(2),
            i32(2), b(2))
    text = str(jax.make_jaxpr(fns["step"])(*args))
    assert "name=paged_decode_attention" in text
    assert "latent_decode_attention" not in text
    scopes = fns["step"].lower(*args).as_text(debug_info=True)
    assert "attn.qkv" in scopes
    for name in ("mla.q_proj", "mla.kv_latent", "mla.absorb"):
        assert name not in scopes
