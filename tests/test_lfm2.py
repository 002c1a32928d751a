"""Gated short-convolution mixers beside attention (the LFM2-MoE family,
``llama.tiny_lfm2``): the served path against the benchmark's plain
reference (``benchmark/reference_lfm2.py``: float32, no kernel, no
cache), on seeded weights made by ``benchmark/weights_lfm2.py``.

A conv layer holds no pages: what a sequence carries from one step to
the next is its window, the last ``conv_len - 1`` rows of the mixer's
``u``, in a fixed-size array beside the page pool, written whole at
admission and moved on by one row a step for live rows only.

Everything compares LOGITS in float32 at ``highest`` matmul precision
(at toy widths a bf16 rounding flips a router near-tie and moves a logit
by tenths).  The tolerance, 2e-4, is float32's own: the served path and
the reference sum the same terms in another order (the paged kernel, the
padded K/V lanes, the batched router), a few 1e-6 on logits of size ~1
over 6 layers, and 2e-4 leaves the room the other families' tests leave.
A window one position late, or taken at the padded bucket's end, moves
the logits by 1e-2 and more (``test_a_planted_window_fault_fails``).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(os.path.join(REPO, "benchmark"))

import reference_lfm2 as ref  # noqa: E402
import weights_lfm2  # noqa: E402
from tpuserver.models import llama  # noqa: E402
from tpuserver.models.llama_serving import LlamaGenerateModel  # noqa: E402
from tpuserver.scheduler import DecodeScheduler  # noqa: E402

PAGE, MAX_SEQ, SEED, SLOTS = 16, 384, 9, 4
TOL = 2e-4          # float32's own room (module docstring)
FAULT = 1e-2        # what a misplaced window moves at the least
# the served geometry of the chip's configuration: K/V heads in 128 lanes
CFG = dataclasses.replace(
    llama.tiny_lfm2(vocab=512), dtype=jnp.float32, attn_impl="pallas",
    decode_impl="pallas", kv_lanes=128)


def sizes_of(cfg):
    """``cfg`` as the benchmark's builder states a configuration
    (``models/lfm2_generate.sizes_of``)."""
    m = cfg.moe
    return {
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": m.d_expert,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.n_layers, "vocab_size": cfg.vocab,
        "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
        "conv_L_cache": cfg.conv_len, "num_experts": m.n_experts,
        "num_experts_per_tok": m.top_k, "norm_topk_prob": m.route_norm,
        "routed_scaling_factor": m.route_scale,
        "layer_types": list(cfg.layer_types),
        "ffn_types": list(cfg.ffn_types),
    }


SIZES = sizes_of(CFG)


@pytest.fixture(scope="module")
def params():
    """The served tree in float32 from the benchmark's generator, its
    expert biases solved as the benchmark solves them (jitted, as the
    reference makes them)."""
    key = weights_lfm2.root_key(SEED)
    with jax.default_matmul_precision("highest"):
        biases = ref.router_biases(SEED, SIZES)
        tree = jax.jit(lambda: weights_lfm2.ends(key, SIZES, jnp.float32))()
        tree["layers"] = [
            jax.jit(lambda b, i=i: weights_lfm2.layer(
                key, SIZES, i, jnp.float32, b))(b)
            for i, b in enumerate(biases)]
    return tree


def reference_logits(prompt, tokens):
    """The reference's logits at the prompt's last position and after
    each fed token: [len(tokens) + 1, V]."""
    row = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None]
    row = np.pad(row, ((0, 0), (0, MAX_SEQ - row.shape[1])))
    return ref.decoder_logits(SEED, SIZES, row, [len(prompt) - 1],
                              len(tokens) + 1)[0]


# -- the mixer ----------------------------------------------------------------


def test_conv_mixer_matches_its_equations():
    """``[B ; C ; x~] = W_in y``, ``u = B * x~``, ``z_t = sum_j w_j
    u_{t-2+j}`` with ``u`` 0 before the start, ``W_out (C * z)``: the
    program's mixer and the reference's, each against the equations
    written out a row at a time."""
    rng = np.random.default_rng(1)
    d, t = 64, 9
    w = {"conv_in": rng.normal(size=(d, 3 * d)).astype(np.float32) / 8,
         "conv_w": rng.normal(size=(3, d)).astype(np.float32),
         "conv_out": rng.normal(size=(d, d)).astype(np.float32) / 8,
         "attn_norm": np.ones(d, np.float32)}
    y = rng.normal(size=(t, d)).astype(np.float32)
    bcx = y @ w["conv_in"]
    b, c, xt = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = b * xt
    z = np.zeros((t, d), np.float32)
    for row in range(t):
        for j in range(3):
            if row - 2 + j >= 0:
                z[row] += w["conv_w"][j] * u[row - 2 + j]
    want = (c * z) @ w["conv_out"]
    with jax.default_matmul_precision("highest"):
        mixed, rows = llama.conv_mix(jnp.asarray(bcx)[None],
                                     jnp.zeros((1, 2, d)), w["conv_w"])
        got = np.asarray(mixed[0]) @ w["conv_out"]
        reference = ref.conv_mixer(
            jax.tree_util.tree_map(jnp.asarray, w), jnp.asarray(y), {})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(reference), want, rtol=1e-5,
                               atol=1e-5)
    # the window after the first n rows is rows n .. n + 1 of ``rows``
    np.testing.assert_allclose(np.asarray(rows[0, 5:7]), u[3:5], rtol=1e-6)


# -- the served path against the reference ------------------------------------


def test_forward_matches_reference(params):
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, CFG.vocab, (1, 96), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(llama.forward(params, jnp.asarray(tokens), CFG))[0]
    want = ref.decoder_logits(SEED, SIZES, tokens, [0], 96)[0]
    np.testing.assert_allclose(got, want, atol=TOL)


class Paged:
    """The scheduler's function bundle driven by hand: prefill and admit
    into a slot, decode steps of the live slots with forced tokens, the
    logits of every row after each."""

    def __init__(self, params):
        self.params = params
        self.fns = fns = llama.make_scheduler_fns(
            CFG, MAX_SEQ, SLOTS, page_size=PAGE)
        self.pages, self.logits = fns["init_cache"](), fns["init_logits"]()
        self.ppseq = fns["pages_per_seq"]
        self.tables = np.full((SLOTS, self.ppseq), fns["n_pages"], np.int32)
        self.pos = np.full((SLOTS,), MAX_SEQ, np.int32)

    def prefill(self, prompt, true_len=None):
        """``(logits, slot cache)`` of a prompt prefilled at its bucket
        (``true_len``: where the prefill is told the prompt ends)."""
        bucket = self.fns["prefill_bucket"](len(prompt))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        return self.fns["prefill"](
            self.params, self.fns["init_slot_cache"](), jnp.asarray(padded),
            true_len or len(prompt))

    def admit(self, slot, prompt, slot_logits, slot_cache):
        self.tables[slot] = np.arange(self.ppseq) + slot * self.ppseq
        self.pages, self.logits = self.fns["admit"](
            self.pages, self.logits, slot_cache, slot_logits,
            jnp.asarray(self.tables[slot]), slot)
        self.pos[slot] = len(prompt)
        return np.asarray(slot_logits[0])

    def step(self, forced):
        """One step; ``forced`` {slot: token}; the other slots inert."""
        active = np.zeros((SLOTS,), bool)
        tokens = np.zeros((SLOTS,), np.int32)
        for slot, tok in forced.items():
            active[slot], tokens[slot] = True, tok
        pos = np.where(active, self.pos, MAX_SEQ).astype(np.int32)
        _, _, self.logits, self.pages, _ = self.fns["step"](
            self.params, self.pages, self.logits, jnp.asarray(self.tables),
            jnp.asarray(pos), jnp.asarray(active), jnp.asarray(tokens),
            jnp.asarray(active))
        self.pos[active] += 1
        return {slot: np.asarray(self.logits[slot]) for slot in forced}

    def windows(self):
        return np.asarray(self.pages["conv"])


@pytest.mark.parametrize("n", [1, 2, 3, 5, 13, 37, 100])
def test_prefill_then_paged_decode_match_reference(params, n):
    """A prompt of ``n`` tokens prefilled at its bucket (8 for 1-5, 16
    for 13 and 64 for 37, whose windows lie inside the padding's reach;
    100 exactly, dense), admitted, then 6 decode steps with fed tokens:
    the logits at every position against the reference's one forward."""
    rng = np.random.default_rng(10 + n)
    prompt = rng.integers(0, CFG.vocab, (n,), dtype=np.int32)
    fed = rng.integers(0, CFG.vocab, (6,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        paged = Paged(params)
        assert paged.fns["decode_attention"] == "paged_kernel"
        got = [paged.admit(1, prompt, *paged.prefill(prompt))]
        got += [paged.step({1: int(t)})[1] for t in fed]
    want = reference_logits(prompt, fed)
    np.testing.assert_allclose(np.stack(got), want, atol=TOL)


def test_admission_mid_decode_leaves_the_neighbours_windows(params):
    """Two rows decode; a third is admitted between two steps: the
    others' windows do not move at the admission, an inert slot's never
    move at a step, and every row's logits stay the reference's."""
    rng = np.random.default_rng(20)
    prompts = [rng.integers(0, CFG.vocab, (n,), dtype=np.int32)
               for n in (21, 64, 9)]
    fed = [rng.integers(0, CFG.vocab, (8,), dtype=np.int32) for _ in prompts]
    got = {0: [], 1: [], 2: []}
    with jax.default_matmul_precision("highest"):
        paged = Paged(params)
        for slot in (0, 1):
            prompt = prompts[slot]
            got[slot].append(paged.admit(slot, prompt, *paged.prefill(prompt)))
        for k in range(4):
            inert = paged.windows()[:, 2:]
            for slot, logits in paged.step(
                    {0: fed[0][k], 1: fed[1][k]}).items():
                got[slot].append(logits)
            assert np.array_equal(paged.windows()[:, 2:], inert)
        before = paged.windows()
        got[2].append(paged.admit(2, prompts[2], *paged.prefill(prompts[2])))
        after = paged.windows()
        assert np.array_equal(after[:, :2], before[:, :2])
        assert np.array_equal(after[:, 3], before[:, 3])
        for k in range(4, 8):
            out = paged.step({0: fed[0][k], 1: fed[1][k], 2: fed[2][k - 4]})
            for slot, logits in out.items():
                got[slot].append(logits)
    for slot, fed_here in ((0, fed[0]), (1, fed[1]), (2, fed[2][:4])):
        want = reference_logits(prompts[slot], fed_here)
        np.testing.assert_allclose(np.stack(got[slot]), want, atol=TOL,
                                   err_msg=str(slot))


def test_a_reused_slot_keeps_nothing_of_its_last_sequence(params):
    """A slot serves one sequence, is given up, and serves another: the
    second's logits are the reference's, from zeros before its start,
    whatever the first left in the slot's window."""
    rng = np.random.default_rng(30)
    first, second = (rng.integers(0, CFG.vocab, (n,), dtype=np.int32)
                     for n in (40, 6))
    fed = rng.integers(0, CFG.vocab, (5,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        paged = Paged(params)
        paged.admit(1, first, *paged.prefill(first))
        for t in fed:
            paged.step({1: int(t)})
        assert np.abs(paged.windows()[:, 1]).max() > 0
        got = [paged.admit(1, second, *paged.prefill(second))]
        got += [paged.step({1: int(t)})[1] for t in fed]
    np.testing.assert_allclose(np.stack(got), reference_logits(second, fed),
                               atol=TOL)


@pytest.mark.parametrize("n", [1, 2, 5])
def test_a_step_moves_a_live_window_as_a_longer_prefill_would(params, n):
    """A row admitted after ``n`` prompt tokens and stepped once on a fed
    token holds the windows a prefill of the ``n + 1`` tokens leaves (at
    ``n`` of 1 and 2 the zeros before the start shift out), and the
    inert slots' windows stay as they were."""
    rng = np.random.default_rng(70 + n)
    prompt = rng.integers(0, CFG.vocab, (n + 1,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        paged = Paged(params)
        paged.admit(2, prompt[:n], *paged.prefill(prompt[:n]))
        inert = paged.windows()[:, [0, 1, 3]]
        paged.step({2: int(prompt[n])})
        want = np.asarray(paged.prefill(prompt)[1]["conv"])[:, 0]
    np.testing.assert_allclose(paged.windows()[:, 2], want, atol=TOL)
    assert np.array_equal(paged.windows()[:, [0, 1, 3]], inert)


@pytest.mark.parametrize("fault", ["shifted_by_one", "at_the_bucket_end"])
def test_a_planted_window_fault_fails(params, fault):
    """The tolerance bites: the same admission and steps with the
    windows planted one position late (the prefill's window of the
    prompt but its last token) or taken at the padded bucket's end move
    the logits after the prompt far beyond ``TOL``."""
    rng = np.random.default_rng(40)
    prompt = rng.integers(0, CFG.vocab, (13,), dtype=np.int32)
    fed = rng.integers(0, CFG.vocab, (4,), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        paged = Paged(params)
        slot_logits, slot_cache = paged.prefill(prompt)
        bucket = paged.fns["prefill_bucket"](len(prompt))
        assert bucket == 16
        planted = paged.prefill(
            prompt, len(prompt) - 1 if fault == "shifted_by_one"
            else bucket)[1]["conv"]
        paged.admit(1, prompt, slot_logits, dict(slot_cache, conv=planted))
        got = np.stack([paged.step({1: int(t)})[1] for t in fed])
    gap = np.abs(got - reference_logits(prompt, fed)[1:]).max()
    assert gap > FAULT > TOL


# -- the scheduler loop -------------------------------------------------------


@pytest.fixture(scope="module")
def served(params):
    model = LlamaGenerateModel(cfg=CFG, max_seq=MAX_SEQ, max_slots=3,
                               page_size=PAGE, params=params)
    with jax.default_matmul_precision("highest"):
        model.warmup()
        yield model
    model.close()


def test_served_streams_are_the_reference_argmax(served):
    """The scheduler loop end to end, three rows co-batched and one
    admitted into a freed slot: every served token is the reference's
    argmax, its logprob the reference's; the pages come back; the
    counters say a step's rows read their windows (4 conv layers x 2
    rows x 64 float32 = 2,048 B a row) and an admission writes one, and
    that K/V of 2 heads lies in 128 lanes a head."""
    rng = np.random.default_rng(50)
    requests = [(rng.integers(0, CFG.vocab, (n,), dtype=np.int32), m)
                for n, m in ((37, 12), (5, 20), (64, 8), (3, 10))]
    before = served.scheduler_stats()
    with jax.default_matmul_precision("highest"):
        streams = [served._scheduler.submit(p, n) for p, n in requests]
        outs = [list(s) for s in streams]
    for (prompt, n), out in zip(requests, outs):
        tokens = [t for t, _ in out]
        assert len(tokens) == n
        logits = reference_logits(prompt, tokens[:-1])
        assert list(logits.argmax(-1)) == tokens
        logp = jax.nn.log_softmax(logits, -1)
        np.testing.assert_allclose(
            [lp for _, lp in out], logp[np.arange(n), tokens], atol=TOL)
    stats = served.scheduler_stats()
    assert stats["pages_free"] == stats["pages_total"]
    assert stats["prefix_hits"] == 0      # no radix cache beside windows
    assert stats["state_writes"] - before["state_writes"] == len(requests)
    row = 4 * 2 * 64 * 4
    grown = stats["state_bytes"] - before["state_bytes"]
    assert grown % row == 0
    assert grown // row >= sum(n for _, n in requests)
    # K and V of 2 heads, 128 lanes a head, float32, a token a layer
    assert stats["context_bytes"] == stats["context_tokens"] * 2 * 2 * 128 * 4


# -- typed refusals -----------------------------------------------------------

REFUSED = {
    "park": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, on_finish=lambda rows: None),
    "resume_cache": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, resume_cache=np.zeros(1), resume_pos=2),
    "kv_export": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, kv_export=True),
    "kv_attach": lambda fns: DecodeScheduler(fns, None, 2, MAX_SEQ).submit(
        [1, 2, 3], 4, attach_cache=np.zeros(1), attach_pos=2),
    "span_prefill": lambda fns: llama.prefill_span(
        None, llama.init_kv_cache(CFG, 1, 64), jnp.zeros((1, 8), jnp.int32),
        0, 7, CFG),
    "single_stream": lambda fns: LlamaGenerateModel(cfg=CFG, max_slots=1),
    "int8": lambda fns: LlamaGenerateModel(cfg=CFG, quantize=True,
                                           max_slots=2),
    "tensor_parallel": lambda fns: llama.param_specs(CFG),
    "window_layers": lambda fns: llama.make_scheduler_fns(
        dataclasses.replace(CFG, layer_types=("conv",) + ("window",) * 5,
                            window=32), MAX_SEQ, 2, page_size=PAGE),
    "latent": lambda fns: llama.make_scheduler_fns(
        dataclasses.replace(CFG, mla=llama.MLAConfig()), MAX_SEQ, 2,
        page_size=PAGE),
    "blocks": lambda fns: llama.make_scheduler_fns(
        dataclasses.replace(CFG, block_len=4), MAX_SEQ, 2, page_size=PAGE),
    "slotted_step": lambda fns: llama.batched_decode_step(
        None, llama.init_kv_cache(CFG, 2, 64), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), CFG),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_conv_layers_refuse_by_name(what):
    """What copies K and V rows alone (park / resume, KV export /
    attach), a span prefill that would not carry the window, what was
    written for the plain block and what conv layers are not served
    beside is refused with a typed error where it is asked for, never
    served wrong; the radix cache and chunked prefill are off
    (``span_safe``), so every prompt is prefilled whole."""
    fns = llama.make_scheduler_fns(CFG, MAX_SEQ, 2, page_size=PAGE)
    assert fns["conv_state"] == 4
    assert not fns["span_safe"]
    assert "gather" not in fns and "prefill_span" not in fns
    with pytest.raises(llama.UnsupportedArchitecture):
        REFUSED[what](fns)


def test_the_pool_holds_the_attention_layers_alone():
    """Of 6 layers 2 attend: the pool's layer axis is 2, its rows 128
    lanes a head; the windows are [conv layers, slots, 2, D]."""
    fns = llama.make_scheduler_fns(CFG, MAX_SEQ, SLOTS, page_size=PAGE)
    pool = jax.eval_shape(fns["init_cache"])
    assert pool["kv"].shape == (2, 2, fns["n_pages"], PAGE, 2, 128)
    assert pool["conv"].shape == (4, SLOTS, 2, 64)
    assert CFG.attn_layers == (1, 4) and CFG.conv_layers == (0, 2, 3, 5)


def test_padded_lanes_keep_the_single_stream_decode_exact():
    """K/V heads stored in more lanes than they have (``kv_lanes``) on
    the contiguous cache's decode kernel path: a padded query scored at
    its own head size, its output cut back, the same logits as the
    unpadded cache's."""
    cfg = dataclasses.replace(llama.tiny(vocab=512), dtype=jnp.float32,
                              decode_impl="pallas")
    padded = dataclasses.replace(cfg, kv_lanes=128)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        llama.init_params(jax.random.PRNGKey(4), cfg))
    tokens = jnp.asarray(np.random.default_rng(60).integers(
        0, 512, (1, 24)), jnp.int32)
    got = {}
    with jax.default_matmul_precision("highest"):
        for name, c in (("plain", cfg), ("padded", padded)):
            cache = llama.init_kv_cache(c, 1, 128)
            logits, cache = llama.prefill(params, cache, tokens[:, :20], c)
            out = [np.asarray(logits[0])]
            for k in range(20, 24):
                logits, cache = llama.decode_step(params, cache,
                                                  tokens[:, k], k, c)
                out.append(np.asarray(logits[0]))
            got[name] = np.stack(out)
    assert cache.shape[-1] == 128
    np.testing.assert_allclose(got["padded"], got["plain"], atol=1e-5)


def test_configurations_without_conv_layers_trace_no_conv_structure():
    """The plain block's and the routed window block's steps lower
    without the conv scopes, and their bundles hold no window state."""
    for cfg in (llama.tiny(vocab=512), llama.tiny_afmoe(vocab=512)):
        cfg = dataclasses.replace(cfg, decode_impl="pallas")
        fns = llama.make_scheduler_fns(cfg, 128, 2, page_size=PAGE)
        assert "conv_state" not in fns
        params = jax.eval_shape(
            lambda cfg=cfg: llama.init_params(jax.random.PRNGKey(0), cfg))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)  # noqa: E731
        tables = (i32(2, 8) if fns["window_class"] is None else
                  {"full": i32(2, 8), "window": i32(2, fns["window_class"][
                      "ring"])})
        args = (params, jax.eval_shape(fns["init_cache"]),
                jax.eval_shape(fns["init_logits"]), tables, i32(2), b(2),
                i32(2), b(2))
        text = str(jax.make_jaxpr(fns["step"])(*args))
        assert "paged_decode_attention" in text
        scopes = fns["step"].lower(*args).as_text(debug_info=True)
        for name in ("conv.in_proj", "conv.window", "conv.out_proj"):
            assert name not in scopes
