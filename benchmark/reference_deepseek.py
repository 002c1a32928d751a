"""Plain reference of the DeepSeek-V3 block (``modeling_deepseek.py`` /
the released ``inference/model.py``; DeepSeek-V3 technical report,
sections 2.1.1 and 2.1.2): the forward pass in straightforward
``jax.numpy``, float32, ``highest`` matmul precision; no kernel, no
cache, no batching, and the EXPANDED form of latent attention only (the
absorbed form is the program's business).  It imports nothing of the
program and takes nothing it made: weights come from
``weights_deepseek.py`` and the seed, one layer at a time.

The equations (one sequence, x [T, D]; ``y`` the normed input):

- embedding ``x = E[token]``; pre-norm block ``x = x + Attn(N1(x))``;
  ``x = x + FFN(N2(x))``; every ``N`` an RMSNorm with gain, eps 1e-6.
- latent attention: ``c_q = N(y W_DQ)`` [q_lora]; per head
  ``[q_nope ; q_pe] = c_q W_UQ``; ``[c_kv ; k_pe] = y W_DKV``, ``c_kv =
  N(c_kv)``; ``q_pe``, ``k_pe`` rotated over pairs (2k, 2k+1) at the YaRN
  frequencies (``yarn_frequencies``), ``k_pe`` ONE vector a token shared
  by all heads; per head ``k_nope = c_kv W_UK``, ``v = c_kv W_UV``;
  ``s(t, j) = (q_nope(t) . k_nope(j) + q_pe(t) . k_pe(j)) * scale``, j <=
  t, ``scale = (nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; ``out = concat_heads(softmax(s) v)
  W_O``.
- dense FFN: SwiGLU.  Routed FFN: ``s = sigmoid(y W_r)`` in float32;
  ``choice = s + b``; a group (``n_group`` groups of consecutive experts)
  scores the sum of its 2 largest ``choice``; of the ``topk_group`` best
  groups' experts the ``top_k`` of largest ``choice`` are chosen (ties:
  the lower index); ``w = s[chosen] / (sum + 1e-20) * scale``;
  ``FFN(y) = Shared(y) + sum over chosen AND held of w_e Expert_e(y)``.
- final RMSNorm, untied head, float32 logits.

Departures from the published description: norm gains are the seed's;
``e_score_correction_bias`` is not trained over a corpus but solved by
the report's balancing rule (an expert loaded above its share loses
bias, one below gains it) on a sample of this seed's own hidden states
(``router_biases``); the sum over experts is cut to the share the
configuration states (experts ``first .. first + held - 1`` of the
router's width), as is the vocabulary; ``kv_b_proj`` is held as its two
parts (``w_uk``, ``w_uv``); the checkpoint's FP8 weights are bfloat16
here; the multi-token-prediction module is not run.  The reference runs
every held expert over every token and weighs by the routing (0 where
not chosen): plain, not fast.

``precision="int8"`` is the CONTROL one step below the bf16 the
configuration states: every linear layer on operands rounded to int8
(weights per output channel, activations per token, symmetric absmax);
the router stays float32, as it is in the program.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights_deepseek
from reference_afmoe import HIGHEST, linear, rms, swiglu

# queries and heads attended at once, tokens fed forward at once, and token
# rows carried through the layers at once: at 10,240 positions, 128 heads
# and a routed layer's 3.75 GB of float32 weights, a pass then takes
# ~11 GB of the chip (compiled for the v5e: PERF.md)
Q_BLOCK, H_BLOCK, T_BLOCK, ROWS = 128, 32, 2048, 4


def yarn_frequencies(s):
    """The ``rope / 2`` rotary frequencies (float64): ``theta ** (-2k /
    rope)``, and under YaRN (factor > 1) the blend of that
    (extrapolated) with the same over the factor (interpolated), by the
    linear ramp between the dimensions that make ``beta_fast`` and
    ``beta_slow`` rotations over the original context."""
    d, theta, factor = s["rope"], s["rope_theta"], s["rope_factor"]
    freqs = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return freqs

    def correction_dim(rotations):
        return (d * np.log(s["rope_orig_max"] / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(correction_dim(s["beta_fast"])), 0)
    high = min(np.ceil(correction_dim(s["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    smooth = 1 - np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return freqs / factor * (1 - smooth) + freqs * smooth


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * np.log(factor) + 1.0


def softmax_scale(s):
    m = yarn_mscale(s["rope_factor"], s["mscale_all_dim"])
    return float((s["nope"] + s["rope"]) ** -0.5 * m * m)


def rope_pairs(x, s):
    """x [T, H, rope]; positions 0..T-1; pairs (2k, 2k+1), the complex
    form of ``inference/model.py``'s ``apply_rotary_emb``."""
    t = x.shape[0]
    ratio = (yarn_mscale(s["rope_factor"], s["mscale"])
             / yarn_mscale(s["rope_factor"], s["mscale_all_dim"]))
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_frequencies(s), jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :] * ratio, jnp.sin(ang)[:, None, :] * ratio
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def attention(w, y, s, precision="f32"):
    """One sequence y [T, D] through one latent-attention layer,
    expanded: every head its own keys and values.  ``H_BLOCK`` heads at
    a time and ``Q_BLOCK`` queries at a time, so that 128 heads over
    10,240 positions fit beside the layer's float32 weights."""
    t = y.shape[0]
    h, nope, rope, dv, kvl = s["n_heads"], s["nope"], s["rope"], s["v"], s["kv_lora"]
    c_q = rms(linear(y, w["wq_a"], precision), w["q_a_norm"], s["eps"])
    kv = linear(y, w["wkv_a"], precision)
    c_kv = rms(kv[:, :kvl], w["kv_a_norm"], s["eps"])
    k_pe = rope_pairs(kv[:, None, kvl:], s)
    hb = min(H_BLOCK, h)
    block = min(Q_BLOCK, t)
    pad = -t % block
    j = jnp.arange(t)[None, :]
    scale = softmax_scale(s)

    def heads(args):
        """Heads ``hb`` at a time: q_b_proj's and kv_b_proj's rows of
        those heads (the keys' rows and the values' rows a matrix each)."""
        w_q, w_uk, w_uv = args
        q = linear(c_q, w_q.reshape(-1, hb * (nope + rope)), precision)
        q = q.reshape(t, hb, nope + rope)
        q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], s)], -1)
        k_nope = linear(c_kv, w_uk.transpose(2, 0, 1).reshape(kvl, hb * nope),
                        precision).reshape(t, hb, nope)
        v = linear(c_kv, w_uv.transpose(1, 0, 2).reshape(kvl, hb * dv),
                   precision).reshape(t, hb, dv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, hb, rope))], -1)
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, block, hb, nope + rope)

        def rows(args):
            qs, i0 = args
            seen = j <= i0 + jnp.arange(block)[:, None]
            sc = jnp.einsum("qhd,uhd->hqu", qs, k, precision=HIGHEST)
            sc = jnp.where(seen[None], sc * scale, -jnp.inf)
            return jnp.einsum("hqu,uhd->qhd", jax.nn.softmax(sc, -1), v,
                              precision=HIGHEST)

        a = lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
        return a.reshape(-1, hb, dv)[:t]

    ql = w["wq_b"].shape[0]
    a = lax.map(heads, (
        w["wq_b"].reshape(ql, h // hb, hb, nope + rope).transpose(1, 0, 2, 3),
        w["w_uk"].reshape(h // hb, hb, nope, kvl),
        w["w_uv"].reshape(h // hb, hb, kvl, dv)))         # [H/hb, T, hb, dv]
    a = a.transpose(1, 0, 2, 3).reshape(t, h * dv)
    return linear(a, w["wo"], precision)


def choose(choice, s):
    """The chosen experts [n, top_k] of ``choice`` [n, E] (scores plus
    bias): group-limited where the router has groups."""
    n, e = choice.shape
    if s["n_group"] > 1:
        per = e // s["n_group"]
        top2, _ = lax.top_k(choice.reshape(n, s["n_group"], per), 2)
        _, groups = lax.top_k(top2.sum(-1), s["topk_group"])
        kept = jnp.zeros((n, s["n_group"]), bool).at[
            jnp.arange(n)[:, None], groups].set(True)
        choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    return lax.top_k(choice, s["top_k"])[1]


def routing(w, y, s):
    """``(chosen [T, top_k], weights [T, top_k])`` of y [T, D]."""
    scores = jax.nn.sigmoid(jnp.matmul(y, w["router"], precision=HIGHEST))
    chosen = choose(scores + w["router_bias"], s)
    wt = jnp.take_along_axis(scores, chosen, 1)
    if s["route_norm"]:
        wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-20)
    return chosen, wt * s["route_scale"]


def routed_ffn(w, y, s, precision="f32"):
    """Shared(y) + the held experts' part of the routed sum, y [T, D].
    ``w["we_*"]`` hold experts ``first .. first + count - 1``."""
    chosen, wt = routing(w, y, s)
    out = swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"], precision)
    first, count = s["first"], w["we_gate"].shape[0]

    def add(acc, e):
        mine = jnp.sum(jnp.where(chosen == first + e, wt, 0.0), -1)
        part = swiglu(y, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                      precision)
        return acc + mine[:, None] * part, None

    out, _ = lax.scan(add, out, jnp.arange(count))
    return out


def attended(w, x, s, precision="f32"):
    """The stream after a layer's attention, and its normed input to the
    feed-forward."""
    x = x + attention(w, rms(x, w["attn_norm"], s["eps"]), s, precision)
    return x, rms(x, w["mlp_norm"], s["eps"])


def layer(w, x, s, routed, precision="f32"):
    """x [T, D] through one layer; the feed-forward ``T_BLOCK`` tokens at
    a time (a token's feed-forward reads no other token)."""
    x, y = attended(w, x, s, precision)
    t = y.shape[0]
    block = min(T_BLOCK, t)

    def ffn(rows):
        return (routed_ffn(w, rows, s, precision) if routed
                else swiglu(rows, w["w_gate"], w["w_up"], w["w_down"],
                            precision))

    f = lax.map(ffn, jnp.pad(y, ((0, -t % block), (0, 0))).reshape(
        -1, block, y.shape[1]))
    return x + f.reshape(-1, y.shape[1])[:t]


def shape_of(sizes):
    """What the equations read of a builder's ``sizes``."""
    return {
        "n_heads": sizes["num_attention_heads"],
        "nope": sizes["qk_nope_head_dim"], "rope": sizes["qk_rope_head_dim"],
        "v": sizes["v_head_dim"], "kv_lora": sizes["kv_lora_rank"],
        "eps": sizes["rms_norm_eps"], "rope_theta": sizes["rope_theta"],
        "rope_factor": sizes["rope_factor"],
        "rope_orig_max": sizes["rope_orig_max"],
        "beta_fast": sizes["beta_fast"], "beta_slow": sizes["beta_slow"],
        "mscale": sizes["mscale"], "mscale_all_dim": sizes["mscale_all_dim"],
        "top_k": sizes["num_experts_per_tok"], "n_group": sizes["n_group"],
        "topk_group": sizes["topk_group"],
        "route_norm": sizes["norm_topk_prob"],
        "route_scale": sizes["routed_scaling_factor"],
        "first": sizes["expert_first"],
    }


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, i, precision):
    sizes = dict(frozen)
    s, routed = shape_of(sizes), sizes["ffn_types"][i] == "moe"

    def run(key, x, bias):
        w = weights_deepseek.layer(key, sizes, i, jnp.float32, bias)
        return lax.map(lambda row: layer(w, row, s, routed, precision), x)
    # the stream is handed on: its room is the next layer's
    return jax.jit(run, donate_argnums=1)


# -- the expert biases --------------------------------------------------------

BALANCE_SAMPLE = (4, 1024)      # token rows the biases are balanced on
BALANCE_ROUNDS = 400


def balance(scores, s):
    """Expert biases b [E] under which the choice of ``scores + b``
    (scores [n, E], group limit and all) falls on every expert alike:
    the report's balancing rule (after every batch an expert chosen less
    than its share gains bias, one chosen more loses it), run to rest on
    one batch with a rate that dies away.  Starts where the experts'
    mean scores are level."""
    n, e = scores.shape
    share = n * s["top_k"] / e
    rate = 0.5 * jnp.mean(jnp.std(scores, axis=0))

    def update(i, b):
        chosen = choose(scores + b, s)
        load = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return b + rate * 0.985 ** i * jnp.clip(1.0 - load / share, -1.0, 1.0)

    return lax.fori_loop(0, BALANCE_ROUNDS, update, -jnp.mean(scores, axis=0))


@functools.lru_cache(maxsize=None)
def _balance_fn(frozen, i):
    sizes = dict(frozen)
    s = shape_of(sizes)

    def run(key, x):
        w = weights_deepseek.layer(key, sizes, i, jnp.float32)
        y = lax.map(lambda row: attended(w, row, s)[1], x)
        scores = jax.nn.sigmoid(jnp.matmul(
            y.reshape(-1, y.shape[-1]), w["router"], precision=HIGHEST))
        return balance(scores, s)
    return jax.jit(run)


@functools.lru_cache(maxsize=2)
def _router_biases(seed, frozen):
    sizes = dict(frozen)
    key = weights_deepseek.root_key(seed)
    biases = []
    with jax.default_matmul_precision("highest"):
        x = _embed_fn(frozen)(key, weights_deepseek.sample_tokens(
            key, sizes, *BALANCE_SAMPLE))
        for i in range(sizes["num_hidden_layers"]):
            biases.append(_balance_fn(frozen, i)(key, x)
                          if sizes["ffn_types"][i] == "moe" else None)
            x = _layer_fn(frozen, i, "f32")(key, x, biases[i])
    return tuple(biases)


def router_biases(seed, sizes):
    """Per layer as run the router's expert biases [router_experts]
    float32 (None for a dense layer), a function of the seed alone: the
    float32 forward of a seeded sample of token rows, each routed layer
    balanced (``balance``) on the sample's hidden states as the layers
    before it, balanced already, left them.  The program's weights and
    the reference take the same ones (made once a process and seed)."""
    return _router_biases(int(seed), weights_deepseek.frozen(sizes))


@functools.lru_cache(maxsize=None)
def _embed_fn(frozen):
    sizes = dict(frozen)
    return jax.jit(lambda key, tokens: weights_deepseek.ends(
        key, sizes, jnp.float32)["embed"][tokens])


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, precision):
    sizes = dict(frozen)

    def run(key, x):
        e = weights_deepseek.ends(key, sizes, jnp.float32)
        return linear(rms(x, e["norm"], sizes["rms_norm_eps"]),
                      e["lm_head"], precision)
    return jax.jit(run)


def decoder_logits(seed, sizes, tokens, first, count, precision="f32"):
    """Logits [S, count, V] (float32, host) of positions ``first[s] ..
    first[s]+count-1`` for token rows ``tokens`` [S, T], one teacher-forced
    pass, ``ROWS`` rows at a time, layer by layer (one layer's float32
    weights live at a time)."""
    frozen = weights_deepseek.frozen(sizes)
    key = weights_deepseek.root_key(seed)
    biases = router_biases(seed, sizes)
    tokens, first = np.asarray(tokens, np.int32), np.asarray(first)
    out = []
    with jax.default_matmul_precision("highest"):
        for r in range(0, len(tokens), ROWS):
            x = _embed_fn(frozen)(key, jnp.asarray(tokens[r:r + ROWS]))
            for i in range(sizes["num_hidden_layers"]):
                x = _layer_fn(frozen, i, precision)(key, x, biases[i])
            idx = (jnp.asarray(first[r:r + ROWS])[:, None]
                   + jnp.arange(count)[None, :])
            picked = jnp.take_along_axis(x, idx[:, :, None], axis=1)
            out.append(np.asarray(_head_fn(frozen, precision)(key, picked)))
    return np.concatenate(out)
