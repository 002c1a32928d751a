"""Plain reference of the LFM2-MoE block (LFM2-8B-A1B: LiquidAI's
``modeling_lfm2_moe.py`` in ``transformers``), the benchmark's own: the
forward pass in straightforward ``jax.numpy``, float32, ``highest``
matmul precision; no kernel, no cache, no batching.  It imports nothing
of the program and takes nothing it made: weights come from
``weights_lfm2.py`` and the seed, one layer at a time.

The equations (one sequence, x [T, D]):

- embedding: ``x = E[token]``.
- layer, pre-norm: ``h = x + Mixer(RMSNorm_op(x))``;
  ``out = h + FFN(RMSNorm_ffn(h))``.
- short-convolution mixer (``layer_types`` "conv"):
  ``[B ; C ; x~] = W_in y`` (split in that order), ``u = B * x~``,
  ``z_t = sum_{j=0..L-1} w_j * u_{t-L+1+j}`` (causal, depthwise, ``u`` 0
  before the sequence starts; L = ``conv_L_cache``, no bias),
  ``Mixer(y) = W_out (C * z)``.
- attention mixer (``layer_types`` "full_attention"): ``q = Wq y``,
  ``k = Wk y``, ``v = Wv y`` (GQA, head size hidden / heads); q and k
  RMS-normed per head over the head size (gains ``q_norm``,
  ``k_norm``); rotary positions (half-split) at ``rope_theta``; causal;
  scale 1/sqrt(head size); ``Wo``.
- FFN: SwiGLU below the published ``num_dense_layers``; routed above:
  ``s = sigmoid(Wr y)`` in float32; top-k of ``s + b`` (``expert_bias``);
  ``w = s[chosen] / (sum s[chosen] + 1e-6)`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``FFN(y) = sum over chosen of w_e
  Expert_e(y)``, every expert a SwiGLU, no shared expert.
- final RMSNorm (``embedding_norm``), head tied to the embedding,
  float32 logits.

Departures from the published code: the norm gains are the seed's (the
published ones are trained), as are the convolution's taps; the
router's expert biases are not trained over a corpus but solved by the
balancing rule on a sample of this seed's own hidden states
(``router_biases``, the rule of ``reference_afmoe.balance``).  The
reference runs every expert over every token and weighs by the routing
(0 where not chosen): plain, not fast.

``precision="int8"`` is the CONTROL one step below the bf16 the
configuration states: every linear layer on operands rounded to int8
(weights per output channel, activations per token, symmetric absmax);
the router stays float32, as it is in the program.
``precision="bf16"`` is the configuration's own precision in the
reference's place: every linear layer on operands rounded to bfloat16,
float32 otherwise; it reads what rounding alone costs, beside the
program.  ``precision="shifted_window"`` is a FAULT of the conv layers,
float32 otherwise: every convolution reaches one position further back,
its taps over ``u_{t-L} .. u_{t-1}``, as a window taken one row late
would make it.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

import weights_lfm2
from reference_afmoe import HIGHEST, Q_BLOCK, rms, rope
from reference_afmoe import linear as _linear_afmoe

ROUTE_EPS = 1e-6     # added to the chosen scores' sum (modeling_lfm2_moe)
PRECISIONS = ("f32", "int8", "bf16", "shifted_window")
BALANCE_SAMPLE = (4, 1024)      # token rows the biases are balanced on
BALANCE_ROUNDS = 400


def linear(x, w, precision="f32"):
    """``x @ w`` at ``highest``: in float32, on int8-rounded operands
    (``reference_afmoe.linear``), or on bfloat16-rounded ones."""
    if precision == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        w = w.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.matmul(x, w, precision=HIGHEST)
    return _linear_afmoe(x, w, "int8" if precision == "int8" else "f32")


def swiglu(y, gate, up, down, precision="f32"):
    return linear(jax.nn.silu(linear(y, gate, precision))
                  * linear(y, up, precision), down, precision)


def conv_mixer(w, y, s, precision="f32"):
    """One sequence y [T, D] through one short-convolution mixer."""
    lin = precision
    b, c, xt = jnp.split(linear(y, w["conv_in"], lin), 3, axis=-1)
    u = b * xt
    taps = w["conv_w"].shape[0]
    shift = 1 if precision == "shifted_window" else 0
    back = taps - 1 + shift
    padded = jnp.pad(u, ((back, 0), (0, 0)))
    t = u.shape[0]
    z = sum(w["conv_w"][j] * padded[j:j + t] for j in range(taps))
    return linear(c * z, w["conv_out"], lin)


def attention(w, y, s, precision="f32"):
    """One sequence y [T, D] through one attention mixer."""
    lin = precision
    t = y.shape[0]
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    q = rms(linear(y, w["wq"], lin).reshape(t, h, hd), w["q_norm"], s["eps"])
    k = rms(linear(y, w["wk"], lin).reshape(t, kv, hd), w["k_norm"], s["eps"])
    v = linear(y, w["wv"], lin).reshape(t, kv, hd)
    q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    block = min(Q_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, hd)
    j = jnp.arange(t)[None, :]

    def rows(args):
        qs, i0 = args
        i = i0 + jnp.arange(block)[:, None]
        sc = jnp.einsum("qhd,uhd->hqu", qs, k, precision=HIGHEST)
        sc = jnp.where((j <= i)[None], sc / jnp.sqrt(float(hd)), -jnp.inf)
        return jnp.einsum("hqu,uhd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HIGHEST)

    a = lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
    return linear(a.reshape(-1, h * hd)[:t], w["wo"], lin)


def routing(w, y, s):
    """``(chosen [T, k], weights [T, k])`` of the router over y [T, D]."""
    scores = jax.nn.sigmoid(jnp.matmul(y, w["router"], precision=HIGHEST))
    _, chosen = lax.top_k(scores + w["router_bias"], s["top_k"])
    wt = jnp.take_along_axis(scores, chosen, 1)
    if s["route_norm"]:
        wt = wt / (jnp.sum(wt, -1, keepdims=True) + ROUTE_EPS)
    return chosen, wt * s["route_scale"]


def routed_ffn(w, y, s, precision="f32"):
    """The routed sum over every expert, y [T, D]."""
    lin = precision
    chosen, wt = routing(w, y, s)

    def add(acc, e):
        mine = jnp.sum(jnp.where(chosen == e, wt, 0.0), -1)
        part = swiglu(y, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                      lin)
        return acc + mine[:, None] * part, None

    experts = jnp.arange(w["we_gate"].shape[0])
    out, _ = lax.scan(add, jnp.zeros_like(y), experts)
    return out


def mixed(w, x, s, conv, precision="f32"):
    """The stream after a layer's mixer, and its normed input to the
    feed-forward."""
    y = rms(x, w["attn_norm"], s["eps"])
    x = x + (conv_mixer(w, y, s, precision) if conv
             else attention(w, y, s, precision))
    return x, rms(x, w["mlp_norm"], s["eps"])


def layer(w, x, s, conv, routed, precision="f32"):
    x, y = mixed(w, x, s, conv, precision)
    f = (routed_ffn(w, y, s, precision) if routed
         else swiglu(y, w["w_gate"], w["w_up"], w["w_down"], precision))
    return x + f


def shape_of(sizes):
    """What the equations read of a builder's ``sizes``."""
    return {
        "n_heads": sizes["num_attention_heads"],
        "n_kv_heads": sizes["num_key_value_heads"],
        "head_dim": sizes["head_dim"], "eps": sizes["norm_eps"],
        "rope_theta": float(sizes["rope_theta"]),
        "top_k": sizes["num_experts_per_tok"],
        "route_norm": sizes["norm_topk_prob"],
        "route_scale": float(sizes["routed_scaling_factor"]),
    }


# one program a KIND of layer (``weights_lfm2.kind_of``), the layer's
# index an argument: a compile for the chip takes ~25 s, and 13 layers
# compiled one by one took longer than a run's set-up may


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, kind, precision):
    sizes = dict(frozen)
    s, conv, routed = shape_of(sizes), kind[0] == "conv", kind[1] == "moe"

    def run(key, i, x, bias):
        w = weights_lfm2.layer(key, sizes, i, jnp.float32, bias, kind)
        return lax.map(
            lambda row: layer(w, row, s, conv, routed, precision), x)
    return jax.jit(run)


def _run_layer(frozen, sizes, i, precision, key, x, bias):
    return _layer_fn(frozen, weights_lfm2.kind_of(sizes, i), precision)(
        key, jnp.int32(i), x, bias)


def balance(scores, top_k):
    """Expert biases b [E] under which top-k of ``scores + b`` (scores
    [n, E]) falls on every expert alike: ``reference_afmoe.balance``'s
    rule, the published one (after every batch an expert chosen less
    than its share gains bias, one chosen more loses it), run to rest on
    one batch with a rate that dies away, from where the experts' mean
    scores are level.  (The tier-1 tests' copy of ``reference_afmoe``
    has no biases to solve, so the rule is written here again.)"""
    n, e = scores.shape
    share = n * top_k / e
    rate = 0.5 * jnp.mean(jnp.std(scores, axis=0))

    def update(i, b):
        _, chosen = lax.top_k(scores + b, top_k)
        load = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return b + rate * 0.985 ** i * jnp.clip(1.0 - load / share, -1.0, 1.0)

    return lax.fori_loop(0, BALANCE_ROUNDS, update, -jnp.mean(scores, axis=0))


@functools.lru_cache(maxsize=None)
def _balance_fn(frozen, kind):
    sizes = dict(frozen)
    s, conv = shape_of(sizes), kind[0] == "conv"

    def run(key, i, x):
        w = weights_lfm2.layer(key, sizes, i, jnp.float32, kind=kind)
        y = lax.map(lambda row: mixed(w, row, s, conv)[1], x)
        scores = jax.nn.sigmoid(jnp.matmul(
            y.reshape(-1, y.shape[-1]), w["router"], precision=HIGHEST))
        return balance(scores, s["top_k"])
    return jax.jit(run)


@functools.lru_cache(maxsize=2)
def _router_biases(seed, frozen):
    sizes = dict(frozen)
    key = weights_lfm2.root_key(seed)
    biases = []
    with jax.default_matmul_precision("highest"):
        x = _embed_fn(frozen)(key, weights_lfm2.sample_tokens(
            key, sizes, *BALANCE_SAMPLE))
        for i in range(sizes["num_hidden_layers"]):
            kind = weights_lfm2.kind_of(sizes, i)
            biases.append(_balance_fn(frozen, kind)(key, jnp.int32(i), x)
                          if kind[1] == "moe" else None)
            x = _run_layer(frozen, sizes, i, "f32", key, x, biases[i])
    return tuple(biases)


def router_biases(seed, sizes):
    """Per layer as run the router's expert biases [num_experts] float32
    (None for a dense layer), a function of the seed alone: the float32
    forward of a seeded sample of token rows, each routed layer balanced
    (``reference_afmoe.balance``) on the sample's hidden states as the
    layers before it, balanced already, left them.  The program's
    weights and the reference take the same ones (made once a process
    and seed)."""
    return _router_biases(int(seed), weights_lfm2.frozen(sizes))


@functools.lru_cache(maxsize=None)
def _embed_fn(frozen):
    sizes = dict(frozen)
    return jax.jit(lambda key, tokens: weights_lfm2.ends(
        key, sizes, jnp.float32)["embed"][tokens])


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, precision):
    sizes = dict(frozen)

    def run(key, x):
        e = weights_lfm2.ends(key, sizes, jnp.float32)
        return linear(rms(x, e["norm"], sizes["norm_eps"]), e["embed"].T,
                      precision)
    return jax.jit(run)


def decoder_logits(seed, sizes, tokens, first, count, precision="f32"):
    """Logits [S, count, V] (float32, host) of positions ``first[s] ..
    first[s]+count-1`` for token rows ``tokens`` [S, T], one teacher-forced
    pass, layer by layer (one layer's float32 weights live at a time)."""
    import numpy as np

    if precision not in PRECISIONS:
        raise ValueError("precision {!r} (known: {})".format(
            precision, ", ".join(PRECISIONS)))
    frozen = weights_lfm2.frozen(sizes)
    key = weights_lfm2.root_key(seed)
    biases = router_biases(seed, sizes)
    with jax.default_matmul_precision("highest"):
        x = _embed_fn(frozen)(key, jnp.asarray(tokens, jnp.int32))
        for i in range(sizes["num_hidden_layers"]):
            x = _run_layer(frozen, sizes, i, precision, key, x, biases[i])
        idx = jnp.asarray(first)[:, None] + jnp.arange(count)[None, :]
        picked = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        return np.asarray(_head_fn(frozen, precision)(key, picked))
