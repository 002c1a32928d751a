"""Plain reference of the AFMoE block (the Trinity family: arcee-ai's
``modeling_afmoe.py`` in ``transformers``), the benchmark's own copy
(the repository's sits beside its tier-1 tests,
``tests/reference_afmoe.py``; a test holds the two together): the
forward pass in straightforward ``jax.numpy``, float32, ``highest``
matmul precision; no kernel, no cache, no batching.  It imports nothing
of the program and takes nothing it made: weights come from
``weights_afmoe.py`` and the seed, one layer at a time.

The equations (one sequence, x [T, D]):

- embedding: ``x = E[token] * sqrt(hidden_size)`` (``mup_enabled``).
- layer, sandwich norms: ``x = x + N2(Attn(N1(x)))``;
  ``x = x + N4(FFN(N3(x)))``; every ``N`` an RMSNorm with gain.
- attention: ``q = Wq y`` (H heads), ``k = Wk y``, ``v = Wv y`` (Hkv
  heads), ``g = Wg y``; q and k RMS-normed per head over the head size
  (gains ``q_norm``, ``k_norm``); WINDOW layers: rotary positions
  (half-split) on q and k, causal and ``0 <= i - j < window``; FULL
  layers: no positions at all, causal; scale 1/sqrt(head size);
  ``out = Wo (softmax(q k^T) v * sigmoid(g))``.
- dense FFN: SwiGLU.  Routed FFN: ``s = sigmoid(Wr y)`` in float32;
  top-k of ``s + b``; ``w = s[chosen]``, ``w = w / (sum(w) + 1e-20)``
  (``route_norm``), ``w = route_scale * w``; ``FFN(y) = Shared(y) +
  sum over chosen AND held of w_e Expert_e(y)``, every expert a SwiGLU.
- final RMSNorm, untied head, float32 logits.

Departures from the published code: the norm gains are the seed's (the
published ones are trained; "depth-scaled" is an initialisation); the
router's expert biases are not trained over a corpus but solved by the
same balancing rule on a sample of this seed's own hidden states
(``router_biases``); the sum over experts is cut to
the share the configuration states (experts ``first .. first + held -
1`` of the router's width; the published code sums over all), as is the
vocabulary; ``n_group = topk_group = 1`` (no group limit) is assumed and
not computed.  The reference runs every held expert over every token and
weighs by the routing (0 where not chosen): plain, not fast.

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

import weights_afmoe

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512       # queries attended at once: 8,704 positions then fit


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    return jnp.round(x / scale) * scale


def linear(x, w, precision="f32"):
    if precision != "f32":
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [T, H, D]; positions 0..T-1; half-split convention."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def swiglu(y, gate, up, down, precision="f32"):
    return linear(jax.nn.silu(linear(y, gate, precision))
                  * linear(y, up, precision), down, precision)


def attention(w, y, s, window, precision="f32"):
    """One sequence y [T, D] through one attention layer; ``window`` 0 is
    a full layer (no positions), else a window layer (rotary)."""
    t = y.shape[0]
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    q = rms(linear(y, w["wq"], precision).reshape(t, h, hd), w["q_norm"],
            s["eps"])
    k = rms(linear(y, w["wk"], precision).reshape(t, kv, hd), w["k_norm"],
            s["eps"])
    v = linear(y, w["wv"], precision).reshape(t, kv, hd)
    if window:
        q, k = rope(q, s["rope_theta"]), rope(k, s["rope_theta"])
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    block = min(Q_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, hd)
    j = jnp.arange(t)[None, :]

    def rows(args):
        qs, i0 = args
        i = i0 + jnp.arange(block)[:, None]
        seen = j <= i
        if window:
            seen = seen & (i - j < window)
        sc = jnp.einsum("qhd,uhd->hqu", qs, k, precision=HIGHEST)
        sc = jnp.where(seen[None], sc / np.sqrt(hd), -jnp.inf)
        return jnp.einsum("hqu,uhd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HIGHEST)

    a = lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
    a = a.reshape(-1, h * hd)[:t]
    a = a * jax.nn.sigmoid(linear(y, w["wg"], precision))
    return linear(a, w["wo"], precision)


def routed_ffn(w, y, s, precision="f32"):
    """Shared(y) + the held experts' part of the routed sum, y [T, D].
    ``w["we_*"]`` hold experts ``first .. first + count - 1``."""
    scores = jax.nn.sigmoid(jnp.matmul(y, w["router"], precision=HIGHEST))
    _, chosen = lax.top_k(scores + w["router_bias"], s["top_k"])
    wt = jnp.take_along_axis(scores, chosen, 1)
    if s["route_norm"]:
        wt = wt / (jnp.sum(wt, -1, keepdims=True) + 1e-20)
    wt = wt * s["route_scale"]
    out = swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"], precision)
    first, count = s["first"], w["we_gate"].shape[0]

    def add(acc, e):
        mine = jnp.sum(jnp.where(chosen == first + e, wt, 0.0), -1)
        part = swiglu(y, w["we_gate"][e], w["we_up"][e], w["we_down"][e],
                      precision)
        return acc + mine[:, None] * part, None

    out, _ = lax.scan(add, out, jnp.arange(count))
    return out


def attended(w, x, s, window, precision="f32"):
    """The stream after a layer's attention, and its normed input to the
    feed-forward."""
    eps = s["eps"]
    a = attention(w, rms(x, w["attn_norm"], eps), s, window, precision)
    x = x + rms(a, w["attn_post_norm"], eps)
    return x, rms(x, w["mlp_norm"], eps)


def layer(w, x, s, window, routed, precision="f32"):
    x, y = attended(w, x, s, window, precision)
    f = (routed_ffn(w, y, s, precision) if routed
         else swiglu(y, w["w_gate"], w["w_up"], w["w_down"], precision))
    return x + rms(f, w["mlp_post_norm"], s["eps"])


def shape_of(sizes):
    """What the equations read of a builder's ``sizes``."""
    return {
        "n_heads": sizes["num_attention_heads"],
        "n_kv_heads": sizes["num_key_value_heads"],
        "head_dim": sizes["head_dim"], "eps": sizes["rms_norm_eps"],
        "rope_theta": sizes["rope_theta"], "window": sizes["sliding_window"],
        "top_k": sizes["num_experts_per_tok"],
        "route_norm": sizes["route_norm"],
        "route_scale": sizes["route_scale"], "first": sizes["expert_first"],
    }


def _kind(sizes, i):
    """(shape, window, routed) of layer ``i`` of the layers as run."""
    s = shape_of(sizes)
    window = (s["window"] if sizes["layer_types"][i] == "sliding_attention"
              else 0)
    return s, window, sizes["ffn_types"][i] == "moe"


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, i, precision):
    sizes = dict(frozen)
    s, window, routed = _kind(sizes, i)

    def run(key, x, bias):
        w = weights_afmoe.layer(key, sizes, i, jnp.float32, bias)
        return lax.map(
            lambda row: layer(w, row, s, window, routed, precision), x)
    return jax.jit(run)


# -- the expert biases --------------------------------------------------------

BALANCE_SAMPLE = (4, 1024)      # token rows the biases are balanced on
BALANCE_ROUNDS = 400


def balance(scores, top_k):
    """Expert biases b [E] under which top-k of ``scores + b`` (scores
    [n, E]) falls on every expert alike: the published balancing rule
    (after every batch an expert chosen less than its share gains bias,
    one chosen more loses it), run to rest on one batch with a rate that
    dies away.  Starts where the experts' mean scores are level."""
    n, e = scores.shape
    share = n * top_k / e
    rate = 0.5 * jnp.mean(jnp.std(scores, axis=0))

    def update(i, b):
        _, chosen = lax.top_k(scores + b, top_k)
        load = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return b + rate * 0.985 ** i * jnp.clip(1.0 - load / share, -1.0, 1.0)

    return lax.fori_loop(0, BALANCE_ROUNDS, update, -jnp.mean(scores, axis=0))


@functools.lru_cache(maxsize=None)
def _balance_fn(frozen, i):
    sizes = dict(frozen)
    s, window, _ = _kind(sizes, i)

    def run(key, x):
        w = weights_afmoe.layer(key, sizes, i, jnp.float32)
        y = lax.map(lambda row: attended(w, row, s, window)[1], x)
        scores = jax.nn.sigmoid(jnp.matmul(
            y.reshape(-1, y.shape[-1]), w["router"], precision=HIGHEST))
        return balance(scores, s["top_k"])
    return jax.jit(run)


@functools.lru_cache(maxsize=2)
def _router_biases(seed, frozen):
    sizes = dict(frozen)
    key = weights_afmoe.root_key(seed)
    biases = []
    with jax.default_matmul_precision("highest"):
        x = _embed_fn(frozen)(key, weights_afmoe.sample_tokens(
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
    return _router_biases(int(seed), weights_afmoe.frozen(sizes))


@functools.lru_cache(maxsize=None)
def _embed_fn(frozen):
    sizes = dict(frozen)
    return jax.jit(lambda key, tokens: weights_afmoe.ends(
        key, sizes, jnp.float32)["embed"][tokens]
        * np.sqrt(sizes["hidden_size"]).astype(np.float32))


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, precision):
    sizes = dict(frozen)

    def run(key, x):
        e = weights_afmoe.ends(key, sizes, jnp.float32)
        return linear(rms(x, e["norm"], sizes["rms_norm_eps"]),
                      e["lm_head"], precision)
    return jax.jit(run)


def decoder_logits(seed, sizes, tokens, first, count, precision="f32"):
    """Logits [S, count, V] (float32, host) of positions ``first[s] ..
    first[s]+count-1`` for token rows ``tokens`` [S, T], one teacher-forced
    pass, layer by layer (one layer's float32 weights live at a time)."""
    frozen = weights_afmoe.frozen(sizes)
    key = weights_afmoe.root_key(seed)
    biases = router_biases(seed, sizes)
    with jax.default_matmul_precision("highest"):
        x = _embed_fn(frozen)(key, jnp.asarray(tokens, jnp.int32))
        for i in range(sizes["num_hidden_layers"]):
            x = _layer_fn(frozen, i, precision)(key, x, biases[i])
        idx = jnp.asarray(first)[:, None] + jnp.arange(count)[None, :]
        picked = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        return np.asarray(_head_fn(frozen, precision)(key, picked))
