"""Plain reference of the AFMoE block (the Trinity family: arcee-ai's
``modeling_afmoe.py`` in ``transformers``): the forward pass in
straightforward ``jax.numpy``, float32, ``highest`` matmul precision;
no kernel, no cache, no batching.  It imports nothing of the program
and takes a plain dict of sizes and a weight tree.

The equations (one sequence, x [T, D]):

- embedding: ``x = E[token] * embed_scale`` (``mup_enabled``:
  sqrt(hidden_size)).
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

Departures from the published code: the norm gains and the router's
expert biases are whatever the weight tree holds (the published ones are
trained; "depth-scaled" is an initialisation); ``held`` (``first``,
``count``) cuts the sum over experts to one process's share under expert
parallelism, where the published code sums over all (``count`` = all of
them is the published layer); ``n_group = topk_group = 1`` (no group
limit) is assumed and not computed.

``precision="int8"`` is the CONTROL one step below bf16: every linear
layer on operands rounded to int8 (weights per output channel,
activations per token, symmetric absmax).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


def layer(w, x, s, window, routed, precision="f32"):
    eps = s["eps"]
    a = attention(w, rms(x, w["attn_norm"], eps), s, window, precision)
    x = x + rms(a, w["attn_post_norm"], eps)
    y = rms(x, w["mlp_norm"], eps)
    f = (routed_ffn(w, y, s, precision) if routed
         else swiglu(y, w["w_gate"], w["w_up"], w["w_down"], precision))
    return x + rms(f, w["mlp_post_norm"], eps)


def logits(weights, tokens, s, precision="f32"):
    """Float32 logits [T, V] of one token row [T], the whole forward
    pass.  ``s``: n_heads, n_kv_heads, head_dim, eps, rope_theta, window,
    layer_types, ffn_types, top_k, route_norm, route_scale, first,
    embed_scale."""
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), weights)
        x = f32["embed"][jnp.asarray(tokens)] * s["embed_scale"]
        for i, w in enumerate(f32["layers"]):
            window = s["window"] if s["layer_types"][i] == "window" else 0
            x = layer(w, x, s, window, s["ffn_types"][i] == "moe", precision)
        return linear(rms(x, f32["norm"], s["eps"]), f32["lm_head"],
                      precision)
