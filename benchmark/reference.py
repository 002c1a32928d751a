"""Plain references: the architectures' forward passes in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision: no kernels, no
cache, no batching tricks.  They import nothing of the program and take
nothing it made: weights come from ``weights.py`` and the seed.

``precision="int8"`` is the CONTROL, the step below the bf16 that the
configurations state: every linear layer runs on operands rounded to
int8, weights per output channel, activations per token, symmetric
absmax scale.  Products of such values are
exact in float32, so this is what such a path computes.

Decoder: Mistral-7B-v0.3's block as ``transformers`` MistralForCausalLM
states it: RMSNorm, grouped-query attention with rotary embedding in the
half-split ("rotate_half") convention, SwiGLU, untied head, no sliding
window, no biases.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights

HIGHEST = lax.Precision.HIGHEST


def _q8(x, axis):
    """Round to the int8 grid along ``axis`` with a symmetric absmax
    scale, kept in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    return jnp.round(x / scale) * scale


def _linear(x, w, precision):
    if precision != "f32":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x [T, H, D]; positions 0..T-1; half-split convention."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _decoder_block(w, x, sizes, precision):
    """One sequence through one block: x [T, D] float32."""
    t = x.shape[0]
    h, kv, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    y = _rms(x, w["attn_norm"], eps)
    q = _rope(_linear(y, w["wq"], precision).reshape(t, h, hd), theta)
    k = _rope(_linear(y, w["wk"], precision).reshape(t, kv, hd), theta)
    v = _linear(y, w["wv"], precision).reshape(t, kv, hd)
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    s = jnp.einsum("thd,uhd->htu", q, k, precision=HIGHEST) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    a = jnp.einsum("htu,uhd->thd", p, v, precision=HIGHEST).reshape(t, h * hd)
    x = x + _linear(a, w["wo"], precision)
    y = _rms(x, w["mlp_norm"], eps)
    g = jax.nn.silu(_linear(y, w["w_gate"], precision))
    return x + _linear(g * _linear(y, w["w_up"], precision), w["w_down"],
                       precision)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, precision):
    sizes = dict(frozen)

    def run(key, i, x):
        w = weights.decoder_layer(key, sizes, i, jnp.float32)
        return lax.map(lambda row: _decoder_block(w, row, sizes, precision), x)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _embed_fn(frozen):
    sizes = dict(frozen)
    return jax.jit(lambda key, tokens: weights.decoder_ends(
        key, sizes, jnp.float32)["embed"][tokens])


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, precision):
    sizes = dict(frozen)

    def run(key, x):
        ends = weights.decoder_ends(key, sizes, jnp.float32)
        y = _rms(x, ends["norm"], sizes["rms_norm_eps"])
        return _linear(y, ends["lm_head"], precision)
    return jax.jit(run)


def decoder_logits(seed, sizes, tokens, first, count, precision="f32"):
    """Logits [S, count, V] (float32, host) of positions ``first[s] ..
    first[s]+count-1`` for token rows ``tokens`` [S, T], one teacher-forced
    pass, layer by layer (one layer's float32 weights live at a time)."""
    frozen = weights.frozen(sizes)
    key = weights.root_key(seed)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed_fn(frozen)(key, tokens)
    layer = _layer_fn(frozen, precision)
    for i in range(sizes["num_hidden_layers"]):
        x = layer(key, i, x)
    idx = jnp.asarray(first)[:, None] + jnp.arange(count)[None, :]
    picked = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    return np.asarray(_head_fn(frozen, precision)(key, picked))
