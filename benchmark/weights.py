"""Weights from ``--seed``, made by the benchmark (the yardstick), on
the device, in the type they are served in.

The program is handed these; the plain reference makes the SAME values
again from the same seed, leaf by leaf, and takes nothing the program
made.  Every leaf has a key of its own (seed -> model part -> leaf), so
the reference can build one layer at a time.

Decoder tree (the layout ``tpuserver.models.llama`` serves):
  {embed [V,D], layers: [{attn_norm, wq, wk, wv, wo, mlp_norm, w_gate,
  w_up, w_down}], norm, lm_head [D,V]}
Matrices ~ N(0, 1/fan_in), norm gains ~ 1 + 0.1 N(0,1), rounded to bf16.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def _matrix(key, shape, fan_in, dtype):
    w = jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)
    return w.astype(jnp.bfloat16).astype(dtype)


def _gain(key, n, dtype):
    g = 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
    return g.astype(jnp.bfloat16).astype(dtype)


def decoder_layer(key, sizes, i, dtype=jnp.bfloat16):
    """Layer ``i`` of the decoder; ``dtype`` float32 gives the reference
    the served bf16 values exactly."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nq, nkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    ff = sizes["intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1), i), 9)
    return {
        "attn_norm": _gain(ks[0], d, dtype),
        "wq": _matrix(ks[1], (d, nq), d, dtype),
        "wk": _matrix(ks[2], (d, nkv), d, dtype),
        "wv": _matrix(ks[3], (d, nkv), d, dtype),
        "wo": _matrix(ks[4], (nq, d), nq, dtype),
        "mlp_norm": _gain(ks[5], d, dtype),
        "w_gate": _matrix(ks[6], (d, ff), d, dtype),
        "w_up": _matrix(ks[7], (d, ff), d, dtype),
        "w_down": _matrix(ks[8], (ff, d), ff, dtype),
    }


def decoder_ends(key, sizes, dtype=jnp.bfloat16):
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 2), 3)
    return {
        "embed": _matrix(ks[0], (v, d), d, dtype),
        "norm": _gain(ks[1], d, dtype),
        "lm_head": _matrix(ks[2], (d, v), d, dtype),
    }


def frozen(sizes):
    """The sizes as a hashable key for the jit caches."""
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float))))


@functools.lru_cache(maxsize=None)
def _decoder_jits(frozen):
    sizes = dict(frozen)
    return (jax.jit(lambda key, i: decoder_layer(key, sizes, i)),
            jax.jit(lambda key: decoder_ends(key, sizes)))


def decoder_weights(seed, sizes):
    """The whole served tree, on the device, in bf16: one jitted call per
    layer (one executable, run once per layer, so its float32
    intermediates never pile up beside 9 GB of results) and one for the
    embedding, the final norm and the head."""
    layer, ends = _decoder_jits(frozen(sizes))
    key = root_key(seed)
    tree = ends(key)
    tree["layers"] = [layer(key, i)
                      for i in range(sizes["num_hidden_layers"])]
    return tree
