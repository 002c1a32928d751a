"""Weights of the SDAR family (softmax-routed experts with no shared
expert, QK-normed rotary GQA, generation by diffusion over blocks) from
``--seed``, made by the benchmark, on the device, in the type they are
served in.

The program is handed these (``LlamaGenerateModel(params=...)``); the
plain reference (``reference_sdar.py``) makes the SAME values again from
the same seed, leaf by leaf and layer by layer, and takes nothing the
program made.  Every leaf has a key of its own (seed -> part -> leaf).

Tree (the layout ``tpuserver.models.llama`` serves):
  {embed [V,D], layers: [{attn_norm, wq, wk, wv, wo, q_norm, k_norm,
  mlp_norm, router [D,E], we_gate / we_up [E,D,F], we_down [E,F,D]}],
  norm, lm_head [D,V]}
Matrices ~ N(0, 1/fan_in), norm gains ~ 1 + 0.1 N(0,1), both rounded to
bf16.  No router bias and no shared expert: the configuration has none.
"""

import functools

import jax
import jax.numpy as jnp

# the seed -> key rule and the leaf makers are the decoder's
from weights import _gain, _matrix, root_key  # noqa: F401


def layer(key, sizes, i, dtype=jnp.bfloat16):
    """Layer ``i`` of the layers as run; ``dtype`` float32 gives the
    reference the served bf16 values exactly."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nq, nkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    f, e = sizes["moe_intermediate_size"], sizes["num_experts"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 21), i), 12)
    return {
        "attn_norm": _gain(ks[0], d, dtype),
        "wq": _matrix(ks[1], (d, nq), d, dtype),
        "wk": _matrix(ks[2], (d, nkv), d, dtype),
        "wv": _matrix(ks[3], (d, nkv), d, dtype),
        "wo": _matrix(ks[4], (nq, d), nq, dtype),
        "q_norm": _gain(ks[5], hd, dtype),
        "k_norm": _gain(ks[6], hd, dtype),
        "mlp_norm": _gain(ks[7], d, dtype),
        "router": _matrix(ks[8], (d, e), d, dtype),
        "we_gate": _matrix(ks[9], (e, d, f), d, dtype),
        "we_up": _matrix(ks[10], (e, d, f), d, dtype),
        "we_down": _matrix(ks[11], (e, f, d), f, dtype),
    }


def ends(key, sizes, dtype=jnp.bfloat16):
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 22), 3)
    return {
        "embed": _matrix(ks[0], (v, d), d, dtype),
        "norm": _gain(ks[1], d, dtype),
        "lm_head": _matrix(ks[2], (d, v), d, dtype),
    }


def frozen(sizes):
    """The sizes as a hashable key for the jit caches."""
    return tuple(sorted(sizes.items()))


@functools.lru_cache(maxsize=None)
def _jits(frozen_sizes):
    sizes = dict(frozen_sizes)
    return (jax.jit(lambda key, i: layer(key, sizes, i), static_argnums=1),
            jax.jit(lambda key: ends(key, sizes)))


def weights(seed, sizes, dtype=jnp.bfloat16):
    """The whole served tree, on the device: one jitted call a layer (its
    float32 intermediates never pile up beside the results) and one for
    the embedding, the final norm and the head.  ``dtype`` float32 is for
    the reference at test sizes (the same bf16 values, held wider)."""
    key = root_key(seed)
    if dtype != jnp.bfloat16:
        tree = ends(key, sizes, dtype)
        tree["layers"] = [layer(key, sizes, i, dtype)
                          for i in range(sizes["num_hidden_layers"])]
        return tree
    layer_fn, ends_fn = _jits(frozen(sizes))
    tree = ends_fn(key)
    tree["layers"] = [layer_fn(key, i)
                      for i in range(sizes["num_hidden_layers"])]
    return tree
