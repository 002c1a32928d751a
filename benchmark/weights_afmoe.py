"""Weights of the AFMoE family (Trinity) from ``--seed``, made by the
benchmark, on the device, in the type they are served in, as ONE CHIP'S
SHARE of the deployment the configuration states: the held experts of
each routed layer and the held rows of the embedding and the head.

The program is handed these (``LlamaGenerateModel(params=...)``); the
plain reference (``reference_afmoe.py``) makes the SAME values again
from the same seed, leaf by leaf and layer by layer, and takes nothing
the program made.  Every leaf has a key of its own (seed -> part ->
leaf).

Tree (the layout ``tpuserver.models.llama`` serves):
  {embed [V,D], layers: [{attn_norm, wq, wk, wv, wg, wo, q_norm, k_norm,
  attn_post_norm, mlp_norm, mlp_post_norm, and w_gate / w_up / w_down
  (dense) or router [D,E], router_bias [E], ws_gate / ws_up / ws_down,
  we_gate / we_up [held,D,F], we_down [held,F,D] (routed)}], norm,
  lm_head [D,V]}
Matrices ~ N(0, 1/fan_in), norm gains ~ 1 + 0.1 N(0,1), both rounded to
bf16.  The router's expert biases (float32) are trained by the balancing
rule and not given by the config (``assumed``): they are HANDED to
``layer`` and ``weights``, solved from the same seed by that rule on a
sample of the seed's own hidden states
(``reference_afmoe.router_biases``), so that the seeded router spreads
its choices over the experts as a trained one does.
"""

import functools

import jax
import jax.numpy as jnp

# the seed -> key rule and the leaf makers are the decoder's
from weights import _gain, _matrix, root_key  # noqa: F401


def layer(key, sizes, i, dtype=jnp.bfloat16, bias=None):
    """Layer ``i`` of the layers as run; ``dtype`` float32 gives the
    reference the served bf16 values exactly.  ``bias``: a routed
    layer's expert biases [router_experts] float32."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    nq, nkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 11), i), 20)
    out = {
        "attn_norm": _gain(ks[0], d, dtype),
        "wq": _matrix(ks[1], (d, nq), d, dtype),
        "wk": _matrix(ks[2], (d, nkv), d, dtype),
        "wv": _matrix(ks[3], (d, nkv), d, dtype),
        "wg": _matrix(ks[4], (d, nq), d, dtype),
        "wo": _matrix(ks[5], (nq, d), nq, dtype),
        "q_norm": _gain(ks[6], hd, dtype),
        "k_norm": _gain(ks[7], hd, dtype),
        "attn_post_norm": _gain(ks[8], d, dtype),
        "mlp_norm": _gain(ks[9], d, dtype),
        "mlp_post_norm": _gain(ks[10], d, dtype),
    }
    if sizes["ffn_types"][i] == "dense":
        ff = sizes["intermediate_size"]
        out.update({
            "w_gate": _matrix(ks[11], (d, ff), d, dtype),
            "w_up": _matrix(ks[12], (d, ff), d, dtype),
            "w_down": _matrix(ks[13], (ff, d), ff, dtype),
        })
        return out
    f, held = sizes["moe_intermediate_size"], sizes["num_experts"]
    out.update({
        "router": _matrix(ks[11], (d, sizes["router_experts"]), d, dtype),
        "router_bias": bias,
        "ws_gate": _matrix(ks[13], (d, f), d, dtype),
        "ws_up": _matrix(ks[14], (d, f), d, dtype),
        "ws_down": _matrix(ks[15], (f, d), f, dtype),
        "we_gate": _matrix(ks[16], (held, d, f), d, dtype),
        "we_up": _matrix(ks[17], (held, d, f), d, dtype),
        "we_down": _matrix(ks[18], (held, f, d), f, dtype),
    })
    return out


def ends(key, sizes, dtype=jnp.bfloat16):
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 12), 3)
    return {
        "embed": _matrix(ks[0], (v, d), d, dtype),
        "norm": _gain(ks[1], d, dtype),
        "lm_head": _matrix(ks[2], (d, v), d, dtype),
    }


def frozen(sizes):
    """The sizes as a hashable key for the jit caches."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in sizes.items()))


@functools.lru_cache(maxsize=None)
def _jits(frozen_sizes):
    sizes = dict(frozen_sizes)
    return (jax.jit(lambda key, i, bias: layer(key, sizes, i, bias=bias),
                    static_argnums=1),
            jax.jit(lambda key: ends(key, sizes)))


def sample_tokens(key, sizes, rows, tokens):
    """The token rows the expert biases are balanced on."""
    return jax.random.randint(jax.random.fold_in(key, 13), (rows, tokens), 0,
                              sizes["vocab_size"], jnp.int32)


def weights(seed, sizes, biases):
    """The whole served tree, on the device, in bf16: one jitted call a
    layer (its float32 intermediates never pile up beside the results)
    and one for the embedding, the final norm and the head.  ``biases``:
    per layer the expert biases (None for a dense layer)."""
    layer_fn, ends_fn = _jits(frozen(sizes))
    key = root_key(seed)
    tree = ends_fn(key)
    tree["layers"] = [layer_fn(key, i, biases[i])
                      for i in range(sizes["num_hidden_layers"])]
    return tree
