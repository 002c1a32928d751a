"""Weights of the LFM2-MoE family (LFM2-8B-A1B: gated short-convolution
mixers and QK-normed GQA attention, a sigmoid-routed feed-forward with
an expert bias) from ``--seed``, made by the benchmark, on the device, in
the type they are served in.  The configuration is one pipeline stage
whose layers are whole on its chip: every expert of each routed layer
and the whole vocabulary are held here.

The program is handed these (``LlamaGenerateModel(params=...)``); the
plain reference (``reference_lfm2.py``) makes the SAME values again from
the same seed, leaf by leaf and layer by layer, and takes nothing the
program made.  Every leaf has a key of its own (seed -> part -> leaf).

Tree (the layout ``tpuserver.models.llama`` serves):
  {embed [V,D], layers: [{attn_norm, mlp_norm, and conv_in [D,3D]
  (``in_proj``: the rows of B, C, x~ in that order), conv_w [L,D] (the
  depthwise taps, ``conv.weight[:, 0, :]`` transposed), conv_out [D,D]
  (a conv layer) or wq, wk, wv, wo, q_norm, k_norm (an attention
  layer); and w_gate / w_up / w_down (dense) or router [D,E],
  router_bias [E], we_gate / we_up [E,D,F], we_down [E,F,D] (routed)}],
  norm}; the head is the embedding's transpose (tied).
Matrices ~ N(0, 1/fan_in) (the taps' fan-in is L), norm gains ~ 1 + 0.1
N(0,1), both rounded to bf16.  The router's expert biases (float32) are
trained by the balancing rule and not given by the config (``assumed``):
they are HANDED to ``layer`` and ``weights``, solved from the same seed
by that rule on a sample of the seed's own hidden states
(``reference_lfm2.router_biases``).
"""

import functools

import jax
import jax.numpy as jnp

# the seed -> key rule and the leaf makers are the decoder's
from weights import _gain, _matrix, root_key  # noqa: F401
from weights_afmoe import frozen, sample_tokens  # noqa: F401


def kind_of(sizes, i):
    """``(mixer, feed-forward)`` of layer ``i`` of the layers as run:
    what decides a layer's leaves; its values come from ``i`` alone."""
    return sizes["layer_types"][i], sizes["ffn_types"][i]


def layer(key, sizes, i, dtype=jnp.bfloat16, bias=None, kind=None):
    """Layer ``i`` of the layers as run; ``dtype`` float32 gives the
    reference the served bf16 values exactly.  ``bias``: a routed
    layer's expert biases [num_experts] float32.  With ``kind``
    (``kind_of``) given, ``i`` may be traced: one compiled program then
    makes every layer of that kind."""
    mixer, ffn = kind or kind_of(sizes, i)
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 21), i),
                          16)
    out = {"attn_norm": _gain(ks[0], d, dtype),
           "mlp_norm": _gain(ks[1], d, dtype)}
    if mixer == "conv":
        taps = sizes["conv_L_cache"]
        out.update({
            "conv_in": _matrix(ks[2], (d, 3 * d), d, dtype),
            "conv_w": _matrix(ks[3], (taps, d), taps, dtype),
            "conv_out": _matrix(ks[4], (d, d), d, dtype),
        })
    else:
        nq = sizes["num_attention_heads"] * hd
        nkv = sizes["num_key_value_heads"] * hd
        out.update({
            "wq": _matrix(ks[2], (d, nq), d, dtype),
            "wk": _matrix(ks[3], (d, nkv), d, dtype),
            "wv": _matrix(ks[4], (d, nkv), d, dtype),
            "wo": _matrix(ks[5], (nq, d), nq, dtype),
            "q_norm": _gain(ks[6], hd, dtype),
            "k_norm": _gain(ks[7], hd, dtype),
        })
    if ffn == "dense":
        ff = sizes["intermediate_size"]
        out.update({
            "w_gate": _matrix(ks[8], (d, ff), d, dtype),
            "w_up": _matrix(ks[9], (d, ff), d, dtype),
            "w_down": _matrix(ks[10], (ff, d), ff, dtype),
        })
        return out
    f, e = sizes["moe_intermediate_size"], sizes["num_experts"]
    out.update({
        "router": _matrix(ks[11], (d, e), d, dtype),
        "router_bias": bias,
        "we_gate": _matrix(ks[12], (e, d, f), d, dtype),
        "we_up": _matrix(ks[13], (e, d, f), d, dtype),
        "we_down": _matrix(ks[14], (e, f, d), f, dtype),
    })
    return out


def ends(key, sizes, dtype=jnp.bfloat16):
    """The embedding (also the head: tied) and the final norm."""
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 22), 2)
    return {"embed": _matrix(ks[0], (v, d), d, dtype),
            "norm": _gain(ks[1], d, dtype)}


@functools.lru_cache(maxsize=None)
def _jits(frozen_sizes):
    sizes = dict(frozen_sizes)
    return (jax.jit(lambda key, i, bias, kind: layer(
                key, sizes, i, bias=bias, kind=kind), static_argnums=3),
            jax.jit(lambda key: ends(key, sizes)))


def weights(seed, sizes, biases):
    """The whole served tree, on the device, in bf16: one jitted call a
    layer (its float32 intermediates never pile up beside the results),
    one program a kind of layer (the index is an argument: a compile of
    such a program for the chip takes ~25 s), and one for the embedding
    and the final norm.  ``biases``: per layer the expert biases (None
    for a dense layer)."""
    layer_fn, ends_fn = _jits(frozen(sizes))
    key = root_key(seed)
    tree = ends_fn(key)
    tree["layers"] = [layer_fn(key, jnp.int32(i), biases[i],
                               kind_of(sizes, i))
                      for i in range(sizes["num_hidden_layers"])]
    return tree
