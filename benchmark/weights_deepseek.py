"""Weights of the DeepSeek-V3 family from ``--seed``, made by the
benchmark, on the device, in the type they are served in, as ONE CHIP'S
SHARE of the deployment the configuration states: the held experts of
each routed layer and the held rows of the embedding and the head.

The program is handed these (``LlamaGenerateModel(params=...)``); the
plain reference (``reference_deepseek.py``) makes the SAME values again
from the same seed, leaf by leaf and layer by layer, and takes nothing
the program made.  Every leaf has a key of its own (seed -> part ->
leaf).

Tree (the layout ``tpuserver.models.llama`` serves under latent
attention):
  {embed [V,D], layers: [{attn_norm, wq_a [D,q_lora], q_a_norm, wq_b
  [q_lora, H*(nope+rope)], wkv_a [D, kv_lora+rope], kv_a_norm, w_uk
  [H,nope,kv_lora], w_uv [H,kv_lora,v], wo [H*v,D], mlp_norm, and w_gate
  / w_up / w_down (dense) or router [D,E], router_bias [E], ws_gate /
  ws_up / ws_down, we_gate / we_up [held,D,F], we_down [held,F,D]
  (routed)}], norm, lm_head [D,V]}
Matrices ~ N(0, 1/fan_in), norm gains ~ 1 + 0.1 N(0,1), both rounded to
bf16.  The published ``kv_b_proj`` [H*(nope+v), kv_lora] is held as its
two parts, the keys' up-projection ``w_uk`` and the values' ``w_uv``
(head ``i``'s rows of it, split at ``nope``): the same numbers in two
leaves, fan-in ``kv_lora`` both.  ``e_score_correction_bias`` (float32)
is trained by the balancing rule and not given by the config
(``assumed``): it is HANDED to ``layer`` and ``weights``, solved from
the same seed by that rule on a sample of the seed's own hidden states
(``reference_deepseek.router_biases``).
"""

import functools

import jax
import jax.numpy as jnp

# the seed -> key rule and the leaf makers are the decoder's
from weights import _gain, _matrix, root_key  # noqa: F401
from weights_afmoe import frozen, sample_tokens  # noqa: F401


def layer(key, sizes, i, dtype=jnp.bfloat16, bias=None):
    """Layer ``i`` of the layers as run; ``dtype`` float32 gives the
    reference the served bf16 values exactly.  ``bias``: a routed
    layer's expert biases [router_experts] float32.

    The leaves are made ONE AFTER ANOTHER (each leaf's key passes a
    barrier with the leaf before it): a routed layer is 0.94 G values,
    and a compiler free to draw every leaf's random bits at once holds
    three float32 copies of the layer beside four layers already made."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    ql, kvl = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    plan = [
        ("attn_norm", _gain, (d,)),
        ("wq_a", _matrix, ((d, ql), d)),
        ("q_a_norm", _gain, (ql,)),
        ("wq_b", _matrix, ((ql, h * (nope + rope)), ql)),
        ("wkv_a", _matrix, ((d, kvl + rope), d)),
        ("kv_a_norm", _gain, (kvl,)),
        ("w_uk", _matrix, ((h, nope, kvl), kvl)),
        ("w_uv", _matrix, ((h, kvl, dv), kvl)),
        ("wo", _matrix, ((h * dv, d), h * dv)),
        ("mlp_norm", _gain, (d,)),
    ]
    if sizes["ffn_types"][i] == "dense":
        ff = sizes["intermediate_size"]
        plan += [("w_gate", _matrix, ((d, ff), d)),
                 ("w_up", _matrix, ((d, ff), d)),
                 ("w_down", _matrix, ((ff, d), ff))]
    else:
        f, held = sizes["moe_intermediate_size"], sizes["n_routed_experts"]
        fs = f * sizes["n_shared_experts"]
        plan += [("router", _matrix, ((d, sizes["router_experts"]), d)),
                 ("ws_gate", _matrix, ((d, fs), d)),
                 ("ws_up", _matrix, ((d, fs), d)),
                 ("ws_down", _matrix, ((fs, d), fs)),
                 ("we_gate", _matrix, ((held, d, f), d)),
                 ("we_up", _matrix, ((held, d, f), d)),
                 ("we_down", _matrix, ((held, f, d), f))]
    # the held experts' values belong to the share (its first expert)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, 31), i), sizes["expert_first"]), len(plan))
    out = {}
    for n, (name, make, args) in enumerate(plan):
        out[name] = make(ks[n], *args, dtype)
        ks, out[name] = jax.lax.optimization_barrier((ks, out[name]))
    if "router" in out:
        out["router_bias"] = bias
    return out


def ends(key, sizes, dtype=jnp.bfloat16):
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    ks = jax.random.split(jax.random.fold_in(key, 32), 3)
    return {
        "embed": _matrix(ks[0], (v, d), d, dtype),
        "norm": _gain(ks[1], d, dtype),
        "lm_head": _matrix(ks[2], (d, v), d, dtype),
    }


@functools.lru_cache(maxsize=None)
def _jits(frozen_sizes):
    sizes = dict(frozen_sizes)
    return (jax.jit(lambda key, i, bias: layer(key, sizes, i, bias=bias),
                    static_argnums=1),
            jax.jit(lambda key: ends(key, sizes)))


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
