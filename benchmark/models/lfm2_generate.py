"""Builder of the LFM2-MoE family (LFM2-8B-A1B: gated short-convolution
mixers beside QK-normed GQA attention at 64-wide heads, a sigmoid top-k
router with an expert bias over experts with no shared expert, the head
tied to the embedding) for a repository entry ``{"builder":
"lfm2_generate", "name", "sizes", "max_seq", "max_slots", "page_size",
"kv_pages", "attn_impl"}``: the program's ``LlamaGenerateModel`` on the
continuous-batching scheduler, one decoder family
(``tpuserver.models.llama`` reads the block as data), as ONE PIPELINE
STAGE of the deployment the configuration states (every layer whole on
its chip), handed the benchmark's weights through ``params=``.

Like every builder it also brings what the yardstick needs to know of
its family and of nothing else: how its executables are told apart in a
device trace (``TRACE_LABELS``, ``SCOPES``), the work the algorithm
needs for what they served
(``work``, arithmetic in ``roofline_lfm2.py``), and its plain reference
(``reference_logits``).
"""

import reference_lfm2
import roofline_lfm2
import weights_lfm2
from models.afmoe_generate import SCOPES, Handed, routed  # noqa: F401
from models.llama_generate import (  # noqa: F401
    TRACE_LABELS, decode_contexts, prompt_tokens)

SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_attention_heads", "num_key_value_heads",
             "num_hidden_layers", "vocab_size", "rope_theta", "norm_eps",
             "conv_L_cache", "conv_bias", "num_experts",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "use_expert_bias")


def sizes_of(config, entry):
    """The configuration as run: the published keys, and from
    ``layers_run`` the kind of each layer that is run (its mixer from
    the published ``layer_types``, dense below the published
    ``num_dense_layers``).  The head size is hidden / heads, as the
    published code derives it."""
    group = config if entry["sizes"] == "top-level" else config[entry["sizes"]]
    sizes = {k: group[k] for k in SIZE_KEYS}
    if sizes["conv_bias"] or not sizes["use_expert_bias"]:
        raise ValueError("the family's convolution has no bias and its "
                         "router an expert bias; {} states otherwise"
                         .format(entry["name"]))
    sizes["head_dim"] = sizes["hidden_size"] // sizes["num_attention_heads"]
    run = group["layers_run"]
    dense_below = group["published"]["num_dense_layers"]
    if len(run) != sizes["num_hidden_layers"]:
        raise ValueError("layers_run names {} layers, num_hidden_layers is "
                         "{}".format(len(run), sizes["num_hidden_layers"]))
    kinds = {"conv": "conv", "full_attention": "full"}
    sizes["layer_types"] = [kinds[group["layer_types"][i]] for i in run]
    sizes["ffn_types"] = ["dense" if i < dense_below else "moe" for i in run]
    if sizes["ffn_types"].count("dense") != group["num_dense_layers"]:
        raise ValueError("layers_run holds {} dense layers, num_dense_layers "
                         "is {}".format(sizes["ffn_types"].count("dense"),
                                        group["num_dense_layers"]))
    return sizes


def build(config, entry):
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    import jax.numpy as jnp

    if "conv_len" not in getattr(llama.LlamaConfig, "__dataclass_fields__",
                                 {}):
        raise RuntimeError("this program has no short-convolution mixer "
                           "(tpuserver.models.llama.LlamaConfig.conv_len)")
    s = sizes_of(config, entry)
    cfg = llama.LlamaConfig(
        dtype=jnp.dtype(entry.get("dtype", "bfloat16")).type,
        vocab=s["vocab_size"], d_model=s["hidden_size"],
        n_layers=s["num_hidden_layers"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_head=s["head_dim"],
        d_ff=s["intermediate_size"], rope_theta=float(s["rope_theta"]),
        norm_eps=s["norm_eps"], attn_impl=entry["attn_impl"],
        decode_impl="pallas", qk_norm=True,
        # the paged decode kernel copies whole 128-lane tiles: 64-wide
        # heads lie in 128 lanes of the pool
        kv_lanes=-(-s["head_dim"] // 128) * 128,
        layer_types=tuple(s["layer_types"]), conv_len=s["conv_L_cache"],
        ffn_types=tuple(s["ffn_types"]),
        moe=llama.MoEConfig(
            n_experts=s["num_experts"], top_k=s["num_experts_per_tok"],
            d_expert=s["moe_intermediate_size"],
            route_norm=s["norm_topk_prob"],
            route_scale=float(s["routed_scaling_factor"]), n_shared=0,
            route_eps=reference_lfm2.ROUTE_EPS),
        tie_embed=True)
    handed = Handed()
    model = LlamaGenerateModel(
        cfg=cfg, max_seq=entry["max_seq"], max_slots=entry["max_slots"],
        page_size=entry["page_size"], kv_pages=entry.get("kv_pages"),
        params=handed)
    model.name = entry["name"]
    model.bench_weights = handed
    return model


def load(model, config, entry, seed):
    """Weights from the seed (one jitted call a layer; the routers'
    expert biases balanced first, before the served tree takes its room),
    handed to the model's own load (scheduler, page pool).  Compiles
    nothing of the model."""
    import jax

    sizes = sizes_of(config, entry)
    biases = reference_lfm2.router_biases(seed, sizes)
    tree = weights_lfm2.weights(seed, sizes, biases)
    if "dtype" in entry:
        tree = jax.tree_util.tree_map(
            lambda leaf: leaf.astype(entry["dtype"]), tree)
    model.bench_weights.tree = jax.block_until_ready(tree)
    model.warmup()


def reference_logits(seed, sizes, tokens, first, count, precision="f32"):
    """The family's plain reference (``reference_lfm2.decoder_logits``)."""
    return reference_lfm2.decoder_logits(seed, sizes, tokens, first, count,
                                         precision)


def work(ctx, entry, scope, runs):
    """``(flops, bytes)`` the algorithm needs for what ``runs`` of the
    scope's executable served in the traced interval, or None where that
    cannot be told."""
    s = sizes_of(ctx.config, entry)
    if scope in ("prefill", "flash_prefill"):
        lengths = prompt_tokens(runs)
        if len(lengths) != len(runs):
            return None
        fn = (roofline_lfm2.flash_prefill_work if scope == "flash_prefill"
              else roofline_lfm2.prefill_work)
        parts = [fn(s, n) for n in lengths]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)
    contexts = decode_contexts(ctx)
    if not contexts:
        return None
    if scope == "decode_attention":
        return roofline_lfm2.decode_attention_work(s, contexts)
    hit = routed(ctx, entry, s, len(runs))
    if hit is None:
        return None
    if scope == "moe_experts":
        return roofline_lfm2.experts_work(s, *hit)
    return roofline_lfm2.decode_step_work(s, contexts, len(runs), *hit)
