"""Builder of the DeepSeek-V3 family (multi-head latent attention over a
latent page class, YaRN rotary pairs, a group-limited sigmoid router over
routed experts with a shared expert) for a repository entry ``{"builder":
"deepseek_generate", "name", "sizes", "max_seq", "max_slots",
"page_size", "kv_pages", "attn_impl"}`` (and ``"dtype": "float32"`` in the
dry run alone: at toy widths a bf16 near-tie swaps a whole GROUP of the
router's experts and moves a logit by as much as an altered token does,
so the CPU self-tests serve the seed's bf16 values in float32): the program's
``LlamaGenerateModel`` on the continuous-batching scheduler, one decoder
family (``tpuserver.models.llama`` reads the block as data), as ONE
CHIP'S SHARE of the deployment the configuration states, handed the
benchmark's weights through ``params=``.

Like every builder it also brings what the yardstick needs to know of
its family and of nothing else: how its executables are told apart in a
device trace (``TRACE_LABELS``, ``SCOPES``: the decode kernel is
``latent_decode_attention``, the prefill's ``flash_attention`` at the
expanded head sizes), the work the algorithm needs for what they served
(``work``, arithmetic in ``roofline_deepseek.py``), and its plain
reference (``reference_logits``).
"""

import reference_deepseek
import roofline_deepseek
import weights_deepseek
from models.afmoe_generate import SCOPES, Handed, routed  # noqa: F401
from models.llama_generate import (  # noqa: F401
    TRACE_LABELS, decode_contexts, prompt_tokens)

SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "num_hidden_layers", "vocab_size", "rope_theta", "rms_norm_eps",
             "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
             "n_group", "topk_group", "norm_topk_prob",
             "routed_scaling_factor")
ROPE_KEYS = {"factor": "rope_factor",
             "original_max_position_embeddings": "rope_orig_max",
             "beta_fast": "beta_fast", "beta_slow": "beta_slow",
             "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def sizes_of(config, entry):
    """The configuration as run: the published keys, the YaRN group laid
    flat, and from ``deployment`` and ``layers_run`` the share held here
    and the kind of each layer that is run (dense below the published
    ``first_k_dense_replace``)."""
    group = config if entry["sizes"] == "top-level" else config[entry["sizes"]]
    sizes = {k: group[k] for k in SIZE_KEYS}
    if group["rope_scaling"]["type"] != "yarn":
        raise ValueError("rope_scaling of type {!r}: the family's is yarn"
                         .format(group["rope_scaling"]["type"]))
    sizes.update((name, group["rope_scaling"][k]) for k, name in ROPE_KEYS.items())
    run = group["layers_run"]
    dense_below = group["published"]["first_k_dense_replace"]
    if len(run) != sizes["num_hidden_layers"]:
        raise ValueError("layers_run names {} layers, num_hidden_layers is "
                         "{}".format(len(run), sizes["num_hidden_layers"]))
    sizes["ffn_types"] = ["dense" if i < dense_below else "moe" for i in run]
    if sizes["ffn_types"].count("dense") != group["first_k_dense_replace"]:
        raise ValueError("layers_run holds {} dense layers, "
                         "first_k_dense_replace is {}".format(
                             sizes["ffn_types"].count("dense"),
                             group["first_k_dense_replace"]))
    sizes["router_experts"] = group["deployment"]["router_experts"]
    sizes["expert_first"] = group["deployment"]["expert_first"]
    return sizes


def build(config, entry):
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    import jax.numpy as jnp

    if not hasattr(llama, "MLAConfig"):
        raise RuntimeError("this program has no latent attention "
                           "(tpuserver.models.llama.MLAConfig)")
    s = sizes_of(config, entry)
    cfg = llama.LlamaConfig(
        dtype=jnp.dtype(entry.get("dtype", "bfloat16")).type,
        vocab=s["vocab_size"], d_model=s["hidden_size"],
        n_layers=s["num_hidden_layers"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_attention_heads"], d_ff=s["intermediate_size"],
        rope_theta=float(s["rope_theta"]), norm_eps=s["rms_norm_eps"],
        attn_impl=entry["attn_impl"], decode_impl="pallas",
        ffn_types=tuple(s["ffn_types"]),
        moe=llama.MoEConfig(
            n_experts=s["router_experts"], top_k=s["num_experts_per_tok"],
            d_expert=s["moe_intermediate_size"],
            route_norm=s["norm_topk_prob"],
            route_scale=s["routed_scaling_factor"], first=s["expert_first"],
            count=s["n_routed_experts"], n_shared=s["n_shared_experts"],
            n_group=s["n_group"], topk_group=s["topk_group"]),
        mla=llama.MLAConfig(
            q_lora=s["q_lora_rank"], kv_lora=s["kv_lora_rank"],
            d_nope=s["qk_nope_head_dim"], d_rope=s["qk_rope_head_dim"],
            d_v=s["v_head_dim"], rope_factor=float(s["rope_factor"]),
            rope_orig_max=s["rope_orig_max"],
            beta_fast=float(s["beta_fast"]), beta_slow=float(s["beta_slow"]),
            mscale=float(s["mscale"]),
            mscale_all_dim=float(s["mscale_all_dim"])))
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
    biases = reference_deepseek.router_biases(seed, sizes)
    tree = weights_deepseek.weights(seed, sizes, biases)
    if "dtype" in entry:
        tree = jax.tree_util.tree_map(
            lambda leaf: leaf.astype(entry["dtype"]), tree)
    model.bench_weights.tree = jax.block_until_ready(tree)
    model.warmup()


def reference_logits(seed, sizes, tokens, first, count, precision="f32"):
    """The family's plain reference (``reference_deepseek.decoder_logits``)."""
    return reference_deepseek.decoder_logits(seed, sizes, tokens, first,
                                             count, precision)


def work(ctx, entry, scope, runs):
    """``(flops, bytes)`` the algorithm needs for what ``runs`` of the
    scope's executable served in the traced interval, or None where that
    cannot be told."""
    s = sizes_of(ctx.config, entry)
    if scope in ("prefill", "flash_prefill"):
        lengths = prompt_tokens(runs)
        if len(lengths) != len(runs):
            return None
        fn = (roofline_deepseek.flash_prefill_work if scope == "flash_prefill"
              else roofline_deepseek.prefill_work)
        parts = [fn(s, n) for n in lengths]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)
    contexts = decode_contexts(ctx)
    if not contexts:
        return None
    if scope == "decode_attention":
        return roofline_deepseek.decode_attention_work(s, contexts)
    hit = routed(ctx, entry, s, len(runs))
    if hit is None:
        return None
    if scope == "moe_experts":
        return roofline_deepseek.experts_work(s, *hit)
    return roofline_deepseek.decode_step_work(s, contexts, len(runs), *hit)
