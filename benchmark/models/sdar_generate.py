"""Builder of the SDAR family (a Qwen3-MoE block: rotary GQA with
per-head QK norm, every layer routed by a softmax top-k with no shared
expert and no router bias; generation by diffusion over blocks) for a
repository entry ``{"builder": "sdar_generate", "name", "sizes",
"max_seq", "max_slots", "page_size", "kv_pages", "attn_impl"}``: the
program's ``LlamaGenerateModel`` on the continuous-batching scheduler,
one decoder family (``tpuserver.models.llama`` reads the block as data,
``block_len`` > 0 gives the block step), handed the benchmark's weights
through ``params=``.

Like every builder it also brings what the yardstick needs to know of
its family and of nothing else: how its executables are told apart in a
device trace (``TRACE_LABELS``, ``SCOPES``), the work the algorithm needs
for what they served (``work``, arithmetic in ``roofline_sdar.py``), and
its plain reference (``reference_logits``, as the ``generate_blocks``
kind calls it: replayed passes).
"""

import dataclasses

import counters
import reference_sdar
import roofline_sdar
import weights_sdar
from models.afmoe_generate import Handed
# one decoder family in the program: the block step holds the paged
# decode kernel and the prefill the flash kernel, as the plain block's do
from models.llama_generate import TRACE_LABELS, prompt_tokens  # noqa: F401

SCOPES = {
    "decode_step": {"label": "decode_step"},
    "prefill": {"label": "prefill"},
    "decode_attention": {"label": "decode_step", "op": "decode_attention"},
    "flash_prefill": {"label": "prefill", "op": "flash_attention"},
    "moe_experts": {"label": "decode_step", "op": "moe_grouped_matmul"},
}

SIZE_KEYS = ("hidden_size", "moe_intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_hidden_layers",
             "vocab_size", "rope_theta", "rms_norm_eps", "num_experts",
             "num_experts_per_tok")
GENERATION_KEYS = ("block_length", "mask_token_id")


def sizes_of(config, entry):
    """The configuration as run: the published keys, and the block
    length and mask token its ``generation`` group assumes."""
    group = config if entry["sizes"] == "top-level" else config[entry["sizes"]]
    sizes = {k: group[k] for k in SIZE_KEYS}
    sizes.update((k, group["generation"][k]) for k in GENERATION_KEYS)
    if not group["norm_topk_prob"] or group["mlp_only_layers"] \
            or group["decoder_sparse_step"] != 1:
        raise ValueError("models/sdar_generate.py builds every layer routed "
                         "with normalised top-k weights; {} states "
                         "otherwise".format(entry["name"]))
    return sizes


def build(config, entry):
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    if "block_len" not in {f.name for f in dataclasses.fields(
            llama.LlamaConfig)}:
        raise RuntimeError("this program has no block step (LlamaConfig has "
                           "no block_len): it cannot serve " + entry["name"])
    s = sizes_of(config, entry)
    layers = s["num_hidden_layers"]
    cfg = llama.LlamaConfig(
        vocab=s["vocab_size"], d_model=s["hidden_size"], n_layers=layers,
        n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_head=s["head_dim"],
        d_ff=0, rope_theta=float(s["rope_theta"]),
        norm_eps=s["rms_norm_eps"], attn_impl=entry["attn_impl"],
        decode_impl="pallas", qk_norm=True, ffn_types=("moe",) * layers,
        moe=llama.MoEConfig(
            n_experts=s["num_experts"], top_k=s["num_experts_per_tok"],
            d_expert=s["moe_intermediate_size"], route_norm=True,
            route_scale=1.0, score_func="softmax", router_bias=False,
            n_shared=0),
        block_len=s["block_length"], mask_id=s["mask_token_id"])
    handed = Handed()
    model = LlamaGenerateModel(
        cfg=cfg, max_seq=entry["max_seq"], max_slots=entry["max_slots"],
        page_size=entry["page_size"], kv_pages=entry.get("kv_pages"),
        params=handed)
    model.name = entry["name"]
    model.bench_weights = handed
    return model


def load(model, config, entry, seed):
    """Weights from the seed (one jitted call a layer), handed to the
    model's own load (scheduler, page pool).  Compiles nothing of the
    model."""
    import jax

    model.bench_weights.tree = jax.block_until_ready(
        weights_sdar.weights(seed, sizes_of(config, entry)))
    model.warmup()


def reference_logits(seed, sizes, tokens, starts, blocks, precision="f32"):
    """The family's plain reference as its kind calls it
    (``reference_sdar.replay_logits``): the logits of replayed passes."""
    return reference_sdar.replay_logits(seed, sizes, tokens, starts, blocks,
                                        precision)


def pass_contexts(ctx, s):
    """The keys attended (``start + B``) of every row pass of the blocks
    that arrived in the traced interval: a block's denoise passes and one
    commit pass (its predecessor's, which ran between the two)."""
    lo, hi = ctx.trace_data.interval()
    b = s["block_length"]
    return [blk.positions[0] // b * b + b
            for r in ctx.kind.records(ctx) for blk in r.blocks
            if lo <= blk.time < hi
            for _ in range(max(blk.passes) + 2)]


def routed(ctx, entry, s, steps):
    """``(pairs, experts read)`` of ``steps`` block steps: the program's
    counters over the window, scaled to the traced steps.  None where the
    program has no such counters."""
    per = [counters.delta(ctx, name, model=entry["name"]) for name in (
        "tpu_moe_layer_steps_total", "tpu_moe_local_pairs_total",
        "tpu_moe_experts_hit_total")]
    if None in per or not per[0]:
        return None
    layer_steps = steps * s["num_hidden_layers"]
    return per[1] / per[0] * layer_steps, per[2] / per[0] * layer_steps


def work(ctx, entry, scope, runs):
    """``(flops, bytes)`` the algorithm needs for what ``runs`` of the
    scope's executable served in the traced interval, or None where that
    cannot be told."""
    s = sizes_of(ctx.config, entry)
    if scope in ("prefill", "flash_prefill"):
        lengths = prompt_tokens(runs)
        if len(lengths) != len(runs):
            return None
        fn = (roofline_sdar.flash_prefill_work if scope == "flash_prefill"
              else roofline_sdar.prefill_work)
        parts = [fn(s, n) for n in lengths]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)
    contexts = pass_contexts(ctx, s)
    if not contexts:
        return None
    if scope == "decode_attention":
        return roofline_sdar.decode_attention_work(s, contexts)
    hit = routed(ctx, entry, s, len(runs))
    if hit is None:
        return None
    if scope == "moe_experts":
        return roofline_sdar.experts_work(s, *hit)
    return roofline_sdar.decode_step_work(s, contexts, len(runs), *hit)
