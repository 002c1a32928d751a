"""Builder of the AFMoE family (Trinity: sigmoid-routed experts with a
shared expert, gated QK-normed attention, window layers with rotary
positions beside position-free full layers) for a repository entry
``{"builder": "afmoe_generate", "name", "sizes", "max_seq", "max_slots",
"page_size", "kv_pages", "kv_window_pages", "attn_impl"}``: the
program's ``LlamaGenerateModel`` on the continuous-batching scheduler,
one decoder family (``tpuserver.models.llama`` reads the block as data),
as ONE CHIP'S SHARE of the deployment the configuration states, handed
the benchmark's weights through ``params=``.

Like every builder it also brings what the yardstick needs to know of
its family and of nothing else: how its executables are told apart in a
device trace (``TRACE_LABELS``, ``SCOPES``), the work the algorithm needs
for what they served (``work``, arithmetic in ``roofline_afmoe.py``), and
its plain reference (``reference_logits``).
"""

import counters
import reference_afmoe
import roofline_afmoe
import weights_afmoe
# one decoder family in the program: its executables are told apart, and
# its prompts and contexts read off a trace, as the plain block's are
from models.llama_generate import (  # noqa: F401
    TRACE_LABELS, decode_contexts, prompt_tokens)

SCOPES = {
    "decode_step": {"label": "decode_step"},
    "prefill": {"label": "prefill"},
    "decode_attention": {"label": "decode_step", "op": "decode_attention"},
    "flash_prefill": {"label": "prefill", "op": "flash_attention"},
    "moe_experts": {"label": "decode_step", "op": "moe_grouped_matmul"},
}

SIZE_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "num_hidden_layers", "vocab_size", "rope_theta", "rms_norm_eps",
             "sliding_window", "num_experts", "num_experts_per_tok",
             "route_norm", "route_scale")


def sizes_of(config, entry):
    """The configuration as run: the published keys, and from
    ``deployment`` and ``layers_run`` the share held here and the kind of
    each layer that is run (attention from the published ``layer_types``,
    dense below the published ``num_dense_layers``)."""
    group = config if entry["sizes"] == "top-level" else config[entry["sizes"]]
    sizes = {k: group[k] for k in SIZE_KEYS}
    run = group["layers_run"]
    dense_below = group["published"]["num_dense_layers"]
    if len(run) != sizes["num_hidden_layers"]:
        raise ValueError("layers_run names {} layers, num_hidden_layers is "
                         "{}".format(len(run), sizes["num_hidden_layers"]))
    sizes["layer_types"] = [group["layer_types"][i] for i in run]
    sizes["ffn_types"] = ["dense" if i < dense_below else "moe" for i in run]
    if sizes["ffn_types"].count("dense") != group["num_dense_layers"]:
        raise ValueError("layers_run holds {} dense layers, num_dense_layers "
                         "is {}".format(sizes["ffn_types"].count("dense"),
                                        group["num_dense_layers"]))
    sizes["router_experts"] = group["deployment"]["router_experts"]
    sizes["expert_first"] = group["deployment"]["expert_first"]
    return sizes


class Handed:
    """The weights between the benchmark's ``load`` and the model's own:
    set once, taken once, so the model's ``_params`` is the only holder."""

    tree = None

    def __call__(self):
        tree, self.tree = self.tree, None
        return tree


def build(config, entry):
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    s = sizes_of(config, entry)
    cfg = llama.LlamaConfig(
        vocab=s["vocab_size"], d_model=s["hidden_size"],
        n_layers=s["num_hidden_layers"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_head=s["head_dim"],
        d_ff=s["intermediate_size"], rope_theta=float(s["rope_theta"]),
        norm_eps=s["rms_norm_eps"], attn_impl=entry["attn_impl"],
        # window layers are served by the paged decode kernel alone
        decode_impl="pallas",
        layer_types=tuple("window" if t == "sliding_attention" else "full"
                          for t in s["layer_types"]),
        window=s["sliding_window"], rope_layers="window", qk_norm=True,
        attn_gate=True, sandwich_norm=True,
        embed_scale=float(s["hidden_size"]) ** 0.5,
        ffn_types=tuple(s["ffn_types"]),
        moe=llama.MoEConfig(
            n_experts=s["router_experts"], top_k=s["num_experts_per_tok"],
            d_expert=s["moe_intermediate_size"], route_norm=s["route_norm"],
            route_scale=s["route_scale"], first=s["expert_first"],
            count=s["num_experts"]))
    handed = Handed()
    model = LlamaGenerateModel(
        cfg=cfg, max_seq=entry["max_seq"], max_slots=entry["max_slots"],
        page_size=entry["page_size"], kv_pages=entry.get("kv_pages"),
        kv_window_pages=entry.get("kv_window_pages"), params=handed)
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
    biases = reference_afmoe.router_biases(seed, sizes)
    model.bench_weights.tree = jax.block_until_ready(
        weights_afmoe.weights(seed, sizes, biases))
    model.warmup()


def reference_logits(seed, sizes, tokens, first, count, precision="f32"):
    """The family's plain reference (``reference_afmoe.decoder_logits``)."""
    return reference_afmoe.decoder_logits(seed, sizes, tokens, first, count,
                                          precision)


def routed(ctx, entry, s, steps):
    """``(pairs, experts read)`` of ``steps`` decode steps: the program's
    counters over the window (held pairs and DISTINCT held experts hit a
    routed layer-step), scaled to the traced steps.  None where the
    program has no such counters."""
    per = [counters.delta(ctx, name, model=entry["name"]) for name in (
        "tpu_moe_layer_steps_total", "tpu_moe_local_pairs_total",
        "tpu_moe_experts_hit_total")]
    if None in per or not per[0]:
        return None
    layer_steps = steps * roofline_afmoe.routed_layers(s)
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
        fn = (roofline_afmoe.flash_prefill_work if scope == "flash_prefill"
              else roofline_afmoe.prefill_work)
        parts = [fn(s, n) for n in lengths]
        return sum(p[0] for p in parts), sum(p[1] for p in parts)
    contexts = decode_contexts(ctx)
    if not contexts:
        return None
    if scope == "decode_attention":
        return roofline_afmoe.decode_attention_work(s, contexts)
    hit = routed(ctx, entry, s, len(runs))
    if hit is None:
        return None
    if scope == "moe_experts":
        return roofline_afmoe.experts_work(s, *hit)
    return roofline_afmoe.decode_step_work(s, contexts, len(runs), *hit)
