"""Builder of the program's generation model for a repository entry
``{"builder": "llama_generate", "sizes": <group>, "max_seq", "max_slots",
"page_size", "attn_impl"}``: ``tpuserver``'s ``LlamaGenerateModel`` on the
continuous-batching scheduler, given the benchmark's weights.

Mistral runs through ``models/llama.py`` as data (a ``LlamaConfig`` with
Mistral's published sizes).  The program builds its weights itself from
``PRNGKey(0)`` and has no way to be handed a tree, so ``load`` swaps
``llama.init_params`` for the duration of the model's own load (PERF.md,
Open questions).

The builder also brings what the yardstick needs to know of this model
family and of nothing else: how its executables are told apart in a
device trace (``TRACE_LABELS``, ``SCOPES``), the work the algorithm needs
for what they served (``work``), and its plain reference
(``reference_logits``).  ``devicework.py`` and the kinds find them by the
repository entry's ``builder``; a new family brings its own.
"""

import reference
import roofline
import weights

# The program's decode step and prefills are nameless executables; the
# Pallas kernel inside says which is which (ops/flash.py's kernel names).
TRACE_LABELS = (("decode_step", "op", "decode_attention"),
                ("prefill", "op", "flash_attention"))

# scope of a per-layer metric -> the labelled executable, and the one
# kernel inside it where the scope is a kernel
SCOPES = {
    "decode_step": {"label": "decode_step"},
    "prefill": {"label": "prefill"},
    "decode_attention": {"label": "decode_step", "op": "decode_attention"},
    "flash_prefill": {"label": "prefill", "op": "flash_attention"},
}

SIZE_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_hidden_layers",
             "vocab_size", "rope_theta", "rms_norm_eps")


def sizes_of(config, entry):
    group = config if entry["sizes"] == "top-level" else config[entry["sizes"]]
    sizes = {k: group[k] for k in SIZE_KEYS}
    if sizes["hidden_size"] != sizes["num_attention_heads"] * sizes["head_dim"]:
        raise ValueError("models/llama.py derives head_dim = hidden_size / "
                         "heads; {} states another".format(entry["name"]))
    return sizes


def build(config, entry):
    from tpuserver.models import llama
    from tpuserver.models.llama_serving import LlamaGenerateModel

    s = sizes_of(config, entry)
    cfg = llama.LlamaConfig(
        vocab=s["vocab_size"], d_model=s["hidden_size"],
        n_layers=s["num_hidden_layers"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], d_ff=s["intermediate_size"],
        rope_theta=s["rope_theta"], norm_eps=s["rms_norm_eps"],
        attn_impl=entry["attn_impl"])
    return LlamaGenerateModel(
        cfg=cfg, max_seq=entry["max_seq"], max_slots=entry["max_slots"],
        page_size=entry["page_size"])


def load(model, config, entry, seed):
    """Weights from the seed in one jitted call, then the model's own
    load (scheduler, page pool).  Compiles nothing of the model."""
    import jax
    from tpuserver.models import llama

    params = jax.block_until_ready(
        weights.decoder_weights(seed, sizes_of(config, entry)))
    program_init = llama.init_params
    llama.init_params = lambda key, cfg: params
    try:
        model.warmup()
    finally:
        llama.init_params = program_init


def reference_logits(seed, sizes, tokens, first, count, precision="f32"):
    """The family's plain reference (``reference.decoder_logits``)."""
    return reference.decoder_logits(seed, sizes, tokens, first, count,
                                    precision)


def prompt_tokens(runs):
    """The prompt length of each prefill run: the flash kernel's result is
    [heads, tokens, head_dim]."""
    dims = [r.op_dims("flash_attention") for r in runs]
    return [d[1] for d in dims if len(d) == 3]


def decode_contexts(ctx):
    """The context of every row-step whose token arrived in the traced
    interval: token k of a request attends over prompt + k + 1."""
    lo, hi = ctx.trace_data.interval()
    return [r.prompt_tokens + k + 1
            for r in ctx.kind.records(ctx)
            for k, t in enumerate(r.token_times) if lo <= t < hi]


def work(ctx, entry, scope, runs):
    """``(flops, bytes)`` the algorithm needs for what ``runs`` of the
    scope's executable served in the traced interval, or None where that
    cannot be told."""
    s = sizes_of(ctx.config, entry)
    if scope in ("decode_step", "decode_attention"):
        contexts = decode_contexts(ctx)
        if not contexts:
            return None
        if scope == "decode_attention":
            return roofline.decode_attention_work(s, contexts)
        flops, nbytes = roofline.decode_step_work(s, contexts)
        # the weights are read once per STEP, not once per window
        weights_bytes = roofline.matmul_params(s) * 2
        return flops, nbytes + weights_bytes * (len(runs) - 1)
    lengths = prompt_tokens(runs)
    if len(lengths) != len(runs):
        return None
    fn = (roofline.flash_prefill_work if scope == "flash_prefill"
          else roofline.prefill_work)
    parts = [fn(s, n) for n in lengths]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)
