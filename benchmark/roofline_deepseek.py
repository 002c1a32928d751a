"""The work a decode step and a prefill of the DeepSeek-V3 family need
(latent attention, group-limited routed experts), for the shares of
roofline and peak: the arithmetic beside ``roofline.py``'s, for one
chip's share of the deployment (the held experts, the held rows of the
vocabulary).

What is counted is what the ALGORITHM needs, whatever implements it.
Decode attends in the latent space: per cached token and layer one
latent row of ``kv_lora + rope`` values read ONCE (padding lanes of the
pool are no work), ``2 x heads x (kv_lora + rope)`` FLOPs of scores and
``2 x heads x kv_lora`` of the weighted sum; carrying the query into
the latent space and the result out of it is 2 FLOPs a parameter of the
two up-projections, which ``shared_params`` holds.  A prefill attends
expanded: causal scores over ``nope + rope`` and sums over ``v`` a head
pair, q, k, v read and o written once; expanding the latent is again 2
FLOPs a parameter of the up-projections.  The routed experts count as
``roofline_afmoe``'s do: each DISTINCT held expert that some row chose
once a layer-step, a prefill's pairs as evenly spread choices give.
"""

from roofline_afmoe import expert_params, experts_work, prefill_routed


def attention_params(s):
    d, h = s["hidden_size"], s["num_attention_heads"]
    ql, kvl = s["q_lora_rank"], s["kv_lora_rank"]
    nope, rope, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    # q_a, q_b, kv_a, kv_b (keys' and values' parts), o
    return (d * ql + ql * h * (nope + rope) + d * (kvl + rope)
            + h * kvl * (nope + dv) + h * dv * d)


def shared_params(s):
    """Matmul parameters every row passes through in a step: attention
    of every layer, the dense layers' SwiGLU, the routed layers' shared
    experts and router, the head (the embedding is a lookup)."""
    d = s["hidden_size"]
    total = s["num_hidden_layers"] * attention_params(s) + d * s["vocab_size"]
    for kind in s["ffn_types"]:
        if kind == "dense":
            total += 3 * d * s["intermediate_size"]
        else:
            total += (s["n_shared_experts"] * expert_params(s)
                      + d * s["router_experts"])
    return total


def latent_width(s):
    """Values a cached token holds in one layer: ``[c_kv ; k_pe]``."""
    return s["kv_lora_rank"] + s["qk_rope_head_dim"]


def latent_token_bytes(s, dtype_bytes=2):
    return latent_width(s) * dtype_bytes


def decode_attention_work(s, contexts, dtype_bytes=2):
    """(FLOPs, bytes) of the latent decode attention of the row-steps
    whose contexts are ``contexts``, all layers: every head's scores
    over the whole latent row and its sum over the ``kv_lora`` lanes;
    the bytes it MUST read are the rows, once."""
    keys = s["num_hidden_layers"] * sum(contexts)
    flops = (2 * keys * s["num_attention_heads"]
             * (latent_width(s) + s["kv_lora_rank"]))
    return flops, latent_token_bytes(s, dtype_bytes) * keys


def decode_step_work(s, contexts, steps, pairs, experts_read, dtype_bytes=2):
    """(FLOPs, bytes) of ``steps`` decode steps that served the row-steps
    ``contexts`` with ``pairs`` held pairs over ``experts_read`` distinct
    expert reads: the shared weights once a step, the experts as
    ``experts_work``, attention as ``decode_attention_work``, one latent
    row written a row a layer."""
    rows = len(contexts)
    a_flops, a_bytes = decode_attention_work(s, contexts, dtype_bytes)
    e_flops, e_bytes = experts_work(s, pairs, experts_read, dtype_bytes)
    flops = 2 * shared_params(s) * rows + a_flops + e_flops
    nbytes = (shared_params(s) * dtype_bytes * steps + a_bytes + e_bytes
              + latent_token_bytes(s, dtype_bytes) * s["num_hidden_layers"]
              * rows)
    return flops, nbytes


def flash_prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of the expanded prefill attention of ``tokens``,
    all layers: causal scores over ``nope + rope`` and sums over ``v`` a
    head; Q, K, V read and O written once."""
    h, dv = s["num_attention_heads"], s["v_head_dim"]
    dqk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    layers = s["num_hidden_layers"]
    flops = layers * tokens * (tokens + 1) // 2 * h * 2 * (dqk + dv)
    nbytes = layers * tokens * h * (2 * dqk + 2 * dv) * dtype_bytes
    return flops, nbytes


def prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of one prefill of ``tokens``: the shared weights
    over every token but the head, which the last token alone passes;
    attention as ``flash_prefill_work``; the experts as ``experts_work``
    on ``prefill_routed``; weights read once, a latent row written a
    token a layer."""
    head = s["hidden_size"] * s["vocab_size"]
    a_flops, _ = flash_prefill_work(s, tokens, dtype_bytes)
    routed = prefill_routed(dict(s, num_experts=s["n_routed_experts"]), tokens)
    e_flops, e_bytes = experts_work(s, *routed, dtype_bytes)
    flops = (2 * (shared_params(s) - head) * tokens + 2 * head + a_flops
             + e_flops)
    nbytes = (shared_params(s) * dtype_bytes + e_bytes
              + latent_token_bytes(s, dtype_bytes) * s["num_hidden_layers"]
              * tokens)
    return flops, nbytes
