"""The work a decode step and a prefill of the LFM2-MoE family need
(short-convolution mixers beside attention, routed experts), for the
shares of roofline and peak: the arithmetic beside ``roofline.py``'s.

What is counted is what the ALGORITHM needs, whatever implements it:
the weights every row passes through once a step, each DISTINCT expert
that some row chose once a layer-step (never all that are held), each
row's keys and values at its true length in every attention layer (K
and V of 8 heads of 64 at 2 B: 2,048 B a token a layer), and in every
conv layer the row's window of ``L - 1`` rows of ``u`` read and
written.  A prefill's routing is
not counted by the program, so its pairs and expert reads are what
evenly spread choices give (``prefill_routed``).
"""


def attention_params(s):
    """One attention mixer: wq, wk, wv, wo and the per-head q / k norm
    gains."""
    d, hd = s["hidden_size"], s["head_dim"]
    return (2 * d * s["num_attention_heads"] * hd
            + 2 * d * s["num_key_value_heads"] * hd + 2 * hd)


def conv_params(s):
    """One short-convolution mixer: in_proj [D, 3D], the L taps of D
    channels, out_proj [D, D] (no bias)."""
    d = s["hidden_size"]
    return 3 * d * d + s["conv_L_cache"] * d + d * d


def expert_params(s):
    """One SwiGLU expert: gate, up, down."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def conv_layers(s):
    return sum(1 for t in s["layer_types"] if t == "conv")


def attn_layers(s):
    return len(s["layer_types"]) - conv_layers(s)


def routed_layers(s):
    return sum(1 for kind in s["ffn_types"] if kind == "moe")


def shared_params(s):
    """Matmul parameters every row passes through in a step: the mixer
    of every layer, the dense layers' SwiGLU, the routers, the head (the
    embedding's transpose; the lookup is no matmul)."""
    d = s["hidden_size"]
    total = (conv_layers(s) * conv_params(s) + attn_layers(s)
             * attention_params(s) + d * s["vocab_size"])
    for kind in s["ffn_types"]:
        total += (3 * d * s["intermediate_size"] if kind == "dense"
                  else d * s["num_experts"])
    return total


def kv_token_bytes(s, dtype_bytes=2):
    """K and V of one token in ONE attention layer."""
    return 2 * s["num_key_value_heads"] * s["head_dim"] * dtype_bytes


def window_bytes(s, dtype_bytes=2):
    """One row's windows over all conv layers: ``L - 1`` rows of D."""
    return (conv_layers(s) * (s["conv_L_cache"] - 1) * s["hidden_size"]
            * dtype_bytes)


def window_traffic(s, rows, dtype_bytes=2):
    """Bytes of ``rows`` row-steps' windows, read and written."""
    return 2 * window_bytes(s, dtype_bytes) * rows


def decode_attention_work(s, contexts, dtype_bytes=2):
    """(FLOPs, bytes) of the decode attention of the row-steps whose
    contexts are ``contexts``, every attention layer: QK^T and PV over
    the whole context; the bytes it MUST read are their K and V."""
    keys = attn_layers(s) * sum(contexts)
    flops = 4 * keys * s["num_attention_heads"] * s["head_dim"]
    return flops, kv_token_bytes(s, dtype_bytes) * keys


def experts_work(s, pairs, experts_read, dtype_bytes=2):
    """(FLOPs, bytes) of the routed experts' matmuls: 2 FLOPs a
    parameter a (token, expert) pair; each distinct expert read once a
    layer-step, the pairs' activations in and out."""
    flops = 2 * expert_params(s) * pairs
    nbytes = (expert_params(s) * experts_read
              + 2 * pairs * s["hidden_size"]) * dtype_bytes
    return flops, nbytes


def decode_step_work(s, contexts, steps, pairs, experts_read, dtype_bytes=2):
    """(FLOPs, bytes) of ``steps`` decode steps that served the row-steps
    ``contexts`` with ``pairs`` pairs over ``experts_read`` distinct
    expert reads: the shared weights once a step (the conv mixers'
    among them), the experts as ``experts_work``, attention as
    ``decode_attention_work``, the convolutions' 7 FLOPs a channel and
    their windows read and written, one K/V row written a row an
    attention layer."""
    rows = len(contexts)
    d, taps = s["hidden_size"], s["conv_L_cache"]
    a_flops, a_bytes = decode_attention_work(s, contexts, dtype_bytes)
    e_flops, e_bytes = experts_work(s, pairs, experts_read, dtype_bytes)
    flops = (2 * shared_params(s) * rows + a_flops + e_flops
             + conv_layers(s) * rows * d * (2 * taps + 1))
    nbytes = (shared_params(s) * dtype_bytes * steps + a_bytes + e_bytes
              + window_traffic(s, rows, dtype_bytes)
              + kv_token_bytes(s, dtype_bytes) * attn_layers(s) * rows)
    return flops, nbytes


# -- prefill ------------------------------------------------------------------


def flash_prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of the causal prefill attention of ``tokens``,
    every attention layer: QK^T and PV over the causal half; Q, K, V
    read and O written once."""
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    flops = attn_layers(s) * 4 * (tokens * (tokens + 1) // 2) * h * hd
    nbytes = attn_layers(s) * tokens * hd * dtype_bytes * (2 * h + 2 * kv)
    return flops, nbytes


def prefill_routed(s, tokens):
    """``(pairs, experts read)`` of one prefill over its routed layers,
    for choices spread evenly over the experts (all held here)."""
    held, layers = s["num_experts"], routed_layers(s)
    pairs = tokens * s["num_experts_per_tok"]
    return layers * pairs, layers * held * (1 - (1 - 1 / held) ** pairs)


def prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of one prefill of ``tokens``: the shared weights
    over every token but the head, which the last token alone passes;
    attention as ``flash_prefill_work``; the conv mixers' middle a token
    a layer; the experts as ``experts_work`` on ``prefill_routed``;
    weights read once, K and V written once, the windows written once."""
    head = s["hidden_size"] * s["vocab_size"]
    a_flops, _ = flash_prefill_work(s, tokens, dtype_bytes)
    e_flops, e_bytes = experts_work(s, *prefill_routed(s, tokens), dtype_bytes)
    c_flops = conv_layers(s) * tokens * s["hidden_size"] * (
        2 * s["conv_L_cache"] + 1)
    flops = (2 * (shared_params(s) - head) * tokens + 2 * head + a_flops
             + e_flops + c_flops)
    nbytes = (shared_params(s) * dtype_bytes + e_bytes + window_bytes(
        s, dtype_bytes) + kv_token_bytes(s, dtype_bytes) * attn_layers(s)
        * tokens)
    return flops, nbytes
