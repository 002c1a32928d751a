"""The work a routed, windowed decode step and prefill need (AFMoE
family), for the shares of roofline and peak: the arithmetic beside
``roofline.py``'s,
for one chip's share of the deployment (the held experts, the held rows
of the vocabulary).

What is counted is what the ALGORITHM needs, whatever implements the
expert layer: the weights every row passes through once a step, each
DISTINCT held expert that some row chose once a layer-step (never all
that are held), each row's keys and values at its true length in a full
layer and inside the window in a window layer.  A prefill's routing is
not counted by the program, so its pairs and expert reads are what
evenly spread choices give (``prefill_routed``): at 1,024 tokens and
more that is every held expert.
"""


def _attention_params(s):
    hd, d = s["head_dim"], s["hidden_size"]
    # wq, wg (output gate), wo; wk, wv
    return (3 * d * s["num_attention_heads"] * hd
            + 2 * d * s["num_key_value_heads"] * hd)


def expert_params(s):
    """One SwiGLU expert: gate, up, down."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def shared_params(s):
    """Matmul parameters every row passes through in a step: attention
    of every layer, the dense layers' SwiGLU, the routed layers' shared
    expert and router, the head (the embedding is a lookup)."""
    d = s["hidden_size"]
    total = s["num_hidden_layers"] * _attention_params(s) + d * s["vocab_size"]
    for kind in s["ffn_types"]:
        if kind == "dense":
            total += 3 * d * s["intermediate_size"]
        else:
            total += expert_params(s) + d * s["router_experts"]
    return total


def routed_layers(s):
    return sum(1 for kind in s["ffn_types"] if kind == "moe")


def kv_token_bytes(s, dtype_bytes=2):
    """K and V of one token in ONE layer."""
    return 2 * s["num_key_value_heads"] * s["head_dim"] * dtype_bytes


def attended(s, contexts):
    """Key positions the step's attention reads, summed over layers: a
    row's whole context in a full layer, its window in a window layer."""
    w = s["sliding_window"]
    full = sum(1 for t in s["layer_types"] if t != "sliding_attention")
    windowed = len(s["layer_types"]) - full
    return sum(full * c + windowed * min(c, w) for c in contexts)


def decode_attention_work(s, contexts, dtype_bytes=2):
    """(FLOPs, bytes) of the decode attention of the row-steps whose
    contexts are ``contexts``, all layers: QK^T and PV over the positions
    attended; the bytes it MUST read are their K and V."""
    keys = attended(s, contexts)
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
    ``contexts`` with ``pairs`` held pairs over ``experts_read`` distinct
    expert reads: the shared weights once a step, the experts as
    ``experts_work``, attention as ``decode_attention_work``, one K/V row
    written a row a layer."""
    rows = len(contexts)
    a_flops, a_bytes = decode_attention_work(s, contexts, dtype_bytes)
    e_flops, e_bytes = experts_work(s, pairs, experts_read, dtype_bytes)
    flops = 2 * shared_params(s) * rows + a_flops + e_flops
    nbytes = (shared_params(s) * dtype_bytes * steps + a_bytes + e_bytes
              + kv_token_bytes(s, dtype_bytes) * s["num_hidden_layers"] * rows)
    return flops, nbytes


# -- prefill ------------------------------------------------------------------


def prefill_keys(s, tokens):
    """Key positions a causal prefill of ``tokens`` attends, summed over
    queries and layers: query i sees i + 1 keys in a full layer and
    min(i + 1, window) in a window layer."""
    w = min(s["sliding_window"], tokens)
    full = tokens * (tokens + 1) // 2
    inside = w * (w + 1) // 2 + (tokens - w) * w
    windowed = sum(1 for t in s["layer_types"] if t == "sliding_attention")
    return (len(s["layer_types"]) - windowed) * full + windowed * inside


def flash_prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of the prefill attention of ``tokens``, all layers:
    QK^T and PV over the keys each query sees (blocks behind the window
    are no work); Q, K, V read and O written once."""
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    flops = 4 * prefill_keys(s, tokens) * h * hd
    nbytes = (s["num_hidden_layers"] * tokens * hd * dtype_bytes
              * (2 * h + 2 * kv))
    return flops, nbytes


def prefill_routed(s, tokens):
    """``(pairs, experts read)`` of one prefill over its routed layers,
    for choices spread evenly over the router's experts: the held share
    of the pairs, and the distinct held experts they then fall on."""
    held, layers = s["num_experts"], routed_layers(s)
    pairs = tokens * s["num_experts_per_tok"] * held / s["router_experts"]
    return layers * pairs, layers * held * (1 - (1 - 1 / held) ** pairs)


def prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of one prefill of ``tokens``: the shared weights
    over every token but the head, which the last token alone passes;
    attention as ``flash_prefill_work``; the experts as ``experts_work``
    on ``prefill_routed``; weights read once, K and V written once."""
    head = s["hidden_size"] * s["vocab_size"]
    a_flops, _ = flash_prefill_work(s, tokens, dtype_bytes)
    e_flops, e_bytes = experts_work(s, *prefill_routed(s, tokens), dtype_bytes)
    flops = (2 * (shared_params(s) - head) * tokens + 2 * head + a_flops
             + e_flops)
    nbytes = (shared_params(s) * dtype_bytes + e_bytes
              + kv_token_bytes(s, dtype_bytes) * s["num_hidden_layers"] * tokens)
    return flops, nbytes
