"""The work a block-diffusion step and its prefill need (SDAR family),
for the shares of roofline and peak: the arithmetic beside
``roofline.py``'s and ``roofline_afmoe.py``'s.

What is counted is what the ALGORITHM needs, whatever implements it.  A
row PASS (denoise or commit) carries the B positions of one block: every
position passes the shared weights (attention projections, router,
head), each (position, expert) pair one expert, and the B queries attend
the ``start + B`` keys of all earlier blocks and their own, whose K and V
are read ONCE a pass (not once a query) and written for the block's B
positions.  The shared weights stream once a STEP, each DISTINCT expert
some position chose once a layer-step.  A prefill runs the prompt's
whole blocks under the block-causal mask and reads nothing of them: no
head, and of the last layer only what its K and V need (norm, Wk, Wv);
its routing is not counted by the program, so its pairs and expert reads
are what evenly spread choices give.
"""


def attention_params(s):
    hd, d = s["head_dim"], s["hidden_size"]
    # wq, wo; wk, wv
    return (2 * d * s["num_attention_heads"] * hd
            + 2 * d * s["num_key_value_heads"] * hd)


def kv_params(s):
    return 2 * s["hidden_size"] * s["num_key_value_heads"] * s["head_dim"]


def expert_params(s):
    """One SwiGLU expert: gate, up, down."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def layer_shared_params(s):
    """A layer's matmul parameters every position passes: attention and
    the router."""
    return attention_params(s) + s["hidden_size"] * s["num_experts"]


def shared_params(s):
    """Every layer's shared parameters and the head (the embedding is a
    lookup)."""
    return (s["num_hidden_layers"] * layer_shared_params(s)
            + s["hidden_size"] * s["vocab_size"])


def kv_token_bytes(s, dtype_bytes=2):
    """K and V of one token in ONE layer."""
    return 2 * s["num_key_value_heads"] * s["head_dim"] * dtype_bytes


def decode_attention_work(s, contexts, dtype_bytes=2):
    """(FLOPs, bytes) of the block attention of the row passes whose keys
    attended are ``contexts`` (``start + B`` each), all layers: QK^T and
    PV of B queries over those keys; the bytes it MUST read are the keys'
    K and V, once a pass."""
    keys = sum(contexts) * s["num_hidden_layers"]
    flops = 4 * keys * s["block_length"] * s["num_attention_heads"] * s["head_dim"]
    return flops, kv_token_bytes(s, dtype_bytes) * keys


def experts_work(s, pairs, experts_read, dtype_bytes=2):
    """(FLOPs, bytes) of the routed experts' matmuls: 2 FLOPs a
    parameter a (position, expert) pair; each distinct expert read once a
    layer-step, the pairs' activations in and out."""
    flops = 2 * expert_params(s) * pairs
    nbytes = (expert_params(s) * experts_read
              + 2 * pairs * s["hidden_size"]) * dtype_bytes
    return flops, nbytes


def decode_step_work(s, contexts, steps, pairs, experts_read, dtype_bytes=2):
    """(FLOPs, bytes) of ``steps`` block steps that served the row passes
    ``contexts`` with ``pairs`` pairs over ``experts_read`` distinct
    expert reads."""
    positions = len(contexts) * s["block_length"]
    a_flops, a_bytes = decode_attention_work(s, contexts, dtype_bytes)
    e_flops, e_bytes = experts_work(s, pairs, experts_read, dtype_bytes)
    flops = 2 * shared_params(s) * positions + a_flops + e_flops
    nbytes = (shared_params(s) * dtype_bytes * steps + a_bytes + e_bytes
              + kv_token_bytes(s, dtype_bytes) * s["num_hidden_layers"]
              * positions)
    return flops, nbytes


# -- prefill ------------------------------------------------------------------


def prefill_keys(s, tokens):
    """Key positions a block-causal prefill of ``tokens`` attends in ONE
    layer, summed over queries: a query sees its own and every earlier
    block whole."""
    b = s["block_length"]
    blocks = tokens // b
    return b * b * blocks * (blocks + 1) // 2


def _attended_layers(s):
    # the last layer's attention output feeds nothing that is kept
    return s["num_hidden_layers"] - 1


def flash_prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of the prefill attention of ``tokens``: QK^T and PV
    over the keys each query sees, in every layer whose output is read;
    Q, K, V read and O written once."""
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    layers = _attended_layers(s)
    flops = 4 * prefill_keys(s, tokens) * h * hd * layers
    return flops, layers * tokens * hd * dtype_bytes * (2 * h + 2 * kv)


def prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of one prefill of ``tokens``: every layer but the
    last whole (experts with evenly spread choices: every expert read),
    of the last its K and V projections; weights read once, K and V
    written once a layer."""
    layers = _attended_layers(s)
    pairs = layers * tokens * s["num_experts_per_tok"]
    a_flops, _ = flash_prefill_work(s, tokens, dtype_bytes)
    e_flops, e_bytes = experts_work(
        s, pairs, layers * s["num_experts"], dtype_bytes)
    flops = (2 * (layers * layer_shared_params(s) + kv_params(s)) * tokens
             + a_flops + e_flops)
    nbytes = ((layers * layer_shared_params(s) + kv_params(s)) * dtype_bytes
              + e_bytes + kv_token_bytes(s, dtype_bytes)
              * s["num_hidden_layers"] * tokens)
    return flops, nbytes
