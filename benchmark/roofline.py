"""Peaks of the chips, and the work an algorithm needs: the yardstick's
arithmetic (copied from ``tpuserver/ops/perf.py``, which lacks the int8
peak; the original is listed in PERF.md for a later PR to delete).

The work counted is what the ALGORITHM needs, whatever implements it:
weights read once per step, each row's KV at its true length, causal
attention.  A later kernel change then does not make the count stale.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class Chip:
    name: str
    bf16_flops: float   # FLOP/s
    int8_ops: float     # OP/s
    hbm_bytes_s: float  # bytes/s
    hbm_bytes: int
    source: str


_V5E = Chip("v5e", 197e12, 393e12, 819e9, 16 << 30,
            "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip")

# keyed by jax Device.device_kind; a kind that is not here is an error
CHIPS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def chip(device_kind):
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise ValueError(
            "no published peaks for device kind {!r} in benchmark/roofline.py "
            "(known: {})".format(device_kind, ", ".join(sorted(CHIPS)))
        ) from None


# -- decoder ------------------------------------------------------------------


def matmul_params(s):
    """Parameters that take part in per-token matmuls: every layer's
    seven matrices and the head; the embedding is a lookup."""
    hd = s["head_dim"]
    layer = (s["hidden_size"] * s["num_attention_heads"] * hd
             + 2 * s["hidden_size"] * s["num_key_value_heads"] * hd
             + s["num_attention_heads"] * hd * s["hidden_size"]
             + 3 * s["hidden_size"] * s["intermediate_size"])
    return s["num_hidden_layers"] * layer + s["hidden_size"] * s["vocab_size"]


def kv_row_bytes(s, dtype_bytes=2):
    """K and V of one token, all layers."""
    return (s["num_hidden_layers"] * 2 * s["num_key_value_heads"]
            * s["head_dim"] * dtype_bytes)


def decode_step_work(s, contexts, dtype_bytes=2):
    """(FLOPs, bytes) of ONE decode step over rows whose contexts are
    ``contexts`` tokens long: 2 FLOPs per matmul parameter per row plus
    QK^T and PV over each row's true context; weights read once, each
    row's KV read at its true length, one KV row written per row."""
    rows, ctx = len(contexts), sum(contexts)
    attn = (s["num_hidden_layers"] * 4 * ctx * s["num_attention_heads"]
            * s["head_dim"])
    flops = 2 * matmul_params(s) * rows + attn
    nbytes = (matmul_params(s) * dtype_bytes
              + kv_row_bytes(s, dtype_bytes) * (ctx + rows))
    return flops, nbytes


def prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of one causal prefill of ``tokens``: matmuls linear
    in tokens, attention over the causal half; weights read once, the
    KV written once."""
    attn = (s["num_hidden_layers"] * 4 * (tokens * (tokens + 1) // 2)
            * s["num_attention_heads"] * s["head_dim"])
    flops = 2 * matmul_params(s) * tokens + attn
    nbytes = matmul_params(s) * dtype_bytes + kv_row_bytes(s, dtype_bytes) * tokens
    return flops, nbytes


def decode_attention_work(s, contexts, dtype_bytes=2):
    """(FLOPs, bytes) of the decode attention of one step, all layers:
    the bytes it MUST read are each row's K and V at its true length."""
    ctx = sum(contexts)
    flops = (s["num_hidden_layers"] * 4 * ctx * s["num_attention_heads"]
             * s["head_dim"])
    return flops, kv_row_bytes(s, dtype_bytes) * ctx


def flash_prefill_work(s, tokens, dtype_bytes=2):
    """(FLOPs, bytes) of causal attention over ``tokens``, all layers:
    QK^T and PV over the causal half; Q, K, V read and O written once."""
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    flops = s["num_hidden_layers"] * 4 * (tokens * (tokens + 1) // 2) * h * hd
    nbytes = s["num_hidden_layers"] * tokens * hd * dtype_bytes * (2 * h + 2 * kv)
    return flops, nbytes


def roofline_share(flops, nbytes, seconds, c):
    """(share in %, which bound) of the least time the chip could take."""
    t_flops, t_bytes = flops / c.bf16_flops, nbytes / c.hbm_bytes_s
    least = max(t_flops, t_bytes)
    return 100.0 * least / seconds, ("compute" if t_flops >= t_bytes
                                     else "memory")


def mfu(flops, seconds, c):
    return 100.0 * flops / seconds / c.bf16_flops
