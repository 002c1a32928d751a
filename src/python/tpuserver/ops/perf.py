"""Roofline accounting for the llama serving path: analytic FLOP/byte
counts per config plus a chip-spec table, so benchmarks can report MFU
(achieved FLOP/s over the chip's peak) and MBU (achieved HBM bytes/s
over peak bandwidth) instead of bare tokens/sec.

No reference counterpart — the reference is a client-side load
generator; this is the TPU-native framework's own proof-of-performance
layer.  Peak numbers are the published per-chip specs (bf16 matmul peak
and HBM bandwidth); MFU follows the standard convention of counting
only algorithmic matmul/attention FLOPs (2*m*n*k per matmul), no
rematerialization credit.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float  # FLOP/s
    hbm_bandwidth: float    # bytes/s
    hbm_bytes: int


# published single-chip specs, keyed by jax Device.device_kind
CHIP_SPECS = {
    "TPU v4": ChipSpec("v4", 275e12, 1228e9, 32 << 30),
    "TPU v5 lite": ChipSpec("v5e", 197e12, 819e9, 16 << 30),
    "TPU v5e": ChipSpec("v5e", 197e12, 819e9, 16 << 30),
    "TPU v5": ChipSpec("v5p", 459e12, 2765e9, 95 << 30),
    "TPU v5p": ChipSpec("v5p", 459e12, 2765e9, 95 << 30),
    "TPU v6 lite": ChipSpec("v6e", 918e12, 1640e9, 32 << 30),
    "TPU v6e": ChipSpec("v6e", 918e12, 1640e9, 32 << 30),
}


def chip_spec(device=None):
    """Spec for ``device`` (default: jax's first device).  ``None`` on
    the CPU (test meshes have no peaks to report against); an
    accelerator whose ``device_kind`` is not in ``CHIP_SPECS`` raises —
    a row computed against a guessed peak is worse than no row."""
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return CHIP_SPECS[device.device_kind]
    except KeyError:
        raise ValueError(
            "no peak FLOP/s / bandwidth entry for {} device kind {!r}; "
            "add it to tpuserver.ops.perf.CHIP_SPECS with its source "
            "(known: {})".format(
                device.platform, device.device_kind,
                ", ".join(sorted(CHIP_SPECS)))
        ) from None


def param_count(cfg):
    """Analytic parameter count of ``llama.init_params`` for ``cfg``."""
    hd = cfg.head_dim
    per_layer = (
        cfg.d_model * cfg.n_heads * hd          # wq
        + 2 * cfg.d_model * cfg.n_kv_heads * hd  # wk, wv
        + cfg.n_heads * hd * cfg.d_model        # wo
        + 3 * cfg.d_model * cfg.d_ff            # gate, up, down
        + 2 * cfg.d_model                       # norms
    )
    return (
        2 * cfg.vocab * cfg.d_model             # embed + lm_head
        + cfg.n_layers * per_layer
        + cfg.d_model                           # final norm
    )


def matmul_params(cfg):
    """Params that participate in per-token matmuls (excludes the embed
    gather, which costs a lookup, not FLOPs; includes lm_head)."""
    return param_count(cfg) - cfg.vocab * cfg.d_model


def decode_flops_per_token(cfg, ctx_len):
    """Forward FLOPs to decode ONE token at context length ``ctx_len``.

    2 FLOPs per matmul parameter, plus attention: per layer the single
    query attends over ctx_len cached K/V rows — QK^T and PV are each
    2 * ctx_len * n_heads * head_dim FLOPs.
    """
    attn = cfg.n_layers * 4 * ctx_len * cfg.n_heads * cfg.head_dim
    return 2 * matmul_params(cfg) + attn


def prefill_flops(cfg, seq_len):
    """Forward FLOPs for a causal prefill of ``seq_len`` tokens.

    Matmuls are linear in tokens; causal attention sums to
    ~seq_len^2/2 score rows per head per layer (QK^T + PV).
    """
    matmul = 2 * matmul_params(cfg) * seq_len
    attn = cfg.n_layers * 4 * (seq_len * seq_len // 2) * (
        cfg.n_heads * cfg.head_dim
    )
    return matmul + attn


def decode_bytes_per_token(cfg, ctx_len, dtype_bytes=2,
                           weight_bytes_per_param=None):
    """HBM bytes touched to decode one token: every matmul weight is
    read once, the valid KV prefix is read, and one KV row is written.
    (The decode roofline — at batch 1 this is bandwidth-bound, so
    tokens/sec * bytes/token vs peak bandwidth is the honest
    utilization number.)  ``weight_bytes_per_param`` overrides the
    weight-read cost (1 for int8-quantized serving; KV stays
    ``dtype_bytes``)."""
    wb = (
        weight_bytes_per_param
        if weight_bytes_per_param is not None
        else dtype_bytes
    )
    weights = matmul_params(cfg) * wb
    kv_row = 2 * cfg.n_kv_heads * cfg.head_dim * dtype_bytes
    kv = cfg.n_layers * kv_row * (ctx_len + 1)
    return weights + kv


def bert_encoder_flops(seq_len=128, d_model=768, n_layers=12, d_ff=3072):
    """Forward FLOPs of one BERT-base-shaped encoder pass (the config-4
    ensemble's device stage): per layer 4 attention projections + the
    2 MLP matmuls (2*m*n*k each) + QK^T/PV attention, plus the pooler."""
    per_layer = (
        2 * seq_len * (4 * d_model * d_model + 2 * d_model * d_ff)
        + 4 * seq_len * seq_len * d_model
    )
    return n_layers * per_layer + 2 * d_model * d_model


def mfu(flops, seconds, spec):
    """Achieved-over-peak FLOP ratio (None without a known chip)."""
    if spec is None or seconds <= 0:
        return None
    return flops / seconds / spec.peak_bf16_flops


def mbu(nbytes, seconds, spec):
    """Achieved-over-peak HBM bandwidth ratio."""
    if spec is None or seconds <= 0:
        return None
    return nbytes / seconds / spec.hbm_bandwidth
