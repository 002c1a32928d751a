"""Llama-family decoder-only transformer, TPU-first.

The flagship compute graph behind BASELINE config #5 ("Llama-3-8B decoupled
streaming").  This is NOT a torch port: parameters are a plain pytree of
``jnp.bfloat16`` arrays, the forward pass is pure einsum (MXU-shaped), all
control flow is static or ``lax``-level, and scale-out is expressed only as
``NamedSharding`` rules over a (dp, sp, tp) mesh — XLA inserts the
collectives.  Long context runs as a ``shard_map`` ring-attention program
over the ``sp`` axis (tpuserver/parallel/ring.py).

Pieces:
- ``LlamaConfig`` presets (``tiny`` test size → ``llama3_8b``)
- ``init_params`` / ``param_specs`` (Megatron column/row tp split)
- ``forward`` (teacher-forcing logits; dense or ring attention)
- ``train_step`` factory (cross-entropy + optax adamw) for the multi-chip
  dry-run
- ``init_kv_cache`` / ``decode_step`` / ``prefill`` for token-by-token
  serving (decoupled streaming)
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuserver.parallel.ring import ring_attention
from tpuserver.parallel.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: object = jnp.bfloat16
    # sequence-parallel attention: "ring" (ppermute K/V rotation — scales
    # to any head count) or "ulysses" (two all_to_alls, full-sequence
    # attention per head shard — needs local heads divisible by sp)
    sp_strategy: str = "ring"
    # single-shard prefill/forward attention: "xla" (compiler-fused
    # dense) or "pallas" (the hand-tiled flash kernel,
    # tpuserver.ops.flash_attention; needs T divisible by its block
    # sizes, falling back to dense otherwise).  Measured on v5e at
    # T=2048 on the 3B preset: flash (bf16 operands, 256x512 tiles)
    # prefills at 55% MFU vs 39% dense — see docs/benchmarking.md.
    # The real-size presets default to "pallas"; "xla" here keeps the
    # tiny test config on the portable dense path.
    attn_impl: str = "xla"
    # single-query decode attention: "auto" (default), "xla" or
    # "pallas" (tpuserver.ops.decode_attention).  The Pallas kernel
    # skips dead cache-tail blocks, winning up to ~10x when the valid
    # prefix is a small fraction of max_seq; XLA's fused dense wins for
    # short, mostly-full caches.  "auto" picks STATICALLY at trace time
    # from the measured cost model (docs/benchmarking.md): the kernel
    # when it wins for the majority of possible cache lengths, dense
    # otherwise.  (A per-step lax.cond was measured and rejected: XLA
    # cannot alias the KV cache through cond branches, and the copies
    # collapsed long-context decode 3x — see bench_prefill_sweep.)
    decode_impl: str = "auto"
    # flash-kernel tile sizes (prefill): preferred tiles, tuned on v5e
    # via tools/bench_kernels.py once the kernel stepped through
    # live block pairs alone: 512x1024 read 40% of the bf16 peak at
    # T=2048 (32 heads of 128) against 26% at 256x512, and 56-64% at
    # T=4096-8192 (48 heads of 128, 128 of 192/128) against 30-34%;
    # prompts not divisible by these fall back to smaller tiles, then
    # to the dense path (_flash_blocks)
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # -- the block as data.  The defaults are the Llama / Mistral block:
    # rotary positions on every layer, full causal attention, two norms
    # a layer, a dense SwiGLU.  Everything below is read at trace time.
    # explicit head size where it is not d_model / n_heads (0 = derived)
    d_head: int = 0
    # per-layer mixer kind, "full" or "window" attention, or "conv" (()
    # = all full); window layers see keys j with 0 <= i - j < window
    layer_types: tuple = ()
    window: int = 0
    # taps of a conv layer's short causal depthwise convolution
    # (``conv_L_cache``): a sequence carries the last ``conv_len - 1``
    # rows of the mixer's ``u`` a conv layer, a window of fixed size
    # beside the page pool (:func:`conv_mix`)
    conv_len: int = 3
    # which layers carry rotary positions: "all", or "window" (the full
    # layers then carry no positions at all)
    rope_layers: str = "all"
    # per-head RMSNorm of q and k (gains q_norm / k_norm over head_dim)
    qk_norm: bool = False
    # sigmoid output gate: wo((softmax(qk)v) * sigmoid(wg y))
    attn_gate: bool = False
    # sandwich norms: x + N2(Attn(N1 x)); x + N4(FFN(N3 x))
    sandwich_norm: bool = False
    # the embedding is multiplied by this (sqrt(d_model) under muP)
    embed_scale: float = 1.0
    # per-layer feed-forward kind, "dense" or "moe" (() = all dense)
    ffn_types: tuple = ()
    # the routed layers' geometry (MoEConfig), None without one
    moe: object = None
    # generation by diffusion over blocks of this many positions (0 =
    # one token at a time under the causal mask): query i sees key j iff
    # j // block_len <= i // block_len, a step carries a block a row
    # (paged_block_step), and ``mask_id`` is the token a position holds
    # until a pass unmasks it
    block_len: int = 0
    mask_id: int = 0
    # multi-head latent attention (MLAConfig) in place of the GQA
    # projections, None without: the cache then holds one latent row a
    # token a layer, and ``n_kv_heads`` / ``d_head`` are not read
    mla: object = None
    # the head is the embedding's transpose (no ``lm_head`` leaf)
    tie_embed: bool = False
    # lanes a K/V head takes in the cache and the pool (0 = head_dim):
    # the TPU lays an array's minor dimension out in tiles of 128 lanes,
    # and the paged decode kernel copies whole tiles, so 64-wide heads
    # are stored in 128 lanes, zeros behind them, and the padding is
    # stated so that the pool's bytes are what HBM holds
    kv_lanes: int = 0

    @property
    def head_dim(self):
        return self.d_head or self.d_model // self.n_heads

    @property
    def kv_width(self):
        """Lanes of one K/V head as the cache and the pool store it."""
        return self.kv_lanes or self.head_dim

    def layer_window(self, i):
        """Layer ``i``'s attention window in tokens, 0 for full."""
        if self.layer_types and self.layer_types[i] == "window":
            return self.window
        return 0

    def layer_rope(self, i):
        return self.rope_layers == "all" or bool(self.layer_window(i))

    def layer_moe(self, i):
        return bool(self.ffn_types) and self.ffn_types[i] == "moe"

    def layer_conv(self, i):
        return bool(self.layer_types) and self.layer_types[i] == "conv"

    @property
    def conv_layers(self):
        """Indices of the short-convolution layers (no K/V, no pages:
        their state is a window a sequence)."""
        return tuple(i for i in range(self.n_layers) if self.layer_conv(i))

    @property
    def attn_layers(self):
        """Indices of the layers that attend, in order: a contiguous
        cache's and a one-class pool's layer axis runs over these."""
        return tuple(i for i in range(self.n_layers)
                     if not self.layer_conv(i))

    @property
    def window_layers(self):
        """Indices of the layers that attend a window (their KV lives in
        the page pool's window class)."""
        return tuple(i for i in range(self.n_layers) if self.layer_window(i))

    @property
    def full_layers(self):
        return tuple(i for i in self.attn_layers if not self.layer_window(i))

    @property
    def plain(self):
        """True for the Llama / Mistral block: what the tensor-parallel,
        int8 and single-shard training paths were written for."""
        return not (self.layer_types or self.ffn_types or self.qk_norm
                    or self.attn_gate or self.sandwich_norm
                    or self.embed_scale != 1.0 or self.block_len
                    or self.mla is not None or self.tie_embed)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A routed feed-forward layer: a router over ``n_experts`` (scores
    ``score_func``: ``"sigmoid"`` of each logit, or ``"softmax"`` over
    all experts before the choice; ``router_bias``: a per-expert bias
    added for the choice alone) choosing ``top_k`` a token, SwiGLU
    experts of width ``d_expert``, and ``n_shared`` shared experts
    (one SwiGLU of ``n_shared`` times that width, none at 0) every token
    passes through.  With ``n_group`` > 1 the choice is group-limited:
    the experts lie in ``n_group`` equal groups in index order, a group
    scores the sum of its 2 largest choice values, and only the experts
    of the ``topk_group`` best groups can be chosen (1 / 1: no limit).
    ``route_eps`` is added to the chosen scores' sum before the
    normalisation divides by it.
    ``first`` / ``count`` say which experts THIS process holds (expert
    parallelism: the router keeps its published width, the layer
    computes the shared expert plus the held experts' part of the sum
    and hands that partial result on; nothing stands in for the rest)."""
    n_experts: int = 16
    top_k: int = 4
    d_expert: int = 64
    route_norm: bool = True
    route_scale: float = 1.0
    first: int = 0
    count: int = 0      # 0 = all of them
    score_func: str = "sigmoid"
    router_bias: bool = True
    n_shared: int = 1
    n_group: int = 1
    topk_group: int = 1
    # added to the chosen scores' sum before ``route_norm`` divides by it
    route_eps: float = 1e-20

    @property
    def held(self):
        return self.count or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention: the query goes through a normed
    bottleneck of ``q_lora``; keys and values of ALL heads are
    up-projections of one normed latent of ``kv_lora`` a token, and a
    rotary key of ``d_rope`` is shared by all heads.  A head's query and
    key are ``d_nope`` position-free dimensions followed by ``d_rope``
    rotary ones, its value ``d_v``.  Rotary pairs are (2k, 2k+1); with
    ``rope_factor`` > 1 the frequencies are YaRN's blend
    (:func:`yarn_inv_freq`) and the softmax scale carries
    ``yarn_mscale(rope_factor, mscale_all_dim) ** 2``.

    The cache holds ``width`` = ``kv_lora + d_rope`` values a token a
    layer, ``[c_kv ; k_pe]``, in a row of ``row`` lanes: the width
    rounded up to whole 128-lane tiles, zeros behind it.  (The TPU lays
    an array's minor dimension out in tiles of 128 lanes whatever its
    shape says, and a Pallas operand is never laid out otherwise: the
    padding is stated so that the pool's bytes are what HBM holds.)"""
    q_lora: int = 24
    kv_lora: int = 32
    d_nope: int = 16
    d_rope: int = 8
    d_v: int = 16
    rope_factor: float = 1.0
    rope_orig_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def d_qk(self):
        return self.d_nope + self.d_rope

    @property
    def width(self):
        return self.kv_lora + self.d_rope

    @property
    def row(self):
        return -(-self.width // 128) * 128


class UnsupportedArchitecture(ValueError):
    """A path of the program that was written for the plain block (or
    for one page class) was asked to run a configuration it cannot
    serve: refused where it is asked for, never served wrong."""


def llama3_8b():
    return LlamaConfig(attn_impl="pallas")


def llama3_3b():
    """Llama-3.2-3B shapes (untied head): ~3.6B params ≈ 7.2 GB bf16 —
    the largest preset that fits a single v5e chip's 16 GB HBM with KV
    cache and compiler workspace to spare (the 8B preset's 16 GB of
    weights alone would not).  The single-chip serving flagship."""
    return LlamaConfig(
        d_model=3072, n_layers=28, n_heads=24, n_kv_heads=8, d_ff=8192,
        attn_impl="pallas",
    )


def llama3_1b():
    """Llama-3.2-1B shapes (untied head): ~1.5B params ≈ 3 GB bf16."""
    return LlamaConfig(
        d_model=2048, n_layers=16, n_heads=32, n_kv_heads=8, d_ff=8192,
        attn_impl="pallas",
    )


def tiny(vocab=256):
    """Test-size config: same graph, toy dims (multiples of 8 for sharding)."""
    return LlamaConfig(
        vocab=vocab, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=128, rope_theta=10000.0,
    )


def tiny_afmoe(vocab=256, window=32, first=0, count=0):
    """Test-size AFMoE block (the Trinity family): a dense window layer,
    then routed layers window, full, window; explicit head size,
    per-head QK norm, output gate, sandwich norms, scaled embedding,
    sigmoid top-4 routing over 16 experts with a shared expert."""
    return LlamaConfig(
        vocab=vocab, d_model=64, n_layers=4, n_heads=8, n_kv_heads=4,
        d_head=16, d_ff=128, rope_theta=10000.0,
        layer_types=("window", "window", "full", "window"), window=window,
        rope_layers="window", qk_norm=True, attn_gate=True,
        sandwich_norm=True, embed_scale=8.0,
        ffn_types=("dense", "moe", "moe", "moe"),
        moe=MoEConfig(n_experts=16, top_k=4, d_expert=32, route_scale=2.448,
                      first=first, count=count),
    )


def tiny_deepseek(vocab=256, first=0, count=0):
    """Test-size latent-attention MoE block (the DeepSeek-V3 family): a
    dense layer, then routed layers; multi-head latent attention with
    YaRN-scaled rotary pairs on 8 of a head's 24 query dimensions;
    group-limited sigmoid top-4 of 16 experts in 4 groups of which 2
    are kept, with a shared expert."""
    return LlamaConfig(
        vocab=vocab, d_model=64, n_layers=3, n_heads=8, n_kv_heads=8,
        d_ff=128, rope_theta=10000.0, norm_eps=1e-6,
        ffn_types=("dense", "moe", "moe"),
        moe=MoEConfig(n_experts=16, top_k=4, d_expert=32, route_scale=2.5,
                      first=first, count=count, n_group=4, topk_group=2),
        mla=MLAConfig(q_lora=24, kv_lora=32, d_nope=16, d_rope=8, d_v=16,
                      rope_factor=40.0, rope_orig_max=4096,
                      mscale_all_dim=1.0),
    )


def tiny_lfm2(vocab=256):
    """Test-size LFM2 block (gated short convolutions beside QK-normed
    GQA, the LFM2-MoE family): a dense conv layer, then routed layers
    attention, conv, conv, attention, conv; a 3-tap convolution, rotary
    positions on every attention layer, sigmoid top-2 of 8 experts with
    an expert bias for the choice and no shared expert, the head tied to
    the embedding."""
    return LlamaConfig(
        vocab=vocab, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2,
        d_ff=128, rope_theta=10000.0, qk_norm=True,
        layer_types=("conv", "full", "conv", "conv", "full", "conv"),
        conv_len=3, ffn_types=("dense",) + ("moe",) * 5,
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=0,
                      route_eps=1e-6),
        tie_embed=True,
    )


# -- parameters --------------------------------------------------------------


def init_params(key, cfg):
    """Pytree of bf16 params: {embed, layers: [..], norm}."""
    k_embed, k_out, *k_layers = jax.random.split(key, 2 + cfg.n_layers)
    hd = cfg.head_dim

    def dense(k, shape, fan_in):
        return (
            jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)
        ).astype(cfg.dtype)

    layers = []
    for i, kl in enumerate(k_layers):
        ks = jax.random.split(kl, 7)
        layer = {
            "attn_norm": jnp.ones((cfg.d_model,), cfg.dtype),
            "mlp_norm": jnp.ones((cfg.d_model,), cfg.dtype),
        }
        if cfg.layer_conv(i):
            # the short-convolution mixer: in-projection to [B ; C ; x~],
            # the taps, the out-projection
            layer.update({
                "conv_in": dense(ks[0], (cfg.d_model, 3 * cfg.d_model),
                                 cfg.d_model),
                "conv_w": dense(ks[1], (cfg.conv_len, cfg.d_model),
                                cfg.conv_len),
                "conv_out": dense(ks[2], (cfg.d_model, cfg.d_model),
                                  cfg.d_model),
            })
        elif cfg.mla is None:
            layer.update({
                "wq": dense(ks[0], (cfg.d_model, cfg.n_heads * hd),
                            cfg.d_model),
                "wk": dense(ks[1], (cfg.d_model, cfg.n_kv_heads * hd),
                            cfg.d_model),
                "wv": dense(ks[2], (cfg.d_model, cfg.n_kv_heads * hd),
                            cfg.d_model),
                "wo": dense(ks[3], (cfg.n_heads * hd, cfg.d_model),
                            cfg.n_heads * hd),
            })
        if not cfg.layer_moe(i):
            layer.update({
                "w_gate": dense(ks[4], (cfg.d_model, cfg.d_ff), cfg.d_model),
                "w_up": dense(ks[5], (cfg.d_model, cfg.d_ff), cfg.d_model),
                "w_down": dense(ks[6], (cfg.d_ff, cfg.d_model), cfg.d_ff),
            })
        if not cfg.plain:
            layer.update(_init_block_extras(kl, cfg, i, dense))
        layers.append(layer)
    params = {
        "embed": dense(k_embed, (cfg.vocab, cfg.d_model), cfg.d_model),
        "layers": layers,
        "norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embed:
        params["lm_head"] = dense(k_out, (cfg.d_model, cfg.vocab),
                                  cfg.d_model)
    return params


def _init_block_extras(key, cfg, i, dense):
    """The leaves a layer has beyond the plain block's nine, by what
    ``cfg`` switches on.  Gains and router biases are random (a test
    with all-ones gains would not see a norm applied in the wrong
    place)."""
    hd, d = cfg.head_dim, cfg.d_model
    ks = jax.random.split(jax.random.fold_in(key, 7), 12)

    def gain(k, n):
        return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
                ).astype(cfg.dtype)

    out = {}
    attends = not cfg.layer_conv(i)
    if cfg.mla is not None:
        # the latent kind's projections in the GQA ones' place; the
        # per-head up-projections of keys and values are two leaves, as
        # the absorbed (decode) and the expanded (prefill) form use them
        m, nh = cfg.mla, cfg.n_heads
        km = jax.random.split(jax.random.fold_in(key, 8), 8)
        out.update({
            "wq_a": dense(km[0], (d, m.q_lora), d),
            "q_a_norm": gain(km[1], m.q_lora),
            "wq_b": dense(km[2], (m.q_lora, nh * m.d_qk), m.q_lora),
            "wkv_a": dense(km[3], (d, m.width), d),
            "kv_a_norm": gain(km[4], m.kv_lora),
            "w_uk": dense(km[5], (nh, m.d_nope, m.kv_lora), m.kv_lora),
            "w_uv": dense(km[6], (nh, m.kv_lora, m.d_v), m.kv_lora),
            "wo": dense(km[7], (nh * m.d_v, d), nh * m.d_v),
        })
    if cfg.qk_norm and attends:
        out["q_norm"], out["k_norm"] = gain(ks[0], hd), gain(ks[1], hd)
    if cfg.attn_gate and attends:
        out["wg"] = dense(ks[2], (d, cfg.n_heads * hd), d)
    if cfg.sandwich_norm:
        out["attn_post_norm"] = gain(ks[3], d)
        out["mlp_post_norm"] = gain(ks[4], d)
    if cfg.layer_moe(i):
        m = cfg.moe
        f, e = m.d_expert, m.held
        out["router"] = dense(ks[5], (d, m.n_experts), d)
        if m.router_bias:
            out["router_bias"] = 0.1 * jax.random.normal(
                ks[6], (m.n_experts,), jnp.float32)
        if m.n_shared:
            fs = f * m.n_shared
            out["ws_gate"] = dense(ks[7], (d, fs), d)
            out["ws_up"] = dense(ks[8], (d, fs), d)
            out["ws_down"] = dense(ks[9], (fs, d), fs)
        # every expert's values depend on its own id alone, so a share
        # holds the same experts the uncut layer has
        def experts(k, shape, fan_in):
            return jnp.stack([
                dense(jax.random.fold_in(k, m.first + j), shape, fan_in)
                for j in range(e)])
        out["we_gate"] = experts(ks[10], (d, f), d)
        out["we_up"] = experts(ks[11], (d, f), d)
        out["we_down"] = experts(jax.random.fold_in(ks[11], 1), (f, d), f)
    return out


def tiny_sdar(vocab=256, block_len=4):
    """Test-size block-diffusion MoE block (the SDAR family): rotary GQA
    with per-head QK norm, every layer routed by a softmax top-2 of 8
    experts with no shared expert and no router bias, generation over
    blocks of ``block_len`` with the vocabulary's last id as the mask."""
    return LlamaConfig(
        vocab=vocab, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
        d_head=16, d_ff=128, rope_theta=10000.0, qk_norm=True,
        ffn_types=("moe", "moe"),
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=32,
                      score_func="softmax", router_bias=False, n_shared=0),
        block_len=block_len, mask_id=vocab - 1,
    )


def _need_plain(cfg, what):
    if not cfg.plain:
        raise UnsupportedArchitecture(
            "{} serves the plain Llama / Mistral block only; this "
            "configuration has per-layer attention or feed-forward kinds, "
            "QK norm, an output gate, sandwich norms, a scaled embedding, "
            "latent attention or generation over blocks".format(what))


def param_specs(cfg, quantized=False, quantized_embed=False):
    """PartitionSpec pytree: Megatron split — qkv/gate/up column-parallel on
    tp, o/down row-parallel; embeddings sharded on vocab.

    With ``quantized=True`` the specs match the ``quantize_params`` tree:
    each int8 weight keeps its bf16 spec and its per-output-channel scale
    vector shards along the weight's sharded *output* dim (replicated for
    row-parallel weights, whose outputs are unsharded).  Pass
    ``quantized_embed=True`` iff ``quantize_params`` ran with
    ``quantize_embed=True`` (its per-ROW scales shard with the vocab
    rows)."""

    _need_plain(cfg, "tensor-parallel sharding (param_specs)")

    def wspec(spec, out_axis_name):
        if not quantized:
            return spec
        return {"q": spec, "s": P(out_axis_name)}

    layer = {
        "attn_norm": P(),
        "wq": wspec(P(None, "tp"), "tp"),
        "wk": wspec(P(None, "tp"), "tp"),
        "wv": wspec(P(None, "tp"), "tp"),
        "wo": wspec(P("tp", None), None),
        "mlp_norm": P(),
        "w_gate": wspec(P(None, "tp"), "tp"),
        "w_up": wspec(P(None, "tp"), "tp"),
        "w_down": wspec(P("tp", None), None),
    }
    return {
        "embed": (
            {"q": P("tp", None), "s": P("tp")}
            if quantized and quantized_embed
            else P("tp", None)
        ),
        "layers": [
            {k: (dict(v) if isinstance(v, dict) else v)
             for k, v in layer.items()}
            for _ in range(cfg.n_layers)
        ],
        "norm": P(),
        "lm_head": wspec(P(None, "tp"), "tp"),
    }


def quantize_params(params, quantize_embed=False):
    """Int8-quantize the serving weights (per-output-channel scales).

    Layer matmul weights and ``lm_head`` go int8 (~2x HBM shrink — what
    fits the 8B preset's 16 GB of bf16 weights into a single v5e);
    norms stay bf16.  ``embed`` is a row gather, not a matmul; it stays
    bf16 by default for exact lookups (pass ``quantize_embed=True`` to
    shrink it too).
    """
    from tpuserver.ops import quant

    out = {
        "embed": (
            quant.quantize_int8(params["embed"], axis=1)
            if quantize_embed
            else params["embed"]
        ),
        "norm": params["norm"],
        "lm_head": quant.quantize_int8(params["lm_head"], axis=0),
        "layers": [],
    }
    for layer in params["layers"]:
        out["layers"].append(
            {
                "attn_norm": layer["attn_norm"],
                "mlp_norm": layer["mlp_norm"],
                "wq": quant.quantize_int8(layer["wq"], axis=0),
                "wk": quant.quantize_int8(layer["wk"], axis=0),
                "wv": quant.quantize_int8(layer["wv"], axis=0),
                "wo": quant.quantize_int8(layer["wo"], axis=0),
                "w_gate": quant.quantize_int8(layer["w_gate"], axis=0),
                "w_up": quant.quantize_int8(layer["w_up"], axis=0),
                "w_down": quant.quantize_int8(layer["w_down"], axis=0),
            }
        )
    return out


# -- kernels -----------------------------------------------------------------


def _flash_blocks(T, cfg):
    """Largest usable (block_q, block_k) for a length-T flash prefill:
    the preferred (tuned) tile when T divides by it, else the largest
    smaller one that does (down to 128), else None (caller falls back
    to dense attention).  Key heads under 128 wide and block diagonals
    take at most 256 x 512, the tile their models' outputs were held to
    the references at (PERF.md, open question 22)."""
    pref_q, pref_k = cfg.flash_block_q, cfg.flash_block_k
    width = cfg.mla.d_qk if cfg.mla is not None else cfg.head_dim
    if width < 128 or cfg.block_len:
        pref_q, pref_k = min(pref_q, 256), min(pref_k, 512)
    bq = next(
        (b for b in (pref_q, 256, 128) if b <= T and T % b == 0),
        None,
    )
    bk = next(
        (b for b in (pref_k, 512, 256, 128) if b <= T and T % b == 0),
        None,
    )
    return bq, bk


def flash_prefill_pairs(cfg, T):
    """``(pairs, grid_pairs)`` of a whole-prompt prefill of T positions:
    the (q-block, k-block) pairs the flash kernel folds
    (``ops.flash.flash_schedule`` at the tiles ``_flash_blocks`` gives
    T), and those a square grid over the same tiles would step through,
    summed over the attention layers and the query heads.  ``(0, 0)``
    where such a prefill attends on the dense path."""
    bq, bk = _flash_blocks(T, cfg)
    if cfg.attn_impl != "pallas" or T < 2 or bq is None or bk is None:
        return 0, 0
    from tpuserver.ops.flash import flash_schedule

    pairs = sum(
        len(flash_schedule(T, T, bq, bk, True, cfg.layer_window(i) or None,
                           cfg.block_len or None)[0])
        for i in cfg.attn_layers)
    grid = len(cfg.attn_layers) * (T // bq) * (T // bk)
    return pairs * cfg.n_heads, grid * cfg.n_heads


def named_partial(fn, **kwargs):
    """``functools.partial`` that keeps ``fn``'s name.  ``jax.jit`` names
    an executable after its function's ``__name__`` and a bare partial
    has none, so a profile's ``XLA Modules`` line (and the host's
    ``PjitFunction`` span) would read ``jit__unknown`` for every one."""
    bound = functools.partial(fn, **kwargs)
    bound.__name__ = fn.__name__
    return bound


def _mm(x, w):
    """Matmul against a plain or int8-quantized weight leaf."""
    from tpuserver.ops import quant

    return quant.matmul(x, w)


def _head(params, x, cfg):
    """Float32 logits of final-normed rows ``x``: through the head, or
    through the embedding's transpose where the head is tied to it."""
    if cfg.tie_embed:
        return jnp.einsum("...d,vd->...v", x, params["embed"]).astype(
            jnp.float32)
    return _mm(x, params["lm_head"]).astype(jnp.float32)


def _lanes(x, cfg):
    """``x`` [..., head_dim] in the cache's ``kv_width`` lanes, zeros
    behind (itself where the two agree)."""
    extra = cfg.kv_width - x.shape[-1]
    if not extra:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def _lanes_cut(out, cfg):
    """An attention output over padded lanes back to ``head_dim``."""
    return out if cfg.kv_width == cfg.head_dim else out[..., :cfg.head_dim]


def _lanes_scale(cfg):
    """The score scale of a query padded to ``kv_width`` lanes: its own
    head size's, or None (the kernel's default) where nothing is
    padded."""
    return None if cfg.kv_width == cfg.head_dim else cfg.head_dim ** -0.5


def _embed_rows(params, tokens, cfg=None):
    from tpuserver.ops import quant

    with jax.named_scope("embed"):
        x = quant.gather_rows(
            params["embed"], tokens,
            dtype=cfg.dtype if cfg is not None else None,
        )
        if cfg is not None and cfg.embed_scale != 1.0:
            x = (x.astype(jnp.float32) * cfg.embed_scale).astype(x.dtype)
        return x


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def _rope(x, positions, theta):
    """Rotary embedding. x: [B, T, H, D]; positions: [T] or [B, T]."""
    d = x.shape[-1]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def yarn_mscale(factor, mscale):
    """YaRN's attention-temperature term, 1 at or below factor 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * np.log(factor) + 1.0


def yarn_inv_freq(m, theta):
    """The ``d_rope / 2`` rotary frequencies of a latent-attention
    configuration ``m`` (float64): plain ``theta ** (-2k / d_rope)`` at
    ``rope_factor`` <= 1, else YaRN's blend of those (extrapolated) with
    the same divided by the factor (interpolated), by a linear ramp
    between the dimensions that make ``beta_fast`` and ``beta_slow``
    rotations over ``rope_orig_max`` positions."""
    d = m.d_rope
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if m.rope_factor <= 1.0:
        return extra

    def dim_of(rotations):
        return (d * np.log(m.rope_orig_max / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(dim_of(m.beta_fast)), 0)
    high = min(np.ceil(dim_of(m.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return extra / m.rope_factor * ramp + extra * (1.0 - ramp)


def mla_softmax_scale(m):
    """``d_qk ** -0.5`` times the square of YaRN's temperature term."""
    return float(m.d_qk ** -0.5
                 * yarn_mscale(m.rope_factor, m.mscale_all_dim) ** 2)


def _rope_pairs(x, positions, m, theta):
    """Rotary embedding over pairs (2k, 2k+1) of the last dimension at
    ``m``'s frequencies (:func:`yarn_inv_freq`).  x: [B, T, D] or
    [B, T, H, D]; positions: [T] or [B, T]."""
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(
        yarn_inv_freq(m, theta), jnp.float32)                # [B, T, D/2]
    # cos and sin carry mscale / mscale_all_dim's terms' ratio
    ratio = (yarn_mscale(m.rope_factor, m.mscale)
             / yarn_mscale(m.rope_factor, m.mscale_all_dim))
    cos, sin = jnp.cos(angles) * ratio, jnp.sin(angles) * ratio
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _mla_project(params, h, positions, cfg, nh):
    """The latent kind's projections of the normed input h [B, T, Dm]:
    ``(q [B, T, H, d_nope + d_rope], latent [B, T, row])``, the query's
    rotary part and the latent's shared rotary key already rotated, the
    latent ``[RMSNorm(c_kv) ; k_pe ; zeros]`` as the cache keeps it."""
    m = cfg.mla
    B, T, _ = h.shape
    with jax.named_scope("mla.q_proj"):
        c_q = _rms_norm(_mm(h, params["wq_a"]), params["q_a_norm"],
                        cfg.norm_eps)
        q = _mm(c_q, params["wq_b"]).reshape(B, T, nh, m.d_qk)
        q = jnp.concatenate(
            [q[..., :m.d_nope],
             _rope_pairs(q[..., m.d_nope:], positions, m, cfg.rope_theta)],
            axis=-1)
    with jax.named_scope("mla.kv_latent"):
        kv = _mm(h, params["wkv_a"])
        c_kv = _rms_norm(kv[..., :m.kv_lora], params["kv_a_norm"],
                         cfg.norm_eps)
        k_pe = _rope_pairs(kv[..., m.kv_lora:], positions, m,
                           cfg.rope_theta)
        latent = jnp.concatenate(
            [c_kv, k_pe, jnp.zeros((B, T, m.row - m.width), c_kv.dtype)],
            axis=-1)
    return q, latent


def _mla_expand(params, latent, cfg):
    """The expanded form's keys and values of cached latents
    [B, S, row]: ``k [B, S, H, d_nope + d_rope]`` (the shared rotary key
    repeated a head) and ``v [B, S, H, d_v]``."""
    m = cfg.mla
    with jax.named_scope("mla.expand"):
        c_kv = latent[..., :m.kv_lora]
        k_nope = jnp.einsum("bsc,hnc->bshn", c_kv, params["w_uk"])
        v = jnp.einsum("bsc,hcv->bshv", c_kv, params["w_uv"])
        k_pe = jnp.broadcast_to(
            latent[:, :, None, m.kv_lora:m.width],
            k_nope.shape[:3] + (m.d_rope,))
        return jnp.concatenate([k_nope, k_pe], axis=-1), v


def _mla_absorb_q(params, q, cfg):
    """A query carried into the latent space: q [B, T, H, d_qk] ->
    ``[W_UK q_nope ; q_pe ; zeros]`` [B, T, H, row], which scores a
    cached row by one dot over the row's lanes."""
    m = cfg.mla
    with jax.named_scope("mla.absorb_q"):
        q_lat = jnp.einsum("bthn,hnc->bthc", q[..., :m.d_nope],
                           params["w_uk"])
        return jnp.concatenate(
            [q_lat, q[..., m.d_nope:],
             jnp.zeros(q.shape[:3] + (m.row - m.width,), q.dtype)],
            axis=-1)


def _mla_absorb_out(params, u, cfg):
    """The attended latents u [B, T, H, kv_lora] through each head's
    value up-projection: [B, T, H, d_v]."""
    with jax.named_scope("mla.absorb_out"):
        return jnp.einsum("bthc,hcv->bthv", u, params["w_uv"])


def _mla_attend_cached(params, q, latents, q_pos, lengths, cfg):
    """Dense absorbed attention of q [B, T, H, d_qk] over cached latents
    [B, S, row]: one key head of all the row's lanes whose first
    ``kv_lora`` lanes are the value.  Where no kernel serves (test
    sizes, the single-stream path)."""
    m = cfg.mla
    q_lat = _mla_absorb_q(params, q, cfg)
    with jax.named_scope("attn.kernel"):
        u = _attend_cached(
            q_lat, latents[:, :, None, :], latents[:, :, None, :m.kv_lora],
            q_pos, lengths, q.shape[2], scale=mla_softmax_scale(m))
    return _mla_absorb_out(params, u, cfg)


def _expand_kv(k, n_rep):
    """GQA: repeat kv heads to full head count. [B,T,Hkv,D] -> [B,T,H,D]."""
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def _swiglu(h, w_gate, w_up, w_down, red=lambda y: y):
    gated = jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up)
    return red(_mm(gated, w_down))


def _route(params, x, m):
    """The router's choice for rows x [n, Dm]: ``(chosen [n, top_k] of all
    n_experts, w [n, top_k])``.  Scores in float32 at full precision
    (``m.score_func``: sigmoid, or softmax over all experts);
    ``top_k`` of ``score + bias`` (of the score alone without a
    ``router_bias``; among the kept groups' experts alone under a group
    limit, :func:`_group_limited`), weighed by their own scores,
    normalised and scaled.  A choice between two experts whose scores nearly tie is the
    one discrete step of the layer, and it should flip as rarely as
    arithmetic allows."""
    scores = jnp.dot(
        x.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(scores, axis=-1) if m.score_func == "softmax"
              else jax.nn.sigmoid(scores))
    choice = scores + params["router_bias"] if m.router_bias else scores
    if m.n_group > 1:
        choice = _group_limited(choice, m)
    _, chosen = lax.top_k(choice, m.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if m.route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + m.route_eps)
    return chosen, w * m.route_scale


def _group_limited(choice, m):
    """``choice`` [n, E] with every expert outside the ``topk_group``
    best of the ``n_group`` groups at -inf.  A group's score is the sum
    of its 2 largest choice values; ties go to the lower index."""
    n, e = choice.shape
    per = e // m.n_group
    best, _ = lax.top_k(choice.reshape(n, m.n_group, per), min(2, per))
    _, keep = lax.top_k(jnp.sum(best, axis=-1), m.topk_group)
    kept = jnp.any(
        keep[:, :, None] == jnp.arange(m.n_group)[None, None, :], axis=1)
    return jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)


def _moe_ffn(params, h, cfg, live=None, stats=None):
    """The routed feed-forward of one layer, as this process holds it:
    ``Shared(y) + sum over the chosen experts that are held here of
    w_e Expert_e(y)`` for h [B, T, Dm] (no ``Shared`` where the
    configuration has no shared expert).

    The router (:func:`_route`) chooses among all ``n_experts``.  The
    (token, expert) pairs whose expert is held here (and whose row is
    ``live``: an inert row or a padding token must not make an expert's
    weights stream) are sorted by expert and go through ONE kernel,
    ``ops.moe_grouped_matmul``, three times (gate, up, down); no pair is
    ever dropped.  ``stats`` (a list) receives ``(pairs held here,
    distinct held experts hit)`` of this call as two int32 scalars."""
    from tpuserver.ops.moe import moe_grouped_matmul

    m = cfg.moe
    b, t, d = h.shape
    n, k, e = b * t, m.top_k, m.held
    x = h.reshape(n, d)
    with jax.named_scope("moe.route"):
        chosen, w = _route(params, x, m)
    if m.n_shared:
        with jax.named_scope("moe.shared"):
            out = _swiglu(x, params["ws_gate"], params["ws_up"],
                          params["ws_down"]).astype(jnp.float32)
    else:
        out = jnp.zeros((n, d), jnp.float32)
    with jax.named_scope("moe.dispatch"):
        local = chosen - m.first
        held = (local >= 0) & (local < e)
        if live is not None:
            held = held & live.reshape(n, 1)
        # pairs of experts held elsewhere sort behind every group
        group = jnp.where(held, local, e).reshape(n * k)
        order = jnp.argsort(group)
        sizes = jnp.sum(
            group[:, None] == jnp.arange(e, dtype=group.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        xs = x[order // k]
    with jax.named_scope("moe.experts"):
        act = (jax.nn.silu(moe_grouped_matmul(xs, params["we_gate"], sizes))
               * moe_grouped_matmul(xs, params["we_up"], sizes))
        ys = moe_grouped_matmul(act, params["we_down"], sizes)
    with jax.named_scope("moe.combine"):
        # rows behind the last group were never written
        ys = jnp.where((group[order] < e)[:, None], ys, 0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=order.dtype))
        ys = ys[back].reshape(n, k, d).astype(jnp.float32)
        out = out + jnp.sum(ys * w[:, :, None], axis=1)
    if stats is not None:
        stats.append((jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32)))
    return out.astype(h.dtype).reshape(b, t, d)


def _block(params, x, positions, cfg, attn_fn, n_heads=None, n_kv_heads=None,
           reduce=None, layer=0, live=None, moe_stats=None):
    """One transformer block: x [B, T, Dm] -> [B, T, Dm].

    The single source of the block math — dense forward, the tp-sharded
    SPMD forward, bulk prefill and token decode all call this with different
    ``attn_fn`` closures.  ``n_heads``/``n_kv_heads`` are the *local* head
    counts (tp-sharded callers pass per-shard values); ``reduce`` is applied
    to row-parallel matmul outputs (psum over tp in SPMD, identity here).
    What the block is made of is ``cfg``'s data for ``layer``: rotary
    positions or none, per-head QK norm, an output gate, sandwich norms,
    a dense or a routed feed-forward (``live`` [B, T] and ``moe_stats``:
    :func:`_moe_ffn`); the defaults trace the plain Llama block.
    """
    B, T, _ = x.shape
    hd = cfg.head_dim
    nh = n_heads if n_heads is not None else cfg.n_heads
    nkv = n_kv_heads if n_kv_heads is not None else cfg.n_kv_heads
    red = reduce if reduce is not None else (lambda y: y)
    if cfg.layer_conv(layer):
        # the short-convolution mixer: the closure gets the normed rows
        # and holds the window (zeros before a sequence's start, a
        # slot's saved rows in decode)
        return _block_ffn(params, _block_conv(params, x, cfg, attn_fn),
                          cfg, red, layer, live, moe_stats)
    if cfg.mla is not None:
        # the latent kind: the closure gets the query, the cache row of
        # the step's own tokens in the keys' place and no values (it
        # writes the row, and attends absorbed or expanded)
        return _block_ffn(
            params, _block_latent_attn(params, x, positions, cfg, attn_fn,
                                       nh, red),
            cfg, red, layer, live, moe_stats)
    with jax.named_scope("attn.qkv"):
        h = _rms_norm(x, params["attn_norm"], cfg.norm_eps)
        q = _mm(h, params["wq"]).reshape(B, T, nh, hd)
        k = _mm(h, params["wk"]).reshape(B, T, nkv, hd)
        v = _mm(h, params["wv"]).reshape(B, T, nkv, hd)
        if cfg.qk_norm:
            q = _rms_norm(q, params["q_norm"], cfg.norm_eps)
            k = _rms_norm(k, params["k_norm"], cfg.norm_eps)
        if cfg.layer_rope(layer):
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        gate = _mm(h, params["wg"]) if cfg.attn_gate else None
    # the caller's closure: attn.kv_write / attn.page_gather / attn.kernel
    attn = attn_fn(q, k, v)
    with jax.named_scope("attn.out"):
        attn = attn.reshape(B, T, nh * hd)
        if gate is not None:
            attn = (attn.astype(jnp.float32)
                    * jax.nn.sigmoid(gate.astype(jnp.float32))
                    ).astype(attn.dtype)
        out = red(_mm(attn, params["wo"]))
        if cfg.sandwich_norm:
            out = _rms_norm(out, params["attn_post_norm"], cfg.norm_eps)
        x = x + out
    return _block_ffn(params, x, cfg, red, layer, live, moe_stats)


def conv_mix(bcx, prev, taps):
    """The short convolution between a conv mixer's projections, over T
    new rows of one or more sequences: ``bcx`` [B, T, 3D] is
    ``[B ; C ; x~]``, ``prev`` [B, L-1, D] the rows of ``u`` before them
    (zeros before a sequence's start), ``taps`` [L, D].  ``u = B * x~``,
    ``z_t = sum_j taps_j u_{t-L+1+j}`` (causal, depthwise).  Returns
    ``(C * z [B, T, D], rows [B, L-1+T, D])``: ``rows`` is ``prev`` then
    ``u``, so the window after the first ``n`` new rows is ``rows[:,
    n:n+L-1]``.  Float32 inside, rounded once."""
    f32 = jnp.float32
    b, c, xt = jnp.split(bcx, 3, axis=-1)
    u = (b.astype(f32) * xt.astype(f32)).astype(bcx.dtype)
    rows = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    t, taps = u.shape[1], taps.astype(f32)
    z = rows[:, :t].astype(f32) * taps[0]
    for j in range(1, taps.shape[0]):
        z = z + rows[:, j:j + t].astype(f32) * taps[j]
    return (c.astype(f32) * z).astype(bcx.dtype), rows


def _block_conv(params, x, cfg, conv_fn):
    """The mixer half of :func:`_block` in a conv layer: x + Mix(N(x)),
    ``Mix(y) = W_out (C * z)`` with ``[B ; C ; x~] = W_in y`` and z the
    short convolution of ``u = B * x~`` (:func:`conv_mix`).  What the
    convolution reaches behind the new rows is the caller's:
    ``conv_fn(h)`` of the normed rows returns ``C * z`` and keeps the
    window (:func:`_conv_in`, then :func:`conv_mix`)."""
    with jax.named_scope("conv.in_proj"):
        h = _rms_norm(x, params["attn_norm"], cfg.norm_eps)
    with jax.named_scope("conv.window"):
        y = conv_fn(h)
    with jax.named_scope("conv.out_proj"):
        return x + _mm(y, params["conv_out"])


def _conv_in(params, h):
    """The in-projection of normed rows h to ``[B ; C ; x~]``."""
    with jax.named_scope("conv.in_proj"):
        return _mm(h, params["conv_in"])


def _block_latent_attn(params, x, positions, cfg, attn_fn, nh, red):
    """The attention half of :func:`_block` under latent attention:
    x [B, T, Dm] -> x + Attn(N(x))."""
    B, T, _ = x.shape
    h = _rms_norm(x, params["attn_norm"], cfg.norm_eps)
    q, latent = _mla_project(params, h, positions, cfg, nh)
    # the caller's closure: attn.kv_write, then mla.absorb_q /
    # attn.kernel / mla.absorb_out, or mla.expand / attn.kernel
    attn = attn_fn(q, latent, None)
    with jax.named_scope("attn.out"):
        out = red(_mm(attn.reshape(B, T, nh * cfg.mla.d_v), params["wo"]))
        if cfg.sandwich_norm:
            out = _rms_norm(out, params["attn_post_norm"], cfg.norm_eps)
        return x + out


def _block_ffn(params, x, cfg, red, layer, live, moe_stats):
    """The feed-forward half of :func:`_block`: x -> x + FFN(N(x))."""
    with jax.named_scope("ffn"):
        h = _rms_norm(x, params["mlp_norm"], cfg.norm_eps)
        if cfg.layer_moe(layer):
            out = _moe_ffn(params, h, cfg, live, moe_stats)
        else:
            out = _swiglu(h, params["w_gate"], params["w_up"],
                          params["w_down"], red)
        if cfg.sandwich_norm:
            out = _rms_norm(out, params["mlp_post_norm"], cfg.norm_eps)
        return x + out


def _dense_causal(q, k, v, n_rep, window=0, block=0, scale=None):
    """Plain causal (optionally windowed, or block-causal) self-attention,
    q [B, T, H, D] against its own k/v [B, T, Hkv, D]: the dense form
    the windowed and the block layers fall back to where the flash
    kernel's tiles do not fit."""
    t = q.shape[1]
    pos = jnp.tile(jnp.arange(t)[None, :], (q.shape[0], 1))
    return _attend_cached(q, k, v, pos, t, n_rep, window=window,
                          block=block, scale=scale)


def forward(params, tokens, cfg):
    """Teacher-forcing logits [B, T, vocab] (float32), single-shard attention
    (for sharded execution use ``sharded_forward``)."""
    B, T = tokens.shape
    n_rep = 1 if cfg.mla is not None else cfg.n_heads // cfg.n_kv_heads
    positions = jnp.arange(T)

    def attn_fn(q, k, v, window=0, layer=None):
        scale = None
        if cfg.mla is not None:
            # the expanded form: every head its own keys and values
            k, v = _mla_expand(layer, k, cfg)
            scale = mla_softmax_scale(cfg.mla)
        bq, bk = _flash_blocks(T, cfg)
        if cfg.attn_impl == "pallas" and bq is not None and bk is not None:
            # MXU-tileable lengths only: the TPU lowering needs
            # (8, 128)-aligned blocks; other lengths fall through to
            # the dense path below
            from tpuserver.ops import flash_attention

            return flash_attention(
                q, _expand_kv(k, n_rep), _expand_kv(v, n_rep),
                causal=True, block_q=bq, block_k=bk,
                window=window or None,
                block_causal=cfg.block_len or None, scale=scale,
            )
        if window or cfg.block_len or cfg.mla is not None:
            return _dense_causal(q, k, v, n_rep, window, cfg.block_len,
                                 scale)
        return ring_attention(
            q, _expand_kv(k, n_rep), _expand_kv(v, n_rep), causal=True
        )

    def conv_fn(h, layer):
        # zeros before the sequence's start, then the prompt
        prev = jnp.zeros((B, cfg.conv_len - 1, cfg.d_model), h.dtype)
        return conv_mix(_conv_in(layer, h), prev, layer["conv_w"])[0]

    x = _embed_rows(params, tokens, cfg)
    for i, layer in enumerate(params["layers"]):
        fn = (functools.partial(conv_fn, layer=layer) if cfg.layer_conv(i)
              else functools.partial(attn_fn, window=cfg.layer_window(i),
                                     layer=layer))
        x = _block(layer, x, positions, cfg, fn, layer=i)
    x = _rms_norm(x, params["norm"], cfg.norm_eps)
    return _head(params, x, cfg)


def sharded_forward(mesh, cfg):
    """shard_map-wrapped forward: batch on dp, time on sp, weights on tp."""
    from jax import shard_map

    specs = param_specs(cfg)
    fn = shard_map(
        functools.partial(_forward_spmd, cfg=cfg),
        mesh=mesh,
        in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp", "tp"),
        check_vma=False,
    )
    return fn


def _forward_spmd(params, tokens, cfg):
    # Inside shard_map each device holds a [B/dp, T/sp] token block and
    # tp-sharded weights; tp matmul partial-sums are reduced explicitly.
    _need_plain(cfg, "the sharded forward")
    B, T = tokens.shape
    tp = lax.psum(1, "tp")
    if cfg.n_kv_heads % tp != 0 or cfg.n_heads % tp != 0:
        raise ValueError(
            "tp={} must divide n_heads={} and n_kv_heads={} (KV-head "
            "replication across tp is not supported)".format(
                tp, cfg.n_heads, cfg.n_kv_heads
            )
        )
    nh_loc = cfg.n_heads // tp
    nkv_loc = cfg.n_kv_heads // tp
    n_rep = nh_loc // nkv_loc
    t0 = lax.axis_index("sp") * T
    positions = t0 + jnp.arange(T)

    if cfg.sp_strategy not in ("ring", "ulysses"):
        raise ValueError(
            "unknown sp_strategy '{}' (expected 'ring' or "
            "'ulysses')".format(cfg.sp_strategy)
        )

    def attn_fn(q, k, v):
        if cfg.sp_strategy == "ulysses":
            # unexpanded kv heads ride the all_to_alls; GQA replication
            # happens after redistribution
            return ulysses_attention(
                q, k, v, axis_name="sp", causal=True, kv_repeat=n_rep,
            )
        return ring_attention(
            q, _expand_kv(k, n_rep), _expand_kv(v, n_rep),
            axis_name="sp", causal=True,
        )

    def psum_tp(y):
        return lax.psum(y, "tp")

    # embed is vocab-sharded on tp: gather local rows then psum.
    vloc = params["embed"].shape[0]
    voff = lax.axis_index("tp") * vloc
    local = tokens - voff
    hit = (local >= 0) & (local < vloc)
    x = jnp.where(
        hit[..., None],
        params["embed"][jnp.clip(local, 0, vloc - 1)],
        jnp.zeros((), params["embed"].dtype),
    )
    x = lax.psum(x, "tp")
    for layer in params["layers"]:
        x = _block(
            layer, x, positions, cfg, attn_fn,
            n_heads=nh_loc, n_kv_heads=nkv_loc, reduce=psum_tp,
        )
    x = _rms_norm(x, params["norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


# -- training (for the multi-chip dry-run and completeness) ------------------


def make_train_step(mesh, cfg, learning_rate=3e-4):
    """jit-compiled SPMD train step over (dp, sp, tp).

    Loss is next-token cross-entropy; gradients/optimizer state inherit the
    parameter sharding, batch is (dp, sp)-sharded; XLA inserts the psums.
    Returns (step_fn, init_fn).
    """
    import optax

    tx = optax.adamw(learning_rate)
    pspecs = param_specs(cfg)
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs
    )
    batch_sh = NamedSharding(mesh, P("dp", "sp"))
    fwd = sharded_forward(mesh, cfg)

    def loss_fn(params, tokens, targets):
        logits = fwd(params, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    def init_fn(key, tokens):
        params = init_params(key, cfg)
        params = jax.device_put(params, param_sh)
        opt_state = tx.init(params)
        return params, opt_state

    @functools.partial(
        jax.jit,
        in_shardings=(param_sh, None, batch_sh, batch_sh),
        donate_argnums=(0,),
    )
    def step_fn(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step_fn, init_fn


# -- decode (serving) --------------------------------------------------------


def init_kv_cache(cfg, batch, max_seq, dtype=None):
    """The contiguous cache, by what a token's row is: K and V of every
    KV head, [n_layers, 2, B, max_seq, n_kv_heads, kv_width], or under
    latent attention ONE latent row (``MLAConfig.row`` lanes), no K/V
    pair and no head axis, [n_layers, B, max_seq, row].  With conv
    layers the K/V layer axis runs over the attention layers alone and
    the cache is ``{"kv": .., "conv": windows}`` (:func:`init_conv_state`)."""
    dtype = dtype or cfg.dtype
    if cfg.mla is not None:
        return jnp.zeros((cfg.n_layers, batch, max_seq, cfg.mla.row), dtype)
    kv = jnp.zeros(
        (len(cfg.attn_layers), 2, batch, max_seq, cfg.n_kv_heads,
         cfg.kv_width),
        dtype,
    )
    if cfg.conv_layers:
        return {"kv": kv, "conv": init_conv_state(cfg, batch, dtype)}
    return kv


def init_conv_state(cfg, rows, dtype=None):
    """The conv layers' windows: ``[n_conv_layers, rows, conv_len - 1,
    d_model]`` zeros, the last ``conv_len - 1`` rows of each conv layer's
    ``u`` a sequence (:func:`conv_mix`).  Fixed in size: no page, no
    table, whatever a sequence's length."""
    return jnp.zeros((len(cfg.conv_layers), rows, cfg.conv_len - 1,
                      cfg.d_model), dtype or cfg.dtype)


def decode_crossover_length(max_seq):
    """Valid-prefix length below which the Pallas decode-attention kernel
    beats dense XLA attention against a cache padded to ``max_seq``.

    Cost model fitted to the measured table in docs/benchmarking.md
    (v5e, llama3-class head geometry): dense reads the whole padded
    cache every token — ~16.5 ns/key at S=2k degrading to ~62 ns/key at
    S=32k as its MBU collapses — while the kernel's length-clamped index
    map costs ~4.6 µs fixed + ~24.7 µs per 1024 *valid* keys.  Returns
    <= 0 when dense always wins, >= max_seq when Pallas always wins.
    """
    pts = ((2048, 16.5), (8192, 18.7), (32768, 61.8))
    if max_seq <= pts[0][0]:
        ns_per_key = pts[0][1]
    elif max_seq >= pts[-1][0]:
        ns_per_key = pts[-1][1]
    else:
        ns_per_key = pts[0][1]
        for (s0, n0), (s1, n1) in zip(pts, pts[1:]):
            if s0 <= max_seq <= s1:
                ns_per_key = n0 + (n1 - n0) * (max_seq - s0) / (s1 - s0)
                break
    dense_us = max_seq * ns_per_key / 1000.0
    return int((dense_us - 4.6) / (24.7 / 1024.0))


def _select_decode_impl(max_seq, lengths):
    """Trace-time selection for ``decode_impl="auto"``.

    Static only: a per-step ``lax.cond`` on the live length was measured
    on v5e and rejected — XLA cannot donate/alias the KV cache through
    cond branches, so every step paid cache copies and long-context
    decode collapsed ~3x (70.8 -> 23.1 tokens/sec at ctx 2176).  With a
    static ``lengths`` the crossover applies exactly; otherwise the
    kernel is chosen when it wins for the MAJORITY of possible cache
    lengths (a serving request sweeps lengths upward, so the majority
    rule tracks the time-averaged cost)."""
    cross = decode_crossover_length(max_seq)
    if cross <= 0:
        return "xla"
    if cross >= max_seq:
        return "pallas"
    if isinstance(lengths, (int, np.integer)):
        return "pallas" if int(lengths) < cross else "xla"
    return "pallas" if cross >= max_seq // 2 else "xla"


def _decode_kernel_block(cfg, max_seq):
    """The K/V block the batched decode steps hand the Pallas decode
    kernel for rows of ``max_seq``, or None where they attend densely
    (``decode_impl`` resolves to ``"xla"``, or no 128-multiple block
    divides ``max_seq``)."""
    impl = cfg.decode_impl
    if impl == "auto":
        impl = _select_decode_impl(max_seq, None)
    if impl != "pallas":
        return None
    # a latent row is one KV head: larger blocks spread a grid step's
    # fixed cost over more rows (v5e, 32 rows of ~8,000: 5.9 ms a step
    # at 256, 4.7 at 512, 3.95 at 1,024, 4.05 at 2,048: PERF.md, PR 37)
    blocks = (1024, 512, 256, 128) if cfg.mla is not None else (256, 128)
    return next((b for b in blocks if max_seq % b == 0), None)


def _run_cached(params, cache, x, positions, write_pos, lengths, cfg,
                live=None, conv_end=None):
    """Shared decode/prefill body: run all blocks, writing new K/V into the
    cache at ``write_pos`` and attending over cache[:lengths].

    x: [B, T, Dm] embedded inputs. Returns (x_out, new_cache).  The
    contiguous cache keeps every position of every layer; a window
    layer masks (dense) or skips (flash) what lies behind its window.
    ``live`` [B, T]: rows that are real tokens (:func:`_moe_ffn`).
    Under latent attention the cache is the latent one
    (:func:`init_kv_cache`) and a layer writes one row a token.  With
    conv layers it is ``{"kv", "conv"}``: a conv layer's convolution
    reaches back into zeros where the call starts at position 0 and into
    the cached window otherwise, and the window kept is the one after
    the first ``conv_end`` rows (default: all of them)."""
    n_rep = cfg.n_heads // cfg.n_kv_heads
    conv = bool(cfg.conv_layers)
    new_cache = cache["kv"] if conv else cache
    state = cache["conv"] if conv else None
    # layer -> index on the K/V layer axis, and on the windows' axis
    kv_at = {n: k for k, n in enumerate(cfg.attn_layers)}
    conv_at = {n: k for k, n in enumerate(cfg.conv_layers)}
    conv_end = x.shape[1] if conv_end is None else conv_end

    def conv_fn(h, c, layer):
        nonlocal state
        prev = (jnp.zeros_like(state[c]) if isinstance(write_pos, int)
                and write_pos == 0 else state[c])
        y, rows = conv_mix(_conv_in(layer, h), prev, layer["conv_w"])
        state = state.at[c].set(lax.dynamic_slice_in_dim(
            rows, conv_end, cfg.conv_len - 1, axis=1).astype(state.dtype))
        return y

    def latent_attn_fn(q, latent, _, i, layer):
        """The latent kind: the step's rows land in the cache; a prefill
        from position 0 at tileable lengths expands its own latents and
        runs the flash kernel at the expanded head sizes, everything
        else attends the cache absorbed and dense."""
        nonlocal new_cache
        with jax.named_scope("attn.kv_write"):
            new_cache = new_cache.at[i].set(
                lax.dynamic_update_slice_in_dim(
                    new_cache[i], latent.astype(new_cache.dtype),
                    write_pos, axis=1))
        pf_bq, pf_bk = _flash_blocks(q.shape[1], cfg)
        if (cfg.attn_impl == "pallas" and q.shape[1] > 1
                and pf_bq is not None and pf_bk is not None
                and isinstance(write_pos, int) and write_pos == 0):
            from tpuserver.ops import flash_attention

            k, v = _mla_expand(layer, latent, cfg)
            with jax.named_scope("attn.kernel"):
                return flash_attention(
                    q, k, v, causal=True, block_q=pf_bq, block_k=pf_bk,
                    scale=mla_softmax_scale(cfg.mla))
        return _mla_attend_cached(layer, q, new_cache[i], positions,
                                  lengths, cfg)

    for i, layer in enumerate(params["layers"]):
        window = cfg.layer_window(i)

        def attn_fn(q, k, v, i=kv_at.get(i), window=window):
            nonlocal new_cache
            with jax.named_scope("attn.kv_write"):
                new_cache = new_cache.at[i, 0].set(
                    lax.dynamic_update_slice_in_dim(
                        new_cache[i, 0], _lanes(k, cfg).astype(
                            new_cache.dtype),
                        write_pos, axis=1,
                    )
                )
                new_cache = new_cache.at[i, 1].set(
                    lax.dynamic_update_slice_in_dim(
                        new_cache[i, 1], _lanes(v, cfg).astype(
                            new_cache.dtype),
                        write_pos, axis=1,
                    )
                )
            with jax.named_scope("attn.kernel"):
                max_seq = new_cache.shape[3]
                pallas_block = next(
                    (b for b in (256, 128) if max_seq % b == 0), None
                )
                impl = cfg.decode_impl
                if impl == "auto" and q.shape[1] == 1:
                    impl = _select_decode_impl(max_seq, lengths)
                if (
                    impl == "pallas"
                    and q.shape[1] == 1
                    and pallas_block is not None
                    and not window
                ):
                    # the serving hot op: hand-tiled single-query decode
                    # attention (GQA expansion stays in VMEM, dead cache
                    # tail blocks never stream from HBM).  Equivalent mask:
                    # with q_pos == lengths-1, "k > q_pos" == "k >= lengths".
                    # max_seq without a tileable block falls through to the
                    # dense path (like the prefill gate above) instead of
                    # erroring at trace time.
                    from tpuserver.ops import decode_attention

                    out = decode_attention(
                        _lanes(q[:, 0], cfg),
                        new_cache[i, 0],
                        new_cache[i, 1],
                        jnp.full((q.shape[0],), lengths, jnp.int32),
                        block_k=pallas_block, scale=_lanes_scale(cfg),
                    )
                    return _lanes_cut(out[:, None], cfg)
                pf_bq, pf_bk = _flash_blocks(q.shape[1], cfg)
                if (
                    cfg.attn_impl == "pallas"
                    and q.shape[1] > 1
                    and pf_bq is not None
                    and pf_bk is not None
                    and isinstance(write_pos, int)
                    and write_pos == 0
                ):
                    # prefill from position 0: the cached attention is
                    # exactly causal self-attention over the prompt, so the
                    # flash kernel applies (K/V still land in the cache via
                    # the updates above).  Only MXU-tileable lengths — the
                    # TPU lowering needs (8, 128)-aligned blocks, so odd
                    # prompt lengths take the dense path.
                    from tpuserver.ops import flash_attention

                    return flash_attention(
                        q, _expand_kv(k, n_rep), _expand_kv(v, n_rep),
                        causal=True, block_q=pf_bq, block_k=pf_bk,
                        window=window or None,
                        block_causal=cfg.block_len or None,
                    )
                return _lanes_cut(_attend_cached(
                    _lanes(q, cfg), new_cache[i, 0], new_cache[i, 1],
                    positions, lengths, n_rep, window=window,
                    block=cfg.block_len, scale=_lanes_scale(cfg),
                ), cfg)

        if cfg.mla is not None:
            attn_fn = functools.partial(latent_attn_fn, i=i, layer=layer)
        if cfg.layer_conv(i):
            attn_fn = functools.partial(conv_fn, c=conv_at[i], layer=layer)
        x = _block(layer, x, positions, cfg, attn_fn, layer=i, live=live)
    if conv:
        return x, {"kv": new_cache, "conv": state}
    return x, new_cache


def _attend_cached(q, cache_k, cache_v, q_pos, length, n_rep, window=0,
                   block=0, scale=None):
    """q: [B, Tq, H, D] against cache [B, S, Hkv, D].

    Masks cache positions >= ``length`` (a scalar, or a per-row [B]
    vector when the continuous-batching step decodes rows at different
    sequence positions) and (causally) > the query's own global position
    ``q_pos`` [B, Tq]; with ``window``, also those at or beyond
    ``window`` positions behind the query.  With ``block`` the causal
    bound is the end of the query's own block of that many positions.
    Scores are divided by the root of the head size, or multiplied by
    ``scale`` where one is given; the values' head size may differ from
    the keys'."""
    k = _expand_kv(cache_k, n_rep)
    v = _expand_kv(cache_v, n_rep)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
        preferred_element_type=jnp.float32,
    )
    s = s / np.sqrt(q.shape[-1]) if scale is None else s * scale
    k_idx = jnp.arange(k.shape[1])[None, None, None, :]
    if getattr(length, "ndim", 0):
        length = length.reshape(-1, 1, 1, 1)  # per-row valid prefixes
    last = q_pos[:, None, :, None]
    if block:
        last = (last // block + 1) * block - 1
    mask = (k_idx >= length) | (k_idx > last)
    if window:
        mask = mask | (k_idx <= q_pos[:, None, :, None] - window)
    s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_step(params, cache, tokens, pos, cfg):
    """One token of autoregressive decode.

    tokens: [B] int32; pos: scalar int32 (current position, same for batch).
    Returns (logits [B, vocab] fp32, updated cache).
    """
    B = tokens.shape[0]
    positions = jnp.full((B, 1), pos)
    x = _embed_rows(params, tokens, cfg)[:, None, :]  # [B, 1, Dm]
    x, new_cache = _run_cached(
        params, cache, x, positions, pos, pos + 1, cfg
    )
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm"], cfg.norm_eps)
        logits = _head(params, x[:, 0, :], cfg)
    return logits, new_cache


def prefill(params, cache, tokens, cfg):
    """Bulk-run the prompt through the cache; returns (last logits, cache).

    tokens: [B, T].  One batched pass — the [T, T] attention stays
    MXU-shaped and K/V blocks land in the cache with a single
    dynamic_update_slice per layer (not T sequential steps)."""
    B, T = tokens.shape
    positions = jnp.tile(jnp.arange(T)[None, :], (B, 1))
    x = _embed_rows(params, tokens, cfg)
    x, new_cache = _run_cached(params, cache, x, positions, 0, T, cfg)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm"], cfg.norm_eps)
        logits = _head(params, x[:, -1, :], cfg)
    return logits, new_cache


def decode_chunk(params, cache, logits, pos, cfg, chunk):
    """Greedy-decode ``chunk`` tokens in ONE device dispatch.

    A per-token dispatch pays the host's dispatch+fetch cost once per
    token; scanning a fixed chunk of argmax+decode_step pairs inside one
    jitted call amortizes it over ``chunk`` tokens.  Greedy
    sampling keeps the result bit-identical to per-token decode.

    logits: [B, vocab] for the NEXT position (from prefill or the prior
    chunk).  Returns (tokens [chunk, B], logprobs [chunk, B],
    next_logits, cache); positions pos..pos+chunk-1 are written.
    """

    def body(carry, _):
        logits, cache, pos = carry
        with jax.named_scope("sample"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok_logp = jnp.take_along_axis(
                logp, token[:, None], axis=-1)[:, 0]
        next_logits, cache = decode_step(params, cache, token, pos, cfg)
        return (next_logits, cache, pos + 1), (token, tok_logp)

    (next_logits, cache, _), (tokens, logps) = lax.scan(
        body, (logits, cache, pos), None, length=chunk
    )
    return tokens, logps, next_logits, cache


# -- continuous batching (the slotted decode step) ---------------------------


def prefill_bucket(cfg, max_seq, true_len):
    """The padded length the scheduler should prefill a ``true_len``
    prompt at: the next power of two (min 8, capped at ``max_seq``) —
    UNLESS padding would change which prefill attention path runs.

    With ``attn_impl="pallas"`` the flash kernel engages only at
    tileable lengths; padding a dense-length prompt to a tileable bucket
    (or changing the tile pair) would alter the accumulation order of
    the admission prefill vs the single-stream path's exact-length
    prefill, and a near-tie in the first token's logits could flip the
    greedy argmax — breaking the token-identity contract.  Such lengths
    compile exactly instead (the pre-bucketing behavior); everything on
    the dense path buckets freely."""
    bucket = 8
    while bucket < true_len:
        bucket <<= 1
    bucket = min(bucket, max_seq)
    if bucket == true_len or cfg.attn_impl != "pallas":
        return bucket

    def dense(T):
        return None in _flash_blocks(T, cfg)

    return bucket if dense(true_len) and dense(bucket) else true_len


def prefill_to_length(params, cache, tokens, true_len, cfg):
    """Prefill a PADDED prompt, returning the logits at ``true_len - 1``.

    The admission prefill compiles one executable per distinct prompt
    length; under continuous batching every novel length would stall
    ALL in-flight streams for a full model compile.  Padding prompts to
    a few fixed buckets bounds the compile set — and causal attention
    makes the result exact: position ``true_len - 1`` attends only
    positions <= itself, so the padding rows (garbage K/V written at
    ``true_len..T-1``, later masked by the slot's length and overwritten
    by decode steps) cannot influence the returned logits.
    """
    B, T = tokens.shape
    positions = jnp.tile(jnp.arange(T)[None, :], (B, 1))
    x = _embed_rows(params, tokens, cfg)
    # padding rows route nowhere: only a routed layer reads this; a
    # conv layer keeps the window at true_len, never at the bucket's end
    live = positions < true_len if cfg.ffn_types else None
    x, new_cache = _run_cached(params, cache, x, positions, 0, T, cfg,
                               live=live, conv_end=true_len)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm"], cfg.norm_eps)
        last = lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)[:, 0]
        logits = _head(params, last, cfg)
    return logits, new_cache


def batched_decode_step(params, cache, tokens, positions, cfg):
    """One decode token per cache SLOT at per-slot positions — the
    compute heart of the continuous-batching scheduler
    (``tpuserver.scheduler``).

    Where ``decode_step`` advances one sequence at a shared scalar
    ``pos``, here every cache row is an independent in-flight generation:
    ``tokens`` [S] int32 are the rows' next input tokens and ``positions``
    [S] int32 their current write positions.  Each row's K/V lands at its
    own position (a scatter instead of a dynamic_update_slice) and
    attention masks each row to its own valid prefix
    (``positions + 1``).  Rows holding no live request use the sentinel
    position ``max_seq`` — out of bounds, so their cache writes DROP
    (mode="drop") and a finished-but-still-in-flight slot's parked rows
    are never corrupted.

    Returns (logits [S, vocab] fp32, new cache).  Per-row math is
    identical to ``decode_step``'s, which is what makes greedy tokens
    from N interleaved slots equal to N sequential single-stream runs.
    """
    if cfg.mla is not None or cfg.conv_layers or cfg.kv_lanes:
        raise UnsupportedArchitecture(
            "the slotted decode step reads K and V rows of every layer; "
            "latent attention and conv layers are served over the paged "
            "pool (paged_batched_decode_step)")
    S = tokens.shape[0]
    max_seq = cache.shape[3]
    q_pos = positions[:, None]  # [S, 1]
    # inert rows (sentinel position max_seq) clamp to length 1, not
    # max_seq: the decode-attention kernel skips blocks past each row's
    # valid prefix, and an empty slot must not stream its whole dead
    # cache from HBM every step (length 0 would NaN the softmax; the
    # one garbage position attended is discarded with the row's output)
    lengths = jnp.where(positions >= max_seq, 1, positions + 1)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    rows = jnp.arange(S)
    x = _embed_rows(params, tokens, cfg)[:, None, :]  # [S, 1, Dm]
    new_cache = cache
    pallas_block = _decode_kernel_block(cfg, max_seq)

    live = (positions < max_seq)[:, None] if cfg.ffn_types else None
    for i, layer in enumerate(params["layers"]):
        window = cfg.layer_window(i)

        def attn_fn(q, k, v, i=i, window=window):
            nonlocal new_cache
            with jax.named_scope("attn.kv_write"):
                new_cache = new_cache.at[i, 0, rows, positions].set(
                    k[:, 0].astype(new_cache.dtype), mode="drop"
                )
                new_cache = new_cache.at[i, 1, rows, positions].set(
                    v[:, 0].astype(new_cache.dtype), mode="drop"
                )
            with jax.named_scope("attn.kernel"):
                if pallas_block is not None and not window:
                    # the decode-attention kernel already takes per-row
                    # lengths — continuous batching is its natural shape
                    from tpuserver.ops import decode_attention

                    out = decode_attention(
                        q[:, 0],
                        new_cache[i, 0],
                        new_cache[i, 1],
                        lengths.astype(jnp.int32),
                        block_k=pallas_block,
                    )
                    return out[:, None]
                return _attend_cached(
                    q, new_cache[i, 0], new_cache[i, 1], q_pos, lengths,
                    n_rep, window=window,
                )

        x = _block(layer, x, q_pos, cfg, attn_fn, layer=i, live=live)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm"], cfg.norm_eps)
        logits = _head(params, x[:, 0, :], cfg)
    return logits, new_cache


def scheduler_step(params, cache, logits_all, positions, active,
                   forced, forced_mask, cfg):
    """One continuous-batching iteration over every cache slot, in ONE
    device dispatch.

    Each slot's next token is sampled greedily from its ``logits_all``
    row — except slots replaying a resumed prompt, whose ``forced``
    token is taken instead (``forced_mask``); those steps only feed the
    cache, the scheduler emits nothing for them.  The batched decode
    step then writes every active row's K/V at its own position.
    Inactive rows keep their previous logits so a dead slot's state
    stays inert until an admission overwrites it.

    Returns (tokens [S], logprobs [S], next logits [S, vocab], cache).
    """
    with jax.named_scope("sample"):
        logp = jax.nn.log_softmax(logits_all, axis=-1)
        greedy = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
        tokens = jnp.where(forced_mask, forced, greedy)
        tok_logp = jnp.take_along_axis(
            logp, tokens[:, None], axis=-1)[:, 0]
    new_logits, new_cache = batched_decode_step(
        params, cache, tokens, positions, cfg
    )
    new_logits = jnp.where(active[:, None], new_logits, logits_all)
    return tokens, tok_logp, new_logits, new_cache


def scheduler_admit(cache, logits_all, slot_cache, slot_logits, slot):
    """Admit one prefilled request into the slotted arrays: write its
    [n_layers, 2, 1, S, Hkv, hd] cache into batch row ``slot`` and its
    next-token logits [1, vocab] into the matching ``logits_all`` row.
    ``slot`` is a traced scalar — one compile covers every slot."""
    cache = lax.dynamic_update_slice_in_dim(
        cache, slot_cache.astype(cache.dtype), slot, axis=2
    )
    logits_all = lax.dynamic_update_slice_in_dim(
        logits_all, slot_logits.astype(logits_all.dtype), slot, axis=0
    )
    return cache, logits_all


def scheduler_extract(cache, slot):
    """One slot's cache rows as a fresh [n_layers, 2, 1, S, Hkv, hd]
    array — the same shape the single-stream path parks in an XLA shm
    region, so park/resume interoperates across both modes."""
    return lax.dynamic_slice_in_dim(cache, slot, 1, axis=2)


# -- paged KV (block-granular cache pool) ------------------------------------


def init_paged_kv_cache(cfg, n_pages, page_size, dtype=None):
    """The page pool — the paged form of :func:`init_kv_cache`, one
    page class by what a token's row is: a K/V class
    [n_layers, 2, n_pages, page_size, n_kv_heads, kv_width], or under
    latent attention a latent class [n_layers, n_pages, page_size, row]
    (one ``[c_kv ; k_pe ; padding]`` row a token, no K/V pair, no head
    axis).  A sequence's rows live scattered across pages named by its
    page table; page id ``n_pages`` is the out-of-bounds scatter
    sentinel (writes drop).  The K/V layer axis runs over the attention
    layers alone: a conv layer holds no pages."""
    dtype = dtype or cfg.dtype
    if cfg.mla is not None:
        return jnp.zeros((cfg.n_layers, n_pages, page_size, cfg.mla.row),
                         dtype)
    return jnp.zeros(
        (len(cfg.attn_layers), 2, n_pages, page_size, cfg.n_kv_heads,
         cfg.kv_width),
        dtype,
    )


def pool_geometry(pool):
    """``(n_pages, page_size)`` of one page class, K/V or latent
    (:func:`init_paged_kv_cache`)."""
    return pool.shape[-3:-1] if pool.ndim == 4 else pool.shape[2:4]


def window_ring_pages(cfg, max_seq, page_size):
    """Pages a sequence holds AT MOST in the window class: one window
    plus one kernel block (a query near a block's end still sees the
    tail of the block ``window`` behind it), in whole blocks — or all of
    ``max_seq`` where that is less.  A window row's page table has this
    many entries and is a ring: logical page ``p`` is entry ``p %
    ring``."""
    _, block = paged_decode_path(cfg, max_seq, page_size)
    if block is None:
        raise UnsupportedArchitecture(
            "window layers are served by the paged decode kernel only: "
            "max_seq {} needs a 128-multiple block of whole {}-token "
            "pages".format(max_seq, page_size))
    ring_blocks = min(-(-cfg.window // block) + 1, max_seq // block)
    return ring_blocks * (block // page_size)


def init_paged_kv_classes(cfg, n_pages, n_window_pages, page_size,
                          dtype=None):
    """The page pool of a configuration with window layers, in two
    classes: ``{"full": [L_full, 2, n_pages, ...], "window": [L_window,
    2, n_window_pages, ...]}``.  The full class keeps every token of a
    sequence; the window class keeps one ring of pages a sequence
    (:func:`window_ring_pages`), whose pages are given back as the
    window moves past them."""
    dtype = dtype or cfg.dtype
    tail = (page_size, cfg.n_kv_heads, cfg.head_dim)
    return {
        "full": jnp.zeros(
            (len(cfg.full_layers), 2, n_pages) + tail, dtype),
        "window": jnp.zeros(
            (len(cfg.window_layers), 2, n_window_pages) + tail, dtype),
    }


def paged_decode_path(cfg, max_seq, page_size):
    """Which decode attention :func:`paged_batched_decode_step` traces
    for this geometry, and the kernel's K/V block (None without a
    kernel).  A fact of the build, read from what the shapes allow:

    - ``"paged_kernel"``: the Pallas kernel reads the pool in place
      through the page table (``ops.paged_decode_attention``) — the
      decode kernel is chosen, ``max_seq`` has a 128-multiple block and
      the block holds whole pages;
    - ``"gather_kernel"``: a block, but not of whole pages — the pages
      gather into the contiguous view and ``ops.decode_attention`` runs
      over it;
    - ``"gather_dense"``: no kernel (``decode_impl`` resolves to
      ``"xla"``, or no block divides ``max_seq``) — the gather, then
      dense XLA attention.
    """
    block = _decode_kernel_block(cfg, max_seq)
    if block is None:
        return "gather_dense", None
    return ("paged_kernel" if block % page_size == 0
            else "gather_kernel"), block


def paged_batched_decode_step(params, pages, tokens, page_tables,
                              positions, cfg):
    """:func:`batched_decode_step` over a paged pool: one decode token
    per sequence row, with each row's KV scattered across the physical
    pages its ``page_tables`` row names.

    ``pages`` is the pool from :func:`init_paged_kv_cache` (a K/V class,
    or a latent class, whose layers attend through ``latent_attn_fn``
    below), or with conv layers ``{"kv": pool, "conv": windows}``
    (:func:`init_conv_state`, a row a slot: a live row's windows move
    on by one row a step, the rest keep theirs); ``page_tables`` [S, pages_per_seq] int32 maps each row's logical
    pages to physical ids (entries may be the sentinel ``n_pages`` for
    unreserved logical pages — they are never read below the row's
    valid length and never written).

    How a row's pages reach the attention is :func:`paged_decode_path`'s
    choice, made from the shapes at trace time.  The served path
    (``"paged_kernel"``: every real preset) hands the kernel the pool
    and the page table, and the kernel copies each row's LIVE pages into
    VMEM itself (``ops.paged_decode_attention``): nothing the size of
    the pool is read or written in HBM, and scope ``attn.kernel`` holds
    the page reads.  The fallback paths (``tiny`` at a ``max_seq`` with
    no 128-multiple block, ``decode_impl="xla"``) gather each layer's
    pages into the contiguous [S, max_seq] view the slotted step attends
    over (scope ``attn.page_gather``: two copies the size of a layer's
    pool, fine at test sizes only).  Either way the attention sees
    identical values in identical order, so greedy tokens are bitwise
    equal to the contiguous step's (A/B-pinned in tests/test_paged_kv.py
    on both paths).

    New K/V writes land at (``page_tables[s, positions[s] //
    page_size]``, ``positions[s] % page_size``); rows at the sentinel
    position ``max_seq`` drop their writes, exactly like the slotted
    step's out-of-bounds rows.
    """
    S = tokens.shape[0]
    # conv layers: the pool beside the windows, {"kv", "conv"}
    conv = bool(cfg.conv_layers)
    state = pages["conv"] if conv else None
    pages = pages["kv"] if conv else pages
    # one page class (the pool is one array, the plain configurations)
    # or two ({"full", "window"} pools and tables: window layers)
    classes = isinstance(pages, dict)
    pools = dict(pages) if classes else {"full": pages}
    tables = page_tables if classes else {"full": page_tables}
    n_pages, page = pool_geometry(pools["full"])
    ppseq = tables["full"].shape[1]
    max_seq = ppseq * page
    # inert rows clamp to length 1 (see batched_decode_step)
    lengths = jnp.where(positions >= max_seq, 1, positions + 1)
    logical = jnp.clip(positions // page, 0, ppseq - 1)
    phys = jnp.take_along_axis(
        tables["full"], logical[:, None], axis=1)[:, 0]
    # sentinel rows scatter out of bounds -> dropped (mode="drop")
    phys = jnp.where(positions >= max_seq, n_pages, phys)
    offs = positions % page
    q_pos = positions[:, None]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    x = _embed_rows(params, tokens, cfg)[:, None, :]  # [S, 1, Dm]
    # unreserved logical pages clip to a valid (arbitrary) physical
    # page: everything they contribute sits beyond the row's valid
    # length and is masked.  The gather would clamp by itself; the
    # kernel's page copies would not (a DMA does not drop a wild index)
    tbl = {"full": jnp.clip(tables["full"], 0, n_pages - 1)}
    write = {"full": phys}
    path, pallas_block = paged_decode_path(cfg, max_seq, page)
    starts = None
    if classes:
        # the window class: a row's table is a ring over logical pages
        # (ops.paged_decode_attention), the new token lands in entry
        # (position // page) % ring, and the kernel starts at the first
        # position still inside the window
        n_wpages = pools["window"].shape[2]
        ring = tables["window"].shape[1]
        phys_w = jnp.take_along_axis(
            tables["window"], ((positions // page) % ring)[:, None],
            axis=1)[:, 0]
        write["window"] = jnp.where(positions >= max_seq, n_wpages, phys_w)
        tbl["window"] = jnp.clip(tables["window"], 0, n_wpages - 1)
        starts = jnp.maximum(lengths - cfg.window, 0).astype(jnp.int32)
    live = (positions < max_seq)[:, None] if cfg.ffn_types else None
    stats = [] if cfg.ffn_types else None
    slot_of = {i: ("window", n) for n, i in enumerate(cfg.window_layers)}
    slot_of.update((i, ("full", n)) for n, i in enumerate(cfg.full_layers))
    conv_at = {n: k for k, n in enumerate(cfg.conv_layers)}
    # rows that hold a request: only theirs move their windows on
    row_live = positions < max_seq if conv else None

    def conv_fn(h, c, layer):
        """A conv layer: the short convolution over each row's saved
        window and its new ``u``; a live row's window moves on by that
        ``u``, every other row's stays as it was."""
        nonlocal state
        y, rows = conv_mix(_conv_in(layer, h), state[c], layer["conv_w"])
        state = state.at[c].set(jnp.where(
            row_live[:, None, None], rows[:, 1:].astype(state.dtype),
            state[c]))
        return y

    def latent_attn_fn(q, latent, _, i, layer):
        """The latent class: the row's new latent lands in its page,
        then the absorbed form: the query carried into the latent space,
        the kernel over the row's pages read ONCE as key and as value
        (``ops.latent_decode_attention``; the gather and dense attention
        where no kernel serves), the value up-projection after it."""
        pool = pools["full"]
        with jax.named_scope("attn.kv_write"):
            pool = pools["full"] = pool.at[i, write["full"], offs].set(
                latent[:, 0].astype(pool.dtype), mode="drop")
        if path != "paged_kernel":
            with jax.named_scope("attn.page_gather"):
                rows = pool[i][tbl["full"]].reshape(S, max_seq, -1)
            return _mla_attend_cached(layer, q, rows, q_pos, lengths, cfg)
        from tpuserver.ops import latent_decode_attention

        q_lat = _mla_absorb_q(layer, q, cfg)
        with jax.named_scope("attn.kernel"):
            u = latent_decode_attention(
                q_lat[:, 0], pool, i, tbl["full"],
                lengths.astype(jnp.int32), d_v=cfg.mla.kv_lora,
                scale=mla_softmax_scale(cfg.mla), block_k=pallas_block)
        return _mla_absorb_out(layer, u[:, None], cfg)

    for i, layer in enumerate(params["layers"]):
        def attn_fn(q, k, v, i=i):
            cls, li = slot_of[i]
            pool, at = pools[cls], write[cls]
            with jax.named_scope("attn.kv_write"):
                pool = pool.at[li, 0, at, offs].set(
                    _lanes(k[:, 0], cfg).astype(pool.dtype), mode="drop"
                )
                pool = pool.at[li, 1, at, offs].set(
                    _lanes(v[:, 0], cfg).astype(pool.dtype), mode="drop"
                )
                pools[cls] = pool
            if path == "paged_kernel":
                with jax.named_scope(
                        "attn.window" if cls == "window" else "attn.kernel"):
                    from tpuserver.ops import paged_decode_attention

                    out = paged_decode_attention(
                        _lanes(q[:, 0], cfg), pool, li, tbl[cls],
                        lengths.astype(jnp.int32), block_k=pallas_block,
                        starts=starts if cls == "window" else None,
                        scale=_lanes_scale(cfg),
                    )
                    return _lanes_cut(out[:, None], cfg)
            with jax.named_scope("attn.page_gather"):
                tail = pool.shape[4:]
                k_seq = pool[li, 0][tbl[cls]].reshape(S, max_seq, *tail)
                v_seq = pool[li, 1][tbl[cls]].reshape(S, max_seq, *tail)
            with jax.named_scope("attn.kernel"):
                if path == "gather_kernel":
                    # the gathered view is a standard contiguous cache:
                    # the decode-attention kernel applies unchanged
                    from tpuserver.ops import decode_attention

                    out = decode_attention(
                        _lanes(q[:, 0], cfg), k_seq, v_seq,
                        lengths.astype(jnp.int32), block_k=pallas_block,
                        scale=_lanes_scale(cfg),
                    )
                    return _lanes_cut(out[:, None], cfg)
                return _lanes_cut(_attend_cached(
                    _lanes(q, cfg), k_seq, v_seq, q_pos, lengths, n_rep,
                    scale=_lanes_scale(cfg)), cfg)

        if cfg.mla is not None:
            attn_fn = functools.partial(latent_attn_fn, i=i, layer=layer)
        if cfg.layer_conv(i):
            attn_fn = functools.partial(conv_fn, c=conv_at[i], layer=layer)
        x = _block(layer, x, q_pos, cfg, attn_fn, layer=i, live=live,
                   moe_stats=stats)
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm"], cfg.norm_eps)
        logits = _head(params, x[:, 0, :], cfg)
    new_pages = pools if classes else pools["full"]
    if conv:
        new_pages = {"kv": new_pages, "conv": state}
    if stats is not None:
        # what the routed layers did this step, for the host's counters:
        # [layer-steps, pairs held here, distinct held experts hit]
        return logits, new_pages, jnp.stack([
            jnp.int32(len(stats)), sum(s[0] for s in stats),
            sum(s[1] for s in stats)])
    return logits, new_pages


def paged_scheduler_step(params, pages, logits_all, page_tables,
                         positions, active, forced, forced_mask, cfg):
    """:func:`scheduler_step` on the paged pool: greedy-or-forced
    token per row, then one :func:`paged_batched_decode_step`.  Same
    sampling math as the slotted form — the page indirection changes
    where K/V bytes live, never what they are."""
    with jax.named_scope("sample"):
        logp = jax.nn.log_softmax(logits_all, axis=-1)
        greedy = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
        tokens = jnp.where(forced_mask, forced, greedy)
        tok_logp = jnp.take_along_axis(
            logp, tokens[:, None], axis=-1)[:, 0]
    new_logits, new_pages, *moe = paged_batched_decode_step(
        params, pages, tokens, page_tables, positions, cfg
    )
    new_logits = jnp.where(active[:, None], new_logits, logits_all)
    # a routed configuration's step also returns its routing counts
    return (tokens, tok_logp, new_logits, new_pages, *moe)


# -- generation by diffusion over blocks --------------------------------------


def init_block_state(cfg, rows):
    """What a block step carries to the next, a row a slot: the block's
    ``tokens`` [rows, B] (``mask_id`` where still masked), its ``masked``
    bits, the block's first position ``start`` and the number ``pass``
    of denoise passes the block has had."""
    b = cfg.block_len
    return {
        "tokens": jnp.full((rows, b), cfg.mask_id, jnp.int32),
        "masked": jnp.ones((rows, b), bool),
        "start": jnp.zeros((rows,), jnp.int32),
        "pass": jnp.zeros((rows,), jnp.int32),
    }


def prefill_blocks(params, cache, tokens, true_len, cfg):
    """Prefill a PADDED prompt of a block configuration: the prompt's
    whole blocks (its first ``true_len // B * B`` tokens) write their
    K/V under the block-causal mask and nothing is read of them (no
    head); the ``true_len % B`` tokens left over open the first block as
    given tokens.  Returns ``(state, cache)``, the state
    (:func:`init_block_state`) of one row.

    A query sees only its own and earlier blocks, so the rows past the
    whole blocks (the rest and the padding: K/V the first pass of the
    first block overwrites or ``lengths`` masks) cannot reach them."""
    b = cfg.block_len
    rows, t = tokens.shape
    positions = jnp.tile(jnp.arange(t)[None, :], (rows, 1))
    aligned = true_len // b * b
    x = _embed_rows(params, tokens, cfg)
    live = positions < aligned if cfg.ffn_types else None
    _, new_cache = _run_cached(params, cache, x, positions, 0, t, cfg,
                               live=live)
    tail = lax.dynamic_slice_in_dim(
        jnp.pad(tokens, ((0, 0), (0, b))), aligned, b, axis=1)
    given = (aligned + jnp.arange(b) < true_len)[None, :]
    state = init_block_state(cfg, rows)
    state.update(
        tokens=jnp.where(given, tail, state["tokens"]),
        masked=state["masked"] & ~given,
        start=state["start"] + aligned)
    return state, new_cache


def unmask_block(logits, masked, n_pass, steps, taus, mask_id):
    """The unmask rule of a denoise pass, a row at a time: logits
    [S, B, V] float32 of the block's positions, ``masked`` [S, B],
    ``n_pass`` [S] the passes the block has had, ``steps`` [S] the
    row's ``denoising_steps`` T (1..B), ``taus`` [S] its confidence
    threshold.  The mask token is never a prediction: its logit is
    taken out first.  ``x0 = argmax``, ``c = softmax(logits)[x0]``; the static
    schedule unmasks ``n = B // T`` (+1 in the first ``B % T`` passes)
    masked positions of highest ``c`` (ties: lowest position); where at
    least ``n`` masked positions have ``c > tau``, all of those instead.
    ``n`` never passes the positions still masked: an unmasked token is
    final.  Returns ``(x0, log c, newly unmasked)``, each [S, B]."""
    b = masked.shape[1]
    logits = logits.at[:, :, mask_id].set(-jnp.inf)
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logc = jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)
    conf = jnp.where(masked, jnp.exp(logc), -jnp.inf)
    t = jnp.clip(steps, 1, b)
    n = b // t + (n_pass < b % t).astype(jnp.int32)
    n = jnp.minimum(n, jnp.sum(masked, axis=1, dtype=jnp.int32))
    at = jnp.arange(b)
    # ahead[s, j, k]: position k is unmasked before position j
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    top = masked & (jnp.sum(ahead, axis=-1, dtype=jnp.int32) < n[:, None])
    high = masked & (conf > taus[:, None])
    enough = jnp.sum(high, axis=1, dtype=jnp.int32) >= n
    return x0, logc, jnp.where(enough[:, None], high, top)


def paged_block_step(params, pages, state, page_tables, steps, taus,
                     active, cfg):
    """One pass of every active row's current block over the paged
    pool, in ONE device dispatch: the step of a configuration with
    ``block_len`` B > 0, as :func:`paged_scheduler_step` is of the rest.

    ``state`` (:func:`init_block_state`) holds each row's block.  A pass
    embeds a block's B positions (the mask token where masked), writes
    their K/V to the block's page slots (a later pass overwrites them,
    the commit last) and attends the ``start + B`` positions of all
    earlier blocks and the block itself with no mask inside it.  What a
    row does in a pass, read on the device from its state:

    - a **denoise** pass (some position masked) unmasks positions by
      :func:`unmask_block`; an unmasked token is final;
    - a row with no position left masked **commits**: the block's final
      tokens run once more, so their K/V is what the cache keeps.  The
      SAME pass is the first denoise pass of the row's next block, all
      masked, at ``start + B``: its queries attend ``start + 2B`` keys,
      the committed K/V among them (written, layer by layer, before the
      kernel reads the pool), while the committing block's queries see
      their own ``start + B`` and never the opening block.  A block so
      costs T passes, not T + 1;
    - where no next block fits (``start + 2B > max_seq``) the pass only
      commits, and the row's state moves on to ``start + B``.

    The two blocks of a committing row are two VIRTUAL rows of one
    forward: rows [0, S) carry each slot's denoise pass (or its lone
    commit), rows [S, 2S) the commit that rides along, under the slot's
    page table both.  The second half of a slot that is not committing
    is inert as an inactive row is (sentinel position, one key attended,
    writes dropped, no expert pair).  Head, logits and the unmask rule
    run over the first S rows only: a commit needs no logits.

    ``steps`` / ``taus`` [S]: the rows' ``denoising_steps`` and
    confidence thresholds; ``active`` [S]: rows that hold a request (the
    rest write nothing, attend one position and keep their state).

    Returns ``(out [S, 2B+4] int32, logc [S, B], new state, new pages,
    routing counts)``; ``out`` is the block the pass denoised after the
    pass, the positions the pass unmasked (0 / 1), that block's
    ``start``, whether the pass committed a block (alone: the block at
    ``start``; riding along: the block before it), the block's pass
    number, and whether the commit rode on the next block's first pass;
    ``logc`` the log-confidence of each position's ``x0`` in this
    pass."""
    b = cfg.block_len
    n_pages, page = pages.shape[2], pages.shape[3]
    ppseq = page_tables.shape[1]
    max_seq = ppseq * page
    held = state["start"]
    commit = ~jnp.any(state["masked"], axis=1)
    # a committing row opens its next block in the same pass
    fused = commit & (held + 2 * b <= max_seq)
    start = jnp.where(fused, held + b, held)
    masked = fused[:, None] | state["masked"]
    n_pass = jnp.where(fused, 0, state["pass"])
    tokens = jnp.where(masked, cfg.mask_id, state["tokens"])
    # two virtual rows a slot: its pass, and the commit riding along
    tokens2 = jnp.concatenate([tokens, state["tokens"]])
    live_row = jnp.concatenate(
        [active & (start + b <= max_seq), active & fused])
    # inert rows: the sentinel position (writes drop), one key attended
    first = jnp.where(live_row, jnp.concatenate([start, held]), max_seq)
    lengths = jnp.where(live_row, first + b, 1).astype(jnp.int32)
    positions = first[:, None] + jnp.arange(b)[None, :]      # [2S, B]
    logical = jnp.clip(first // page, 0, ppseq - 1)
    tables2 = jnp.concatenate([page_tables, page_tables])
    phys = jnp.take_along_axis(tables2, logical[:, None], axis=1)
    phys = jnp.where(live_row[:, None], phys, n_pages)       # [2S, 1]
    # a block lies in one page: page_size is a multiple of B
    offs = (first % page)[:, None] + jnp.arange(b)[None, :]
    tbl = jnp.clip(tables2, 0, n_pages - 1)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    path, pallas_block = paged_decode_path(cfg, max_seq, page)
    with jax.named_scope("diffusion.embed_block"):
        x = _embed_rows(params, tokens2, cfg)                # [2S, B, Dm]
    live = jnp.broadcast_to(live_row[:, None], tokens2.shape)
    stats = []
    pool = pages

    for i, layer in enumerate(params["layers"]):
        def attn_fn(q, k, v, i=i):
            nonlocal pool
            with jax.named_scope("attn.kv_write"):
                pool = pool.at[i, 0, phys, offs].set(
                    k.astype(pool.dtype), mode="drop")
                pool = pool.at[i, 1, phys, offs].set(
                    v.astype(pool.dtype), mode="drop")
            if path == "paged_kernel":
                with jax.named_scope("attn.kernel"):
                    from tpuserver.ops import paged_decode_attention

                    return paged_decode_attention(
                        q, pool, i, tbl, lengths, block_k=pallas_block)
            with jax.named_scope("attn.page_gather"):
                tail = pool.shape[4:]
                k_seq = pool[i, 0][tbl].reshape(-1, max_seq, *tail)
                v_seq = pool[i, 1][tbl].reshape(-1, max_seq, *tail)
            with jax.named_scope("attn.kernel"):
                # every query of the block sees all ``lengths`` keys
                return _attend_cached(
                    q, k_seq, v_seq,
                    jnp.broadcast_to(lengths[:, None] - 1, tokens2.shape),
                    lengths, n_rep)

        x = _block(layer, x, positions, cfg, attn_fn, layer=i, live=live,
                   moe_stats=stats)
    with jax.named_scope("head"):
        x = _rms_norm(x[:tokens.shape[0]], params["norm"], cfg.norm_eps)
        logits = _head(params, x, cfg)
    with jax.named_scope("diffusion.unmask"):
        x0, logc, newly = unmask_block(
            logits, masked, n_pass, steps, taus, cfg.mask_id)
        tokens = jnp.where(newly, x0, tokens)
        out = jnp.concatenate([
            tokens, newly.astype(jnp.int32), start[:, None],
            commit.astype(jnp.int32)[:, None], n_pass[:, None],
            fused.astype(jnp.int32)[:, None]], axis=1)
        # a lone commit moves the row on; every other pass stays with
        # the block it denoised
        alone = commit & ~fused
        nxt = {
            "tokens": jnp.where(alone[:, None], cfg.mask_id, tokens),
            "masked": alone[:, None] | (masked & ~newly),
            "start": jnp.where(alone, start + b, start),
            "pass": jnp.where(alone, 0, n_pass + 1),
        }
        # rows without a request keep their state
        nxt = {k: jnp.where(active.reshape((-1,) + (1,) * (v.ndim - 1)),
                            v, state[k]) for k, v in nxt.items()}
    return out, logc, nxt, pool, jnp.stack([
        jnp.int32(len(stats)), sum(s[0] for s in stats),
        sum(s[1] for s in stats)])


def paged_admit(pages, logits_all, slot_cache, slot_logits, dest_ids,
                slot):
    """Admit one prefilled request into the paged pool: the single-row
    contiguous cache [L, 2, 1, max_seq, Hkv, hd] splits into
    ``pages_per_seq`` logical pages and scatters to the physical ids
    ``dest_ids`` names (the sentinel ``n_pages`` drops a page — shared
    prefix pages already live in the pool and must not be rewritten).
    The row's next-token logits land in ``logits_all`` row ``slot`` (of
    a block configuration: every leaf of the row's block state in that
    of ``logits_all``, :func:`init_block_state`).  A latent class takes
    the latent slot cache [L, 1, max_seq, row] the same way, a row a
    token."""
    ppseq = dest_ids.shape[0]
    if pages.ndim == 4:
        src = slot_cache.reshape(
            slot_cache.shape[0], ppseq, *pages.shape[2:])
        pages = pages.at[:, dest_ids].set(
            src.astype(pages.dtype), mode="drop")
        return pages, _admit_logits(logits_all, slot_logits, slot)
    page = pages.shape[3]
    shape = pages.shape
    if shape[4] % 8:
        # fewer KV heads than the 8 rows of a tile: scattered as it
        # lies, the pool is copied into a layout that tiles pages with
        # heads and back (two copies of the whole pool and as much
        # temporary memory an admission, seen on the v5e at 4 KV heads).
        # Over the [.., page * Hkv, D] view, a bitcast, it is in place.
        pages = pages.reshape(*shape[:3], page * shape[4], shape[5])
    src = slot_cache.reshape(
        slot_cache.shape[0], 2, ppseq, *pages.shape[3:]
    )
    pages = pages.at[:, :, dest_ids].set(
        src.astype(pages.dtype), mode="drop"
    ).reshape(shape)
    return pages, _admit_logits(logits_all, slot_logits, slot)


def paged_admit_conv(pages, logits_all, slot_cache, slot_logits, dest_ids,
                     slot):
    """:func:`paged_admit` beside the conv layers' windows: ``pages``
    and ``slot_cache`` are ``{"kv", "conv"}``; the K/V rows scatter to
    their pages and the prefill's windows [n_conv, 1, L-1, D] replace
    the WHOLE of slot ``slot``'s, so that a reused slot never keeps
    anything of the sequence it held before."""
    kv, logits_all = paged_admit(pages["kv"], logits_all, slot_cache["kv"],
                                 slot_logits, dest_ids, slot)
    state = lax.dynamic_update_slice_in_dim(
        pages["conv"], slot_cache["conv"].astype(pages["conv"].dtype), slot,
        axis=1)
    return {"kv": kv, "conv": state}, logits_all


def _admit_logits(logits_all, slot_logits, slot):
    return jax.tree_util.tree_map(
        lambda rows, row: lax.dynamic_update_slice_in_dim(
            rows, row.astype(rows.dtype), slot, axis=0),
        logits_all, slot_logits)


def paged_admit_classes(pages, logits_all, slot_cache, slot_logits,
                        dest_ids, slot, cfg):
    """:func:`paged_admit` into a pool of two page classes.  ``pages``
    and ``dest_ids`` are ``{"full", "window"}``; both ``dest_ids`` are
    [pages_per_seq] physical ids by LOGICAL page (the class's own
    sentinel drops a page).  The full layers' rows of the prefilled
    cache go to the full class whole; of the window layers' rows only
    the logical pages the host still names — the prompt's last window —
    are written, so a prefill of two windows admits one."""
    page = pages["full"].shape[3]
    ppseq = dest_ids["full"].shape[0]
    src = slot_cache.reshape(
        slot_cache.shape[0], 2, ppseq, page, *slot_cache.shape[4:]
    )
    out = {}
    for cls, layers in (("full", cfg.full_layers),
                        ("window", cfg.window_layers)):
        out[cls] = pages[cls].at[:, :, dest_ids[cls]].set(
            src[np.asarray(layers)].astype(pages[cls].dtype), mode="drop"
        )
    logits_all = lax.dynamic_update_slice_in_dim(
        logits_all, slot_logits.astype(logits_all.dtype), slot, axis=0
    )
    return out, logits_all


def paged_gather(pages, page_ids):
    """One sequence's pages of a K/V class as a fresh single-row
    contiguous cache [L, 2, 1, max_seq, Hkv, hd] — the park/extract shape (so paged
    park/resume interoperates with the single-stream path) and the
    prefix-restore source a shared-prefix admission prefills on top
    of.  Sentinel/unreserved ids gather as zeros."""
    n_pages, page = pages.shape[2], pages.shape[3]
    ppseq = page_ids.shape[0]
    valid = (page_ids >= 0) & (page_ids < n_pages)
    ids = jnp.clip(page_ids, 0, n_pages - 1)
    rows = pages[:, :, ids]  # [L, 2, ppseq, page, Hkv, hd]
    rows = jnp.where(
        valid[None, None, :, None, None, None], rows,
        jnp.zeros((), rows.dtype),
    )
    return rows.reshape(
        pages.shape[0], 2, 1, ppseq * page, *pages.shape[4:]
    )


def prefill_span(params, cache, tokens, start, logits_at, cfg):
    """Prefill a token span at positions ``start..start+T-1`` into a
    single-row contiguous cache — the chunked-prefill and
    shared-prefix-suffix building block.

    Generalizes :func:`prefill_to_length`: K/V land at ``write_pos =
    start`` and queries attend the cache's first ``start + T``
    positions under the causal mask, so a span conditioned on an
    already-present prefix (earlier chunks, or a radix-cache restore)
    computes exactly what a from-zero prefill would.  All keys read
    from the cache post-write (the dense cached path), so chunked
    output is bitwise identical to one-shot dense prefill — the
    token-identity contract tests/test_paged_kv.py pins.  The caller
    guarantees ``start + T <= max_seq`` (XLA would silently clamp the
    write start otherwise) and that the flash prefill kernel is not in
    play for this model (``make_scheduler_fns`` gates chunking/sharing
    with ``span_safe`` exactly like :func:`prefill_bucket` gates
    padding).

    Returns the logits at chunk-relative index ``logits_at`` (only
    meaningful on the span containing the prompt's last token) and
    the updated cache."""
    if cfg.conv_layers:
        raise UnsupportedArchitecture(
            "a span prefill does not carry the conv layers' windows from "
            "one span to the next: prompts with conv layers prefill whole")
    B, T = tokens.shape
    positions = start + jnp.tile(jnp.arange(T)[None, :], (B, 1))
    x = _embed_rows(params, tokens, cfg)
    x, new_cache = _run_cached(
        params, cache, x, positions, start, start + T, cfg
    )
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm"], cfg.norm_eps)
        last = lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)[:, 0]
        logits = _head(params, last, cfg)
    return logits, new_cache


def make_scheduler_fns(cfg, max_seq, max_slots, mesh=None, quantized=False,
                       page_size=16, kv_pages=None, kv_window_pages=None):
    """Compiled function bundle for the continuous-batching scheduler,
    over a block-paged KV pool.

    The device cache is a page pool of ``kv_pages`` pages of
    ``page_size`` tokens, a K/V or a latent class by what a token's row
    is (:func:`init_paged_kv_cache`), rather than ``max_slots``
    contiguous rows: a sequence occupies only the pages
    its length spans, page tables map logical to physical pages, and
    the scheduler's host-side allocator/radix tree
    (``tpuserver.paging``) decides who owns what.  ``kv_pages``
    defaults to ``max_slots * max_seq / page_size`` — byte-identical
    capacity to the old slotted cache, which shared prefixes and short
    spans then stretch across MORE concurrent streams.

    Returns a dict of:

    - ``init_cache()`` — the page pool
    - ``init_slot_cache()`` — a single-row contiguous cache for
      prefill-on-admit (scattered into pages by ``admit``)
    - ``init_logits()`` — [max_slots, vocab] fp32 zeros
    - ``prefill(params, slot_cache, tokens, true_len)`` — the one-shot
      admission prefill (:func:`prefill_to_length`)
    - ``prefill_span(params, slot_cache, tokens, start, logits_at)`` —
      the chunked / shared-prefix-suffix prefill
      (:func:`prefill_span`)
    - ``prefill_bucket(true_len)`` — the padded length to use
    - ``step(params, pages, logits, page_tables, positions, active,
      forced, forced_mask)`` — :func:`paged_scheduler_step`, pages and
      logits donated
    - ``admit(pages, logits, slot_cache, slot_logits, dest_ids,
      slot)`` — :func:`paged_admit`, pages and logits donated
    - ``gather(pages, page_ids)`` — :func:`paged_gather`: the park
      copy AND the shared-prefix restore (pages NOT donated)
    - ``page_size`` / ``pages_per_seq`` / ``n_pages`` — the pool
      geometry the scheduler's allocator mirrors
    - ``span_safe`` — whether chunked/shared prefill preserves the
      one-shot kernel choice (False for flash-prefill configs: a
      dense chunk vs a one-shot flash pass could flip a near-tie
      greedy argmax, the same hazard :func:`prefill_bucket` guards,
      so the scheduler falls back to whole-prompt prefill there)
    - ``decode_attention`` — which decode attention ``step`` was
      built with (:func:`paged_decode_path`):
      ``"paged_kernel"``, ``"gather_kernel"`` or ``"gather_dense"``
    - ``window_class`` — None, or for a configuration with window
      layers ``{"window", "ring", "n_pages"}``: the pool is then two
      classes of pages (:func:`init_paged_kv_classes`), ``step`` and
      ``admit`` take ``{"full", "window"}`` tables / destinations, and
      ``gather`` and ``prefill_span`` are ABSENT: what rides on them
      (park / resume / KV export, shared prefixes and chunked
      prefill) the scheduler refuses by name
      (``UnsupportedArchitecture``).  ``kv_window_pages`` bounds the
      window class (default: every slot a full ring).

    The ``step`` of a configuration with routed layers returns a fifth
    result, their ``[layer-steps, held pairs, distinct held experts
    hit]`` of the step.

    A configuration that generates by diffusion over blocks
    (``cfg.block_len`` B > 0) gets the block forms under the same keys:
    ``step`` is :func:`paged_block_step` ``(params, pages, state,
    page_tables, steps, taus, active)``, ``prefill`` is
    :func:`prefill_blocks` (a row's block state in the logits' place),
    ``init_logits`` the state of every slot (:func:`init_block_state`),
    ``block_len`` / ``mask_id`` say so to the scheduler (0 otherwise),
    and ``gather`` / ``prefill_span`` are absent: park, export, shared
    prefixes and chunked prefill do not know blocks yet and the
    scheduler refuses them by name.

    A configuration with conv layers gets, beside a K/V pool over its
    attention layers alone, the windows of its conv layers
    (:func:`init_conv_state`, a row a slot); the pool argument and
    result of ``init_cache``, ``step`` and ``admit`` are then
    ``{"kv": pool, "conv": windows}``, ``init_slot_cache`` and
    ``prefill`` carry a row's windows the same way
    (:func:`paged_admit_conv` writes a slot's whole window), and
    ``conv_state``, the number of conv layers, says so to the scheduler
    (absent otherwise).  ``gather`` / ``prefill_span`` are
    absent and ``span_safe`` is false: park, export and attach copy K/V
    rows alone, and a span prefill does not carry the window, so the
    scheduler refuses the former by name and prefills every prompt
    whole.

    A configuration with latent attention (``cfg.mla``) gets a latent
    page class under the same keys (``init_cache``, ``init_slot_cache``,
    ``step``, ``admit``); ``latent_class`` ``{"width", "row"}`` says so
    to the scheduler (absent otherwise).  ``gather`` / ``prefill_span``
    are absent and ``span_safe`` is false: park, export and attach copy
    K/V rows, and a suffix prefill against cached latents is not written
    yet, so the scheduler refuses the former by name and prefills every
    prompt whole.

    With a ``mesh`` the bundle is the GSPMD form: params
    Megatron-split, the page pool and slot cache kv-head-sharded over
    tp (``cache_spec`` — the page axes are unsharded, so the
    gather/scatter indexing stays collective-free), control vectors
    replicated.
    """
    if mesh is not None and (cfg.n_heads % mesh.shape["tp"]
                             or cfg.n_kv_heads % mesh.shape["tp"]):
        raise ValueError(
            "tp={} must divide n_heads={} and n_kv_heads={}".format(
                mesh.shape["tp"], cfg.n_heads, cfg.n_kv_heads
            )
        )
    page_size = int(page_size)
    if page_size < 1 or max_seq % page_size:
        raise ValueError(
            "page_size must be >= 1 and divide max_seq (got page_size="
            "{}, max_seq={}): the park/extract row shape must stay "
            "[.., max_seq, ..] for single-stream interop".format(
                page_size, max_seq
            )
        )
    pages_per_seq = max_seq // page_size
    n_pages = int(kv_pages) if kv_pages is not None \
        else max_slots * pages_per_seq
    if n_pages < pages_per_seq:
        raise ValueError(
            "kv_pages={} cannot hold even one full-length sequence "
            "({} pages of {} tokens)".format(
                n_pages, pages_per_seq, page_size
            )
        )
    window_class = None
    if mesh is not None or quantized:
        _need_plain(cfg, "tensor-parallel or int8 serving")
    if cfg.mla is not None and (cfg.window_layers or cfg.block_len):
        raise UnsupportedArchitecture(
            "the latent page class is one class of full causal attention "
            "layers: no window layers and no generation over blocks")
    if cfg.block_len and (cfg.window_layers or page_size % cfg.block_len
                          or max_seq % cfg.block_len):
        raise UnsupportedArchitecture(
            "generation over blocks of {} needs full attention layers, "
            "and a page_size ({}) and max_seq ({}) of whole blocks".format(
                cfg.block_len, page_size, max_seq))
    if cfg.conv_layers and (cfg.mla is not None or cfg.window_layers
                            or cfg.block_len):
        raise UnsupportedArchitecture(
            "conv layers are served beside one page class of full "
            "causal attention layers: no window layers, latent "
            "attention or generation over blocks")
    if cfg.window_layers:
        ring = window_ring_pages(cfg, max_seq, page_size)
        n_wpages = (int(kv_window_pages) if kv_window_pages is not None
                    else max_slots * ring)
        if n_wpages < ring:
            raise ValueError(
                "kv_window_pages={} cannot hold even one sequence's "
                "window ({} pages of {} tokens)".format(
                    n_wpages, ring, page_size))
        window_class = {"window": cfg.window, "ring": ring,
                        "n_pages": n_wpages}
    if mesh is None:
        step = jax.jit(
            named_partial(paged_scheduler_step, cfg=cfg),
            donate_argnums=(1, 2),
        )
        admit = jax.jit(paged_admit, donate_argnums=(0, 1))
        gather = jax.jit(paged_gather)
        prefill_fn = jax.jit(named_partial(prefill_to_length, cfg=cfg))
        prefill_span_fn = jax.jit(
            named_partial(prefill_span, cfg=cfg),
        )

        def init_cache():
            return init_paged_kv_cache(cfg, n_pages, page_size)

        if window_class is not None:
            # two page classes: their own pool and admit; what assumes
            # one table a sequence is left out of the bundle
            gather = prefill_span_fn = None
            admit = jax.jit(
                named_partial(paged_admit_classes, cfg=cfg),
                donate_argnums=(0, 1),
            )

            def init_cache():  # noqa: F811
                return init_paged_kv_classes(
                    cfg, n_pages, n_wpages, page_size)

        def init_slot_cache():
            return init_kv_cache(cfg, 1, max_seq)

        def init_logits():
            return jnp.zeros((max_slots, cfg.vocab), jnp.float32)

        if cfg.block_len:
            # a block a row a step: the block forms of step and prefill,
            # the block state in the logits' place
            gather = prefill_span_fn = None
            step = jax.jit(
                named_partial(paged_block_step, cfg=cfg),
                donate_argnums=(1, 2),
            )
            prefill_fn = jax.jit(named_partial(prefill_blocks, cfg=cfg))

            def init_logits():  # noqa: F811
                return init_block_state(cfg, max_slots)

        if cfg.mla is not None:
            # a latent class: what copies K/V rows out of the pool or
            # prefills against cached rows is left out of the bundle
            gather = prefill_span_fn = None

        if cfg.conv_layers:
            # the windows ride beside the pool through step and admit;
            # what copies K/V rows alone, or prefills a span without the
            # window before it, is left out of the bundle
            gather = prefill_span_fn = None
            admit = jax.jit(paged_admit_conv, donate_argnums=(0, 1))

            def init_cache():  # noqa: F811
                return {"kv": init_paged_kv_cache(cfg, n_pages, page_size),
                        "conv": init_conv_state(cfg, max_slots)}

    else:
        param_sh, cache_sh, repl = serving_shardings(
            mesh, cfg, quantized=quantized
        )
        step = jax.jit(
            named_partial(paged_scheduler_step, cfg=cfg),
            in_shardings=(param_sh, cache_sh, repl, repl, repl, repl,
                          repl, repl),
            out_shardings=(repl, repl, repl, cache_sh),
            donate_argnums=(1, 2),
        )
        admit = jax.jit(
            paged_admit,
            in_shardings=(cache_sh, repl, cache_sh, repl, repl, repl),
            out_shardings=(cache_sh, repl),
            donate_argnums=(0, 1),
        )
        gather = jax.jit(
            paged_gather,
            in_shardings=(cache_sh, repl),
            out_shardings=cache_sh,
        )
        prefill_fn = jax.jit(
            named_partial(prefill_to_length, cfg=cfg),
            in_shardings=(param_sh, cache_sh, repl, repl),
            out_shardings=(repl, cache_sh),
        )
        prefill_span_fn = jax.jit(
            named_partial(prefill_span, cfg=cfg),
            in_shardings=(param_sh, cache_sh, repl, repl, repl),
            out_shardings=(repl, cache_sh),
        )

        def init_cache():
            return jax.device_put(
                init_paged_kv_cache(cfg, n_pages, page_size), cache_sh
            )

        def init_slot_cache():
            return jax.device_put(init_kv_cache(cfg, 1, max_seq), cache_sh)

        def init_logits():
            return jax.device_put(
                jnp.zeros((max_slots, cfg.vocab), jnp.float32), repl
            )

    fns = {
        "init_cache": init_cache,
        "init_slot_cache": init_slot_cache,
        "init_logits": init_logits,
        "prefill": prefill_fn,
        "prefill_span": prefill_span_fn,
        "prefill_bucket": functools.partial(prefill_bucket, cfg, max_seq),
        "flash_pairs": functools.partial(flash_prefill_pairs, cfg),
        "step": step,
        "admit": admit,
        "gather": gather,
        "page_size": page_size,
        "pages_per_seq": pages_per_seq,
        "n_pages": n_pages,
        "span_safe": (cfg.attn_impl != "pallas" and window_class is None
                      and not cfg.block_len and cfg.mla is None
                      and not cfg.conv_layers),
        "block_len": cfg.block_len,
        "mask_id": cfg.mask_id,
        "decode_attention": paged_decode_path(cfg, max_seq, page_size)[0],
        "window_class": window_class,
        "latent_class": (None if cfg.mla is None else
                         {"width": cfg.mla.width, "row": cfg.mla.row}),
        "conv_state": len(cfg.conv_layers) or None,
    }
    # what a two-class pool cannot serve is absent, not None
    return {k: v for k, v in fns.items()
            if v is not None or k == "window_class"}


# -- tensor-parallel serving (decode over a tp mesh) -------------------------


def cache_spec(cfg):
    """PartitionSpec of a K/V cache [n_layers, 2, B, S, n_kv_heads, hd]
    (the plain block's: a latent cache has no head axis and is never
    sharded here): kv heads sharded over tp — each tp shard owns its heads' cache rows,
    so cache reads/writes during decode are collective-free."""
    return P(None, None, None, None, "tp", None)


def make_tp_serving(mesh, cfg, chunk=8, donate=True, quantized=False):
    """Tensor-parallel prefill + chunked decode over a mesh's ``tp`` axis.

    Where training uses an explicit ``shard_map`` (psums spelled out),
    serving uses the pure GSPMD form: jit with ``NamedSharding``
    annotations on params (Megatron column/row split, ``param_specs``)
    and cache (kv heads on tp, ``cache_spec``) and let XLA place the
    collectives — one all-reduce after each row-parallel matmul, the
    attention itself collective-free because each shard holds exactly
    its own heads' Q and KV rows.  The TPU-native analogue of the
    reference stack's multi-GPU serving (its clients drive
    NCCL-backed backends; here the backend itself is the sharded jit).

    Requires tp | n_heads and tp | n_kv_heads.  Returns
    ``(init_cache, prefill_fn, decode_fn)``; ``decode_fn`` is
    ``decode_chunk`` with the cache donated (pass ``donate=False`` when
    the caller needs the input cache afterwards, e.g. A/B tests).
    """
    tp = mesh.shape["tp"]
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(
            "tp={} must divide n_heads={} and n_kv_heads={}".format(
                tp, cfg.n_heads, cfg.n_kv_heads
            )
        )
    param_sh, cache_sh, repl = serving_shardings(
        mesh, cfg, quantized=quantized
    )

    prefill_fn = jax.jit(
        named_partial(prefill, cfg=cfg),
        in_shardings=(param_sh, cache_sh, repl),
        out_shardings=(repl, cache_sh),
    )
    decode_fn = jax.jit(
        named_partial(decode_chunk, cfg=cfg, chunk=chunk),
        in_shardings=(param_sh, cache_sh, repl, repl),
        out_shardings=(repl, repl, repl, cache_sh),
        donate_argnums=(1,) if donate else (),
    )

    def init_cache(batch, max_seq):
        return jax.device_put(
            init_kv_cache(cfg, batch, max_seq), cache_sh
        )

    return init_cache, prefill_fn, decode_fn


def serving_shardings(mesh, cfg, quantized=False, quantized_embed=False):
    """(param_sh, cache_sh, repl) NamedSharding trees for TP serving —
    the single source shared by ``make_tp_serving``, ``make_tp_step``
    and the serving model's ``device_put`` of loaded params."""
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_specs(
            cfg, quantized=quantized, quantized_embed=quantized_embed
        ),
    )
    cache_sh = NamedSharding(mesh, cache_spec(cfg))
    repl = NamedSharding(mesh, P())
    return param_sh, cache_sh, repl


def make_tp_step(mesh, cfg, donate=True, quantized=False):
    """Single-token tensor-parallel ``decode_step`` (same sharding rules
    as ``make_tp_serving``) — the per-token path serving uses for chunk
    tails and for feeding resumed-prompt tokens into a parked cache."""
    param_sh, cache_sh, repl = serving_shardings(
        mesh, cfg, quantized=quantized
    )
    return jax.jit(
        named_partial(decode_step, cfg=cfg),
        in_shardings=(param_sh, cache_sh, repl, repl),
        out_shardings=(repl, cache_sh),
        donate_argnums=(1,) if donate else (),
    )
