"""Plain reference of the SDAR family (JetLM's ``modeling_sdar_moe.py``
and ``generate.py``: a Qwen3-MoE block under a block-causal mask,
generation by diffusion over blocks): straightforward ``jax.numpy``,
float32, ``highest`` matmul precision; no kernel, no pages, no batching
of requests.  It imports nothing of the program and takes nothing it
made: weights come from ``weights_sdar.py`` and the seed.

The equations (one sequence, x [T, D], block length B):

- layer: ``y = RMSNorm(x)``; ``q, k, v = y Wq, y Wk, y Wv`` in heads of
  ``head_dim``; ``q = RMSNorm_head(q)``, ``k = RMSNorm_head(k)`` (gains
  over the head size); rotary positions (half-split) on q and k, every
  layer; BLOCK-CAUSAL attention: query i sees key j iff ``j // B <= i //
  B``; scale 1/sqrt(head size); GQA; ``x = x + attn Wo``.
- ``h = RMSNorm(x)``; ``p = softmax(h Wr)`` over all experts, float32;
  top-k of ``p``; ``w_e = p_e / sum over the chosen of p``
  (``norm_topk_prob``); ``x = x + sum over chosen of w_e
  Down_e(silu(Gate_e h) * Up_e h)``.  No shared expert, no router bias.
- final RMSNorm, untied head, float32 logits.  The logits at a position
  are of THAT position's token (no shift).

Generation (``generate``): the prompt's whole blocks are context; the
rest of the prompt opens the first block as given tokens.  A block
starts as mask tokens; a DENOISE pass runs the whole sequence so far
with the block as it stands, takes ``x0 = argmax`` and ``c =
softmax(logits)[x0]`` at the masked positions and unmasks (``unmask``);
when none is masked the block is final (the published loop then runs
its commit pass, which only stores K/V: a reference without a cache has
nothing to store).

Departures from the published code, each also under the configuration's
``assumed``: (1) the norm gains are the seed's; (2) the mask token's
logit is taken out before argmax and softmax, so the mask token is
never a prediction (the published code would leave such a position
masked for good); (3) a pass unmasks at most the positions still masked
(the published ``topk`` over ``-inf`` confidences could overwrite a
final token when a threshold pass had run ahead of the schedule): an
unmasked token is final; (4) every pass is a from-scratch forward over
the whole sequence so far (the published loop keeps the K/V of earlier
blocks, which the block-causal mask makes the same numbers); (5) the
reference runs every expert over every token and weighs by the routing
(0 where not chosen): plain, not fast.  ``replay_logits`` computes a
long sequence once and the B positions of each replayed pass against
its keys and values: again the same numbers by the mask, and what lets
4,608 positions of 128 float32 experts fit the chip.

``precision="int8"`` is the CONTROL one step below the bf16 the
configuration states: every linear layer on operands rounded to int8
(weights per output channel, activations per token, symmetric absmax);
the router stays float32, as it is in the program.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import weights_sdar
# the linear layer (float32, or the int8 control's rounding) and the
# RMSNorm are the same equations in every family's reference
from reference_afmoe import HIGHEST, Q_BLOCK, linear, rms


def rope(x, positions, theta):
    """x [T, H, D] at ``positions`` [T]; half-split convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def shape_of(sizes):
    """What the equations read of a builder's ``sizes``."""
    return {
        "n_heads": sizes["num_attention_heads"],
        "n_kv_heads": sizes["num_key_value_heads"],
        "head_dim": sizes["head_dim"], "eps": sizes["rms_norm_eps"],
        "rope_theta": sizes["rope_theta"],
        "top_k": sizes["num_experts_per_tok"],
        "block": sizes["block_length"], "mask_id": sizes["mask_token_id"],
    }


def qkv(w, y, positions, s, precision="f32"):
    """Normed, rotated q [T, H, hd] and k, and v [T, Hkv, hd] of the
    normed stream y [T, D] at ``positions``."""
    t = y.shape[0]
    h, kv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    q = rms(linear(y, w["wq"], precision).reshape(t, h, hd), w["q_norm"],
            s["eps"])
    k = rms(linear(y, w["wk"], precision).reshape(t, kv, hd), w["k_norm"],
            s["eps"])
    v = linear(y, w["wv"], precision).reshape(t, kv, hd)
    return (rope(q, positions, s["rope_theta"]),
            rope(k, positions, s["rope_theta"]), v)


def attend(q, k, v, seen):
    """softmax(q k^T / sqrt(hd)) v for q [Tq, H, hd] over k, v
    [Tk, Hkv, hd] where ``seen`` [Tq, Tk]; returns [Tq, H * hd]."""
    h, hd = q.shape[1], q.shape[2]
    k, v = (jnp.repeat(a, h // a.shape[1], 1) for a in (k, v))
    sc = jnp.einsum("qhd,uhd->hqu", q, k, precision=HIGHEST) / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
    return jnp.einsum("hqu,uhd->qhd", p, v, precision=HIGHEST).reshape(
        q.shape[0], h * hd)


def routed_ffn(w, y, s, precision="f32"):
    """The sum over the chosen experts, y [T, D]; every expert runs over
    every token and is weighed by the routing."""
    p = jax.nn.softmax(jnp.matmul(y, w["router"], precision=HIGHEST), -1)
    wt, chosen = lax.top_k(p, s["top_k"])
    wt = wt / jnp.sum(wt, -1, keepdims=True)

    def add(acc, e):
        mine = jnp.sum(jnp.where(chosen == e, wt, 0.0), -1)
        part = linear(
            jax.nn.silu(linear(y, w["we_gate"][e], precision))
            * linear(y, w["we_up"][e], precision), w["we_down"][e], precision)
        return acc + mine[:, None] * part, None

    out, _ = lax.scan(add, jnp.zeros_like(y), jnp.arange(w["router"].shape[1]))
    return out


def layer(w, x, s, precision="f32"):
    """One sequence x [T, D] through one layer under the block-causal
    mask; returns the stream and the layer's k, v [T, Hkv, hd]."""
    t, b = x.shape[0], s["block"]
    q, k, v = qkv(w, rms(x, w["attn_norm"], s["eps"]), jnp.arange(t), s,
                  precision)
    block = min(Q_BLOCK, t)
    pad = -t % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, *q.shape[1:])
    j = jnp.arange(t)[None, :]

    def rows(args):
        qs, i0 = args
        i = i0 + jnp.arange(block)[:, None]
        return attend(qs, k, v, j < (i // b + 1) * b)

    a = lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
    x = x + linear(a.reshape(-1, a.shape[-1])[:t], w["wo"], precision)
    return x + routed_ffn(w, rms(x, w["mlp_norm"], s["eps"]), s, precision), k, v


def block_layer(w, xb, start, k, v, s, precision="f32"):
    """The B positions xb [B, D] of a block that starts at ``start``
    through one layer, against the keys and values k, v [T, Hkv, hd] of
    the sequence before it (positions below ``start``) and its own."""
    b = xb.shape[0]
    q, kb, vb = qkv(w, rms(xb, w["attn_norm"], s["eps"]),
                    start + jnp.arange(b), s, precision)
    seen = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(k.shape[0])[None, :] < start,
                         (b, k.shape[0])),
        jnp.ones((b, b), bool)], 1)
    a = attend(q, jnp.concatenate([k, kb]), jnp.concatenate([v, vb]), seen)
    xb = xb + linear(a, w["wo"], precision)
    return xb + routed_ffn(w, rms(xb, w["mlp_norm"], s["eps"]), s, precision)


def head(ends, x, s, precision="f32"):
    return linear(rms(x, ends["norm"], s["eps"]), ends["lm_head"], precision)


def forward(params, ids, masked, s, with_kv=False):
    """Logits [T, V] of token ids [T], the mask token standing where
    ``masked`` [T] is set, under the block-causal mask of ``s["block"]``;
    ``params`` the served tree in float32.  With ``with_kv`` also every
    layer's (k, v)."""
    ids = jnp.where(jnp.asarray(masked), s["mask_id"], jnp.asarray(ids))
    x, kvs = params["embed"][ids], []
    for w in params["layers"]:
        x, k, v = layer(w, x, s)
        kvs.append((k, v))
    logits = head(params, x, s)
    return (logits, kvs) if with_kv else logits


def schedule(b, steps, n_pass):
    """Positions the static schedule unmasks in pass ``n_pass`` of a block
    of ``b`` at ``steps`` denoising steps."""
    return b // steps + (1 if n_pass < b % steps else 0)


def unmask(logits, masked, n_pass, steps, tau, mask_id):
    """The unmask rule on one block: logits [B, V], ``masked`` [B] bool.
    Returns ``(x0 [B], log c [B], newly unmasked [B] bool)`` (numpy)."""
    logits = np.array(logits, np.float32)
    logits[:, mask_id] = -np.inf
    x0 = logits.argmax(-1)
    top = logits.max(-1)
    logc = top - (top + np.log(np.exp(logits - top[:, None]).sum(-1)))
    conf = np.where(masked, np.exp(logc), -np.inf)
    n = min(schedule(len(masked), steps, n_pass), int(masked.sum()))
    high = masked & (conf > tau)
    if high.sum() >= n:
        return x0, logc, high
    # highest confidence first, ties to the lowest position
    order = sorted(range(len(masked)), key=lambda j: (-conf[j], j))
    newly = np.zeros(len(masked), bool)
    newly[order[:n]] = True
    return x0, logc, newly & masked


def generate(params, prompt, n, steps, tau, s):
    """The published loop: ``n`` tokens after ``prompt`` in blocks of
    ``s["block"]``, ``steps`` denoising steps a block, threshold ``tau``
    (1 = the static schedule alone).  Returns ``(tokens, records)``:
    the sequence (prompt and whole generated blocks) and per generated
    position ``(position, token, log c, pass)`` in position order."""
    b, mask_id = s["block"], s["mask_id"]
    seq = [int(t) for t in prompt]
    start = len(seq) // b * b
    records = []
    while start < len(prompt) + n:
        block = (seq[start:] + [mask_id] * b)[:b]
        masked = np.arange(b) >= len(seq) - start
        seq = seq[:start]
        n_pass = 0
        while masked.any():
            logits = forward(params, np.asarray(seq + block),
                             np.concatenate([np.zeros(start, bool), masked]),
                             s)[start:]
            x0, logc, newly = unmask(np.asarray(logits), masked, n_pass,
                                     steps, tau, mask_id)
            for j in np.flatnonzero(newly):
                block[j] = int(x0[j])
                records.append((start + int(j), int(x0[j]), float(logc[j]),
                                n_pass))
            masked = masked & ~newly
            n_pass += 1
        seq, start = seq + block, start + b
    return seq, sorted(records)


# -- the seeded model, a layer at a time, for the chip -------------------------


@functools.lru_cache(maxsize=None)
def _embed_fn(frozen):
    sizes = dict(frozen)
    return jax.jit(lambda key, tokens: weights_sdar.ends(
        key, sizes, jnp.float32)["embed"][tokens])


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, i, precision):
    sizes = dict(frozen)
    s = shape_of(sizes)

    def run(key, x, xb, starts):
        w = weights_sdar.layer(key, sizes, i, jnp.float32)

        def one(args):
            x1, xb1, starts1 = args
            out, k, v = layer(w, x1, s, precision)
            blocks = lax.map(lambda job: block_layer(
                w, job[0], job[1], k, v, s, precision), (xb1, starts1))
            return out, blocks
        return lax.map(one, (x, xb, starts))
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, precision):
    sizes = dict(frozen)
    s = shape_of(sizes)
    return jax.jit(lambda key, x: head(
        weights_sdar.ends(key, sizes, jnp.float32), x, s, precision))


def replay_logits(seed, sizes, tokens, starts, blocks, precision="f32"):
    """Logits [R, J, B, V] (float32, host) of replayed passes: for each of
    R sequences ``tokens`` [R, W] (prompt and served blocks, final
    tokens) and each of its J jobs, the block of B token ids ``blocks``
    [R, J, B] (the mask id where masked at that pass) standing at
    ``starts`` [R, J] after the sequence's first ``starts`` positions.
    One from-scratch pass over each sequence, layer by layer (one
    layer's float32 weights live at a time); each job's B positions go
    through every layer against the sequence's keys and values below
    its start and its own."""
    frozen = weights_sdar.frozen(sizes)
    key = weights_sdar.root_key(seed)
    starts = jnp.asarray(starts, jnp.int32)
    with jax.default_matmul_precision("highest"):
        embed = _embed_fn(frozen)
        x = embed(key, jnp.asarray(tokens, jnp.int32))
        xb = embed(key, jnp.asarray(blocks, jnp.int32))
        for i in range(sizes["num_hidden_layers"]):
            x, xb = _layer_fn(frozen, i, precision)(key, x, xb, starts)
        return np.asarray(_head_fn(frozen, precision)(key, xb))
