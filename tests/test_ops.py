"""Pallas hot-op kernels (tpuserver.ops) against dense references —
interpret mode on the CPU mesh; the same kernels compile through Mosaic
on TPU (see docs/development.md)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuserver.ops import flash_attention


def _dense(q, k, v, causal=True):
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[1]
        mask = np.tril(np.ones((t, t), bool))
        s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def test_flash_attention_causal_matches_dense():
    rng = np.random.RandomState(0)
    q = rng.randn(2, 64, 4, 16).astype(np.float32)
    k = rng.randn(2, 64, 4, 16).astype(np.float32)
    v = rng.randn(2, 64, 4, 16).astype(np.float32)
    out = flash_attention(
        jnp.array(q), jnp.array(k), jnp.array(v), block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), _dense(q, k, v), rtol=2e-4, atol=2e-4)


def test_flash_attention_noncausal_uneven_blocks():
    rng = np.random.RandomState(1)
    q = rng.randn(1, 96, 2, 8).astype(np.float32)
    k = rng.randn(1, 96, 2, 8).astype(np.float32)
    v = rng.randn(1, 96, 2, 8).astype(np.float32)
    out = flash_attention(
        jnp.array(q), jnp.array(k), jnp.array(v), causal=False,
        block_q=32, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), _dense(q, k, v, False), rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16_inputs():
    rng = np.random.RandomState(2)
    q = rng.randn(1, 32, 2, 8).astype(np.float32)
    k = rng.randn(1, 32, 2, 8).astype(np.float32)
    v = rng.randn(1, 32, 2, 8).astype(np.float32)
    out = flash_attention(
        jnp.array(q, jnp.bfloat16), jnp.array(k, jnp.bfloat16),
        jnp.array(v, jnp.bfloat16), block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), _dense(q, k, v), rtol=5e-2, atol=5e-2)


def test_flash_attention_block_divisibility_error():
    q = jnp.zeros((1, 48, 2, 8), jnp.float32)
    try:
        flash_attention(q, q, q, block_q=32, block_k=32)
        raise AssertionError("expected divisibility error")
    except ValueError as e:
        assert "divide" in str(e)


def _seen(t, causal, window=None, block_causal=None):
    """[t, t] bool, query i sees key j: the masks as the model defines
    them, written apart from the schedule's arithmetic."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    if not causal:
        return np.ones((t, t), bool)
    seen = (j < (i // block_causal + 1) * block_causal if block_causal
            else j <= i)
    if window:
        seen &= i - j < window
    return seen


@pytest.mark.parametrize("t,block_q,block_k,causal,window,block_causal", [
    (128, 128, 128, True, None, None),      # one q-block, one pair
    (1024, 256, 512, True, None, None),
    (2048, 512, 256, True, None, None),
    (8192, 256, 512, True, None, None),
    (8192, 128, 256, True, 4096, None),     # Trinity's window layers
    (1024, 128, 256, True, 100, None),      # a window of no whole blocks
    (512, 256, 128, True, 1000, None),      # a window longer than T
    (256, 64, 64, True, 66, None),          # a pair that sees ONE score
    (1024, 128, 256, True, None, 4),        # SDAR's blocks of 4
    (4096, 256, 128, True, None, 4),
    (384, 128, 64, False, None, None),
])
def test_flash_schedule_is_the_live_block_pairs(t, block_q, block_k, causal,
                                                window, block_causal):
    """The kernel's grid is exactly the block pairs that hold a seen
    score, each once, a q-block's k-blocks ascending; its first and last
    pair flagged, and PARTIAL on exactly the pairs with a masked score."""
    from tpuserver.ops import flash

    q_blocks, k_blocks, flags = flash.flash_schedule(
        t, t, block_q, block_k, causal, window, block_causal)
    blocks = _seen(t, causal, window, block_causal).reshape(
        t // block_q, block_q, t // block_k, block_k)
    live, whole = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
    pairs = list(zip(q_blocks.tolist(), k_blocks.tolist()))
    assert len(set(pairs)) == len(pairs)
    assert set(pairs) == set(zip(*(a.tolist() for a in np.nonzero(live))))
    assert np.all(np.diff(q_blocks) >= 0)
    new_q = np.diff(q_blocks) != 0
    assert np.all(np.diff(k_blocks)[~new_q] > 0)
    first = np.r_[True, new_q]
    last = np.r_[new_q, True]
    assert np.array_equal((flags & flash.FIRST) != 0, first)
    assert np.array_equal((flags & flash.LAST) != 0, last)
    assert np.array_equal((flags & flash.PARTIAL) != 0,
                          ~whole[q_blocks, k_blocks])


@pytest.mark.parametrize("d,d_v,block_q,block_k", [
    (64, 64, 256, 128),     # one q-block, bq > bk
    (128, 128, 64, 128),    # bq < bk
    (192, 128, 128, 64),    # the expanded latent form's head sizes
])
def test_flash_attention_head_sizes_match_dense(d, d_v, block_q, block_k):
    """The served head sizes in interpret mode, causal, against dense
    attention: a single q-block, and tiles either way round."""
    rng = np.random.default_rng(7)
    q, k = (rng.normal(size=(1, 256, 2, d)).astype(np.float32)
            for _ in range(2))
    v = rng.normal(size=(1, 256, 2, d_v)).astype(np.float32)
    out = flash_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                          block_q=block_q, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fields,t,tiles", [
    ({}, 2048, (512, 1024)),                       # Mistral's 128-wide heads
    ({}, 1024, (512, 1024)),
    ({}, 8192, (512, 1024)),
    ({}, 1536, (512, 512)),                         # T does not divide by 1024
    ({}, 384, (128, 128)),
    ({}, 200, (None, None)),                        # dense path
    ({"block_len": 4}, 4096, (256, 512)),           # a block diagonal
    ({"d_head": 64}, 1024, (256, 512)),             # 64-wide heads
    ({"d_head": 64}, 256, (256, 256)),
    ({"mla": (128, 64)}, 8192, (512, 1024)),        # 192-wide expanded keys
    ({"mla": (16, 8)}, 1024, (256, 512)),           # 24-wide
    ({"flash_block_q": 32, "flash_block_k": 64}, 128, (32, 64)),
])
def test_flash_tiles_follow_the_length_and_the_head_width(fields, t, tiles):
    """``_flash_blocks``: the preferred 512 x 1024 where T divides by it
    and the keys are at least 128 wide, at most 256 x 512 for narrower
    heads and block diagonals, smaller tiles where T asks for them."""
    from tpuserver.models import llama

    fields = dict(fields)
    if "mla" in fields:
        d_nope, d_rope = fields.pop("mla")
        fields["mla"] = llama.MLAConfig(d_nope=d_nope, d_rope=d_rope)
    cfg = llama.LlamaConfig(d_model=4096, n_heads=32, n_kv_heads=8,
                            **fields)
    assert llama._flash_blocks(t, cfg) == tiles


def test_llama_forward_pallas_matches_xla():
    """The flagship model's single-shard forward agrees across attention
    implementations."""
    from tpuserver.models import llama

    cfg = llama.tiny(vocab=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    # 128-multiple length: forward()'s flash path only engages on
    # MXU-tileable T, anything else silently falls back to dense
    tokens = jnp.array(
        np.random.RandomState(3).randint(0, 64, (1, 128)), jnp.int32)
    xla_logits = llama.forward(params, tokens, cfg)
    pallas_logits = llama.forward(
        params, tokens, dataclasses.replace(cfg, attn_impl="pallas"))
    np.testing.assert_allclose(
        np.asarray(xla_logits), np.asarray(pallas_logits),
        rtol=5e-2, atol=5e-2)


def test_llama_decode_pallas_matches_xla():
    """The serving decode path (prefill + chunked greedy decode) emits
    identical tokens with the Pallas kernels wired in (attn_impl='pallas'
    routes prefill through flash_attention, decode_impl='pallas' routes
    single-query attention through decode_attention)."""
    import dataclasses as dc
    import functools

    from tpuserver.models import llama

    max_seq = 256
    cfg_xla = llama.tiny(vocab=128)
    cfg_pal = dc.replace(
        cfg_xla, attn_impl="pallas", decode_impl="pallas")
    params = llama.init_params(jax.random.PRNGKey(5), cfg_xla)
    # 128-token prompt so the flash PREFILL branch engages (shorter
    # prompts fall back to dense and the test would go vacuous)
    prompt = jnp.array(
        np.random.RandomState(9).randint(0, 128, (1, 128)), jnp.int32)

    def generate(cfg, n=12, chunk=4):
        prefill = jax.jit(functools.partial(llama.prefill, cfg=cfg))
        decode = jax.jit(
            functools.partial(llama.decode_chunk, cfg=cfg, chunk=chunk))
        cache = llama.init_kv_cache(cfg, 1, max_seq)
        logits, cache = prefill(params, cache, prompt)
        out, pos = [], prompt.shape[1]
        for _ in range(n // chunk):
            toks, _, logits, cache = decode(params, cache, logits, pos)
            out.append(np.asarray(toks)[:, 0])
            pos += chunk
        return np.concatenate(out), np.asarray(logits)

    toks_xla, logits_xla = generate(cfg_xla)
    toks_pal, logits_pal = generate(cfg_pal)
    np.testing.assert_array_equal(toks_xla, toks_pal)
    np.testing.assert_allclose(logits_xla, logits_pal, rtol=5e-2, atol=5e-2)


def _dense_decode(q, kc, vc, lengths, n_rep):
    k = np.repeat(kc, n_rep, axis=2)
    v = np.repeat(vc, n_rep, axis=2)
    s = np.einsum("bhd,bkhd->bhk", q, k) / np.sqrt(q.shape[-1])
    for bi, length in enumerate(lengths):
        s[bi, :, length:] = -np.inf
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhk,bkhd->bhd", p, v)


def test_decode_attention_matches_dense():
    """Single-query decode over a padded KV cache: GQA head mapping and
    per-batch valid lengths."""
    from tpuserver.ops import decode_attention

    rng = np.random.RandomState(4)
    q = rng.randn(2, 6, 16).astype(np.float32)
    kc = rng.randn(2, 64, 2, 16).astype(np.float32)
    vc = rng.randn(2, 64, 2, 16).astype(np.float32)
    lengths = np.array([40, 17], np.int32)
    out = decode_attention(
        jnp.array(q), jnp.array(kc), jnp.array(vc), jnp.array(lengths),
        block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), _dense_decode(q, kc, vc, lengths, 3),
        rtol=2e-4, atol=2e-4)


def test_decode_attention_no_gqa_short_length():
    from tpuserver.ops import decode_attention

    rng = np.random.RandomState(5)
    q = rng.randn(1, 4, 8).astype(np.float32)
    kc = rng.randn(1, 32, 4, 8).astype(np.float32)
    vc = rng.randn(1, 32, 4, 8).astype(np.float32)
    lengths = np.array([1], np.int32)  # attend a single position
    out = decode_attention(
        jnp.array(q), jnp.array(kc), jnp.array(vc), jnp.array(lengths),
        block_k=8)
    np.testing.assert_allclose(
        np.asarray(out), _dense_decode(q, kc, vc, lengths, 1),
        rtol=2e-4, atol=2e-4)


# -- paged decode attention: the pool read in place --------------------------


def _paged_case(h, hkv, d, page, ppseq, lengths, dtype, seed, layers=2):
    """A pool of ``layers`` layers, one shuffled page table row per
    length (no physical page shared), queries to match."""
    rng = np.random.RandomState(seed)
    rows = len(lengths)
    n_pages = rows * ppseq + 3
    pool = jnp.asarray(
        rng.randn(layers, 2, n_pages, page, hkv, d).astype(np.float32),
        dtype)
    tables = rng.permutation(n_pages)[:rows * ppseq].reshape(
        rows, ppseq).astype(np.int32)
    q = jnp.asarray(rng.randn(rows, h, d).astype(np.float32), dtype)
    return q, pool, tables, np.asarray(lengths, np.int32)


def _gathered(pool, layer, tables):
    """The contiguous [rows, S, Hkv, D] K and V views of ``layer`` that
    the fallback path materialises."""
    rows, ppseq = tables.shape
    tail = pool.shape[4:]
    return (pool[layer, 0][tables].reshape(rows, -1, *tail),
            pool[layer, 1][tables].reshape(rows, -1, *tail))


# lengths: 1, inside a page, on a page edge, on a block edge, one past
# it, the whole row; a 0 (nothing attended: zeros, as decode_attention)
PAGED_CASES = {
    "gqa_bf16": dict(h=6, hkv=2, d=16, page=16, ppseq=8, block_k=64,
                     lengths=[1, 37, 48, 64, 65, 128], dtype="bfloat16"),
    "no_gqa_f32": dict(h=4, hkv=4, d=8, page=8, ppseq=8, block_k=32,
                       lengths=[1, 13, 32, 33, 64], dtype="float32"),
    "one_block_a_row": dict(h=4, hkv=2, d=16, page=16, ppseq=4, block_k=64,
                            lengths=[64, 1, 17], dtype="bfloat16"),
    "block_of_one_page": dict(h=2, hkv=1, d=8, page=16, ppseq=4, block_k=16,
                              lengths=[16, 15, 64, 1], dtype="float32"),
    "empty_rows": dict(h=4, hkv=2, d=8, page=8, ppseq=4, block_k=16,
                       lengths=[0, 20, 0, 32, 0], dtype="float32"),
}


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("case", sorted(PAGED_CASES))
def test_paged_decode_attention_equals_the_gathered_view(case, layer):
    """The paged kernel folds the same blocks in the same order as
    decode_attention over the gathered view: BIT-equal to it, for
    shuffled page tables, at every kind of length, on either layer."""
    from tpuserver.ops import decode_attention, paged_decode_attention

    c = dict(PAGED_CASES[case])
    block_k, dtype = c.pop("block_k"), jnp.dtype(c.pop("dtype"))
    q, pool, tables, lengths = _paged_case(dtype=dtype, seed=11, **c)
    got = paged_decode_attention(
        q, pool, layer, jnp.array(tables), jnp.array(lengths),
        block_k=block_k)
    k_seq, v_seq = _gathered(pool, layer, tables)
    want = decode_attention(
        q, k_seq, v_seq, jnp.array(lengths), block_k=block_k)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))
    live = lengths > 0
    q, k_seq, v_seq, got = (
        np.asarray(x, np.float32)[live] for x in (q, k_seq, v_seq, got))
    np.testing.assert_allclose(
        got, _dense_decode(q, k_seq, v_seq, lengths[live],
                           c["h"] // c["hkv"]),
        rtol=2e-2, atol=2e-2)


def test_paged_decode_attention_never_reads_past_a_rows_live_blocks():
    """Page-table entries past a row's length (the step clips the
    sentinel ``n_pages`` onto the last page) change nothing: entries in
    dead blocks are never copied, and the rest of the last live block
    is masked."""
    from tpuserver.ops import paged_decode_attention

    q, pool, tables, lengths = _paged_case(
        h=6, hkv=2, d=16, page=16, ppseq=8, lengths=[1, 37, 64, 100],
        dtype=jnp.bfloat16, seed=12)
    n_pages = pool.shape[2]
    dead = np.arange(8)[None, :] * 16 >= lengths[:, None]
    sentinel = np.where(dead, n_pages, tables)
    outs = [
        np.asarray(paged_decode_attention(
            q, pool, 1, jnp.clip(jnp.array(tbl), 0, n_pages - 1),
            jnp.array(lengths), block_k=64), np.float32)
        for tbl in (tables, sentinel)]
    np.testing.assert_array_equal(outs[0], outs[1])
    # dead BLOCKS are not even copied: poison their pages
    dead_blocks = np.arange(8)[None, :] // 4 * 64 >= lengths[:, None]
    poisoned = pool.at[:, :, tables[dead_blocks]].set(jnp.nan)
    out = np.asarray(paged_decode_attention(
        q, poisoned, 1, jnp.array(tables), jnp.array(lengths), block_k=64),
        np.float32)
    np.testing.assert_array_equal(outs[0], out)


def test_paged_decode_attention_takes_a_traced_layer():
    """One executable serves every layer: ``layer`` may be traced."""
    from tpuserver.ops import paged_decode_attention

    q, pool, tables, lengths = _paged_case(
        h=4, hkv=2, d=8, page=8, ppseq=4, lengths=[9, 32],
        dtype=jnp.float32, seed=13, layers=3)
    fn = jax.jit(lambda layer: paged_decode_attention(
        q, pool, layer, jnp.array(tables), jnp.array(lengths), block_k=16))
    for layer in range(3):
        np.testing.assert_array_equal(
            np.asarray(fn(jnp.int32(layer))),
            np.asarray(paged_decode_attention(
                q, pool, layer, jnp.array(tables), jnp.array(lengths),
                block_k=16)))
    assert not np.array_equal(np.asarray(fn(0)), np.asarray(fn(2)))


def test_paged_decode_attention_refuses_a_block_of_broken_pages():
    from tpuserver.ops import paged_decode_attention

    q, pool, tables, lengths = _paged_case(
        h=4, hkv=2, d=8, page=24, ppseq=4, lengths=[9],
        dtype=jnp.float32, seed=14)
    with pytest.raises(ValueError, match="whole pages"):
        paged_decode_attention(
            q, pool, 0, jnp.array(tables), jnp.array(lengths), block_k=32)


# -- the fold on the MXU, at the served head geometries ----------------------


def _ring_reference(q, pool, layer, tables, lengths, starts, page):
    """Float64 single-query attention of every row over positions
    ``[start, length)``, position ``p`` read through the table as a ring
    (entry ``p // page % entries``; a straight table never wraps).  A row
    that attends nothing gives zeros, as the kernel does."""
    q, kv = np.asarray(q, np.float64), np.asarray(pool[layer], np.float64)
    rows, h, d = q.shape
    out = np.zeros((rows, h, d))
    for b in range(rows):
        pos = np.arange(starts[b], lengths[b])
        if not pos.size:
            continue
        entry = tables[b][pos // page % tables.shape[1]]
        k, v = (np.repeat(x[entry, pos % page], h // kv.shape[3], axis=1)
                for x in kv)                               # [n, H, D]
        s = np.einsum("hd,nhd->hn", q[b], k) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = np.einsum("hn,nhd->hd", p / p.sum(-1, keepdims=True), v)
    return out


# bf16 in and out: the result is rounded to 8 bits (values reach ~4: a
# row of length 1 returns V itself), and so are the probabilities
# before the value product; the products accumulate in float32
SERVED_TOL = dict(rtol=2e-2, atol=2e-2)
SERVED_HEADS = {"mistral_32_8": (32, 8), "trinity_48_8": (48, 8),
                "no_gqa_8_8": (8, 8)}


@pytest.mark.parametrize("table", ["straight", "ring_wrapped"])
@pytest.mark.parametrize("heads", sorted(SERVED_HEADS))
def test_paged_decode_attention_at_served_geometry(heads, table):
    """H/Hkv/D as served (32/8/128, 48/8/128; 8/8: no grouping), bf16,
    the step's block of 256 over pages of 16, against float64 dense
    attention: lengths 0, 1, on and around block edges; and the same
    through ``starts=`` over a ring of 3 blocks whose rows have grown
    past it, so logical blocks land on reused entries."""
    from tpuserver.ops import paged_decode_attention

    h, hkv = SERVED_HEADS[heads]
    d, page, block, window = 128, 16, 256, 300
    if table == "straight":
        lengths = np.array([0, 1, 255, 256, 257, 512, 700], np.int32)
        starts = np.zeros_like(lengths)
    else:
        lengths = np.array([0, 1, 256, 557, 768, 769, 1025, 1500], np.int32)
        starts = np.maximum(lengths - window, 0)
    q, pool, tables, _ = _paged_case(
        h=h, hkv=hkv, d=d, page=page, ppseq=3 * block // page,
        lengths=lengths, dtype=jnp.bfloat16, seed=21)
    got = paged_decode_attention(
        q, pool, 1, jnp.array(tables), jnp.array(lengths), block_k=block,
        starts=None if table == "straight" else jnp.array(starts))
    np.testing.assert_allclose(
        np.asarray(got, np.float64),
        _ring_reference(q, pool, 1, tables, lengths, starts, page),
        **SERVED_TOL)


@pytest.mark.parametrize("windowed", [False, True])
def test_decode_fold_masks_after_the_products(windowed):
    """The dots run over the whole block before the mask: what the last
    live block holds past ``length`` (and the first holds before
    ``start``), under every KV head, must weigh exactly nothing.  Large
    finite values there change no bit."""
    from tpuserver.ops import paged_decode_attention

    page, block = 16, 64
    lengths = np.array([1, 37, 64, 70, 150], np.int32)
    starts = (np.array([0, 5, 33, 40, 101], np.int32) if windowed
              else np.zeros_like(lengths))
    q, pool, tables, _ = _paged_case(
        h=6, hkv=2, d=16, page=page, ppseq=3 * block // page,
        lengths=lengths, dtype=jnp.bfloat16, seed=22)
    pos = np.arange(tables.shape[1] * page)
    outs = []
    for fill in (0.0, 1e4, -1e4):
        filled = pool
        for b, (lo, hi) in enumerate(zip(starts, lengths)):
            dead = pos[(pos < lo) | (pos >= hi)]
            filled = filled.at[:, :, tables[b][dead // page],
                               dead % page].set(fill)
        outs.append(np.asarray(paged_decode_attention(
            q, filled, 1, jnp.array(tables), jnp.array(lengths),
            block_k=block,
            starts=jnp.array(starts) if windowed else None), np.float32))
    assert np.abs(outs[0]).max() > 0.1
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
