"""Generation by diffusion over blocks (the SDAR family) through the one
decoder family: a softmax-routed MoE block with no shared expert and no
router bias under a block-causal mask, a step that carries a block a row
and yields 0..B tokens, served from the page pool by the scheduler.

Everything runs at toy widths on the CPU with interpreted kernels, in
float32 (in bf16 at these widths a near-tie in the router or between two
confidences flips a choice, which says nothing of the equations), against
the plain reference the benchmark brings (``benchmark/reference_sdar.py``:
``jax.numpy`` only, no cache) on the benchmark's seeded weights.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.append(os.path.join(REPO, "benchmark"))

import reference_sdar as ref  # noqa: E402
import weights_sdar  # noqa: E402
from tpuserver.models import llama  # noqa: E402
from tpuserver.models.llama_serving import LlamaGenerateModel  # noqa: E402
from tpuserver.ops import flash_attention, paged_decode_attention  # noqa: E402
from tpuserver.scheduler import DecodeScheduler  # noqa: E402

PAGE, B, MASK = 16, 4, 255
SIZES = dict(
    hidden_size=64, moe_intermediate_size=32, num_attention_heads=8,
    num_key_value_heads=4, head_dim=16, num_hidden_layers=2, vocab_size=256,
    rope_theta=10000.0, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=2, block_length=B, mask_token_id=MASK)
SHAPE = ref.shape_of(SIZES)


def config(max_seq_kernel=False, **over):
    """The toy SDAR block in float32; with ``max_seq_kernel`` on the
    paged decode kernel (needs a ``max_seq`` with a 128 block)."""
    return llama.LlamaConfig(**dict(dict(
        vocab=256, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
        d_head=16, d_ff=128, rope_theta=10000.0, norm_eps=1e-6,
        qk_norm=True, dtype=jnp.float32, ffn_types=("moe", "moe"),
        attn_impl="pallas" if max_seq_kernel else "xla",
        decode_impl="pallas" if max_seq_kernel else "xla",
        moe=llama.MoEConfig(n_experts=8, top_k=2, d_expert=32,
                            score_func="softmax", router_bias=False,
                            n_shared=0),
        block_len=B, mask_id=MASK), **over))


CFG = config()
PARAMS = weights_sdar.weights(7, SIZES, jnp.float32)
MAX_SEQ, SLOTS = 64, 4


@pytest.fixture(scope="module")
def fns():
    return llama.make_scheduler_fns(CFG, MAX_SEQ, SLOTS, page_size=PAGE)


def admit_row(fns, pages, state, prompt, n, slot):
    """Prefill ``prompt`` and admit it to ``slot`` as the scheduler does,
    with pages reserved for ``n`` tokens in whole blocks.  Returns the
    pool, the carried state, the row's page table and the positions
    reserved."""
    bucket = fns["prefill_bucket"](len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    slot_state, slot_cache = fns["prefill"](
        PARAMS, fns["init_slot_cache"](), jnp.asarray(padded), len(prompt))
    table = np.full((fns["pages_per_seq"],), fns["n_pages"], np.int32)
    need = -(-(len(prompt) + n) // B) * B
    # each slot its own pages, from 3 up
    table[:-(-need // PAGE)] = 3 + slot * fns["pages_per_seq"] + np.arange(
        -(-need // PAGE))
    pages, state = fns["admit"](pages, state, slot_cache, slot_state, table,
                                slot)
    return pages, state, table, need


def serve_alone(fns, prompt, n, steps, tau=1.0, slot=1):
    """Drive the bundle as the scheduler does for ONE request: prefill,
    admit, then block steps until ``n`` tokens stand, and the one step
    after them.  Returns the records ``(position, token, log c, pass)``
    in position order, the pool, the row's page table, and of the run's
    steps: their number, the pool before the last and its result row."""
    pages, state, table, need = admit_row(
        fns, fns["init_cache"](), fns["init_logits"](), prompt, n, slot)
    tables = np.full((SLOTS, fns["pages_per_seq"]), fns["n_pages"], np.int32)
    tables[slot] = table
    active = np.arange(SLOTS) == slot
    records, dispatched = [], 0
    while len(records) < need - len(prompt):
        out, logc, state, pages, _ = fns["step"](
            PARAMS, pages, state, tables, np.full((SLOTS,), steps, np.int32),
            np.full((SLOTS,), tau, np.float32), active)
        dispatched += 1
        row, logc = np.asarray(out)[slot], np.asarray(logc)[slot]
        start, _, n_pass, _ = row[2 * B:]
        for j in np.flatnonzero(row[B:2 * B]):
            records.append((int(start + j), int(row[j]), float(logc[j]),
                            int(n_pass)))
    # the step after the last block's last denoise pass commits it (the
    # scheduler's one-deep pipeline has dispatched it by then)
    before = np.asarray(pages)
    out, _, state, pages, _ = fns["step"](
        PARAMS, pages, state, tables, np.full((SLOTS,), steps, np.int32),
        np.full((SLOTS,), tau, np.float32), active)
    last = np.asarray(out)[slot]
    assert last[2 * B + 1] == 1
    return sorted(records), np.asarray(pages), table, {
        "steps": dispatched, "before": before, "last": last}


def reference(prompt, n, steps, tau=1.0):
    with jax.default_matmul_precision("highest"):
        return ref.generate(PARAMS, prompt, n, steps, tau, SHAPE)


def assert_pages_hold(pages, table, seq):
    """The row's pages hold the K/V a from-scratch forward over the final
    sequence ``seq`` gives, every layer."""
    with jax.default_matmul_precision("highest"):
        _, kvs = ref.forward(PARAMS, np.asarray(seq), np.zeros(len(seq), bool),
                             SHAPE, with_kv=True)
    for layer, (k, v) in enumerate(kvs):
        for kv, want_kv in ((0, k), (1, v)):
            held = pages[layer, kv][table[:-(-len(seq) // PAGE)]].reshape(
                -1, *pages.shape[4:])[:len(seq)]
            np.testing.assert_allclose(held, np.asarray(want_kv), atol=2e-4)


def assert_same_records(got, want):
    assert [r[:2] + r[3:] for r in got] == [r[:2] + r[3:] for r in want]
    np.testing.assert_allclose([r[2] for r in got], [r[2] for r in want],
                               atol=2e-4)


@pytest.mark.parametrize("rest,steps", [
    (0, 4), (1, 2), (2, 3), (3, 1), (0, 1), (1, 3), (2, 4), (3, 2)])
def test_block_step_matches_reference(fns, rest, steps):
    """Prefill, every denoise pass and the commit against the published
    loop: the same tokens at the same positions in the same passes, the
    same log-confidences, and in the pages the K/V a from-scratch
    forward over the final sequence gives."""
    prompt = list(np.random.default_rng(rest).integers(0, MASK, 8 + rest))
    got, pages, table, _ = serve_alone(fns, prompt, 7, steps)
    seq, want = reference(prompt, 7, steps)
    assert_same_records(got, want)
    assert_pages_hold(pages, table, seq)


@pytest.mark.parametrize("tau,passes", [(0.0, 1), (1.0, 4)])
def test_threshold_ends_of_the_range(fns, tau, passes):
    """tau 0: every confidence passes, a whole block in one pass; tau 1:
    none does, the static schedule."""
    prompt = list(range(5, 13))
    got, *_ = serve_alone(fns, prompt, 8, 4, tau)
    assert_same_records(got, reference(prompt, 8, 4, tau)[1])
    assert max(r[3] for r in got) + 1 == passes


CRAFTED = {
    # confidences of the 4 positions (the argmax's probability), tau,
    # masked, pass, steps -> newly unmasked
    "two_pass_the_threshold": ([.9, .2, .8, .3], .5, [1, 1, 1, 1], 0, 4,
                               [1, 0, 1, 0]),
    "none_passes_falls_back": ([.4, .2, .45, .3], .5, [1, 1, 1, 1], 0, 4,
                               [0, 0, 1, 0]),
    "too_few_pass_for_the_schedule": ([.9, .2, .3, .1], .5, [1, 1, 1, 1], 0,
                                      2, [1, 0, 1, 0]),
    "ties_go_to_the_lowest_position": ([.5, .5, .5, .5], 1., [1, 1, 1, 1],
                                       0, 2, [1, 1, 0, 0]),
    "unmasked_positions_stay": ([.99, .2, .98, .3], .5, [0, 1, 0, 1], 1, 4,
                                [0, 0, 0, 1]),
    "uneven_schedule_first_pass": ([.1, .2, .3, .4], 1., [1, 1, 1, 1], 0, 3,
                                   [0, 0, 1, 1]),
    "uneven_schedule_later_pass": ([.1, .2, .3, .4], 1., [1, 1, 0, 0], 1, 3,
                                   [0, 1, 0, 0]),
    "never_more_than_are_masked": ([.1, .2, .3, .4], 1., [0, 0, 0, 1], 1, 1,
                                   [0, 0, 0, 1]),
}


@pytest.mark.parametrize("case", sorted(CRAFTED))
def test_unmask_rule_on_crafted_logits(case):
    conf, tau, masked, n_pass, steps, want = CRAFTED[case]
    vocab = 16
    # logits whose softmax puts ``c`` on token 3 + position
    logits = np.zeros((B, vocab), np.float32)
    for j, c in enumerate(conf):
        logits[j, 3 + j] = np.log(c * (vocab - 2) / (1 - c))
    logits[:, vocab - 1] = 50.0         # the mask token: never chosen
    masked = np.array(masked, bool)
    x0, logc, newly = (np.asarray(a)[0] for a in llama.unmask_block(
        jnp.asarray(logits)[None], jnp.asarray(masked)[None],
        jnp.array([n_pass]), jnp.array([steps]),
        jnp.array([tau], jnp.float32), vocab - 1))
    assert list(newly) == [bool(w) for w in want]
    assert list(x0) == [3, 4, 5, 6]
    np.testing.assert_allclose(np.exp(logc), conf, rtol=1e-5)
    rx0, rlogc, rnewly = ref.unmask(logits, masked, n_pass, steps, tau,
                                    vocab - 1)
    assert list(rnewly) == list(newly) and list(rx0) == list(x0)
    np.testing.assert_allclose(rlogc, logc, atol=1e-5)


def test_rows_at_other_passes_and_step_classes_share_a_step(fns):
    """Rows of both step classes, at different passes of their blocks and
    with different rests of the prompt, in one batched step: each equal
    to itself alone."""
    asks = [(list(range(3, 3 + 8 + k)), 9 + k, steps)
            for k, steps in enumerate((4, 2, 1, 3))]
    scheduler = DecodeScheduler(fns, PARAMS, SLOTS, MAX_SEQ)
    try:
        streams = [scheduler.submit(np.asarray(p), n, denoising_steps=s)
                   for p, n, s in asks]
        together = [[blk for blk, _ in st] for st in streams]
        stats = scheduler.stats()
    finally:
        scheduler.close()
    for (prompt, n, steps), blocks in zip(asks, together):
        want = reference(prompt, n, steps)[1][:n]
        got = [(p, t, lp, u) for toks, lps, at, passes in blocks
               for t, lp, p, u in zip(toks, lps, at, passes)]
        assert_same_records(got, want)
        assert [b[2][0] for b in blocks[1:]] == [
            (len(prompt) // B + 1 + i) * B for i in range(len(blocks) - 1)]
    assert stats["tokens"] == sum(n for _, n, _ in asks)
    assert stats["diffusion_tokens_unmasked"] >= stats["tokens"]
    # every block but a request's last has a successor to commit it, in
    # that block's first pass; a last block's commit is never fetched
    blocks = sum(len(b) for b in together)
    assert stats["diffusion_fused_commits"] == blocks - len(asks)
    assert stats["diffusion_commit_passes"] == 0
    assert stats["diffusion_blocks_committed"] == stats[
        "diffusion_fused_commits"] + stats["diffusion_commit_passes"]
    # (of a last block cut at ``max_tokens`` not every pass is delivered)
    assert stats["diffusion_row_passes"] >= sum(
        max(u) + 1 for blks in together for *_, u in blks)
    assert stats["moe_local_pairs"] > 0 and stats["context_tokens"] > 0


@pytest.mark.parametrize("steps", [1, 2, 3, 4])
def test_a_block_costs_its_denoise_passes_and_no_more(fns, steps,
                                                      monkeypatch):
    """N blocks at ``denoising_steps`` T take N x T step dispatches and
    the one-deep pipeline's last: each block's commit rode on its
    successor's first denoise pass."""
    from tpuserver import scheduler as scheduler_mod

    calls = []
    dispatch = scheduler_mod._ControlledStep.__call__

    def counted(self, *args):
        calls.append(1)
        return dispatch(self, *args)

    monkeypatch.setattr(scheduler_mod._ControlledStep, "__call__", counted)
    scheduler = DecodeScheduler(fns, PARAMS, SLOTS, MAX_SEQ)
    try:
        blocks = [blk for blk, _ in scheduler.submit(
            np.arange(3, 11), 20, denoising_steps=steps)]
        stats = scheduler.stats()
    finally:
        scheduler.close()
    assert [len(b[0]) for b in blocks] == [4] * 5
    assert all(max(b[3]) == steps - 1 for b in blocks)
    # (a commit pass of its own a block would make it 5 more)
    assert 5 * steps <= len(calls) <= 5 * steps + 2
    assert stats["diffusion_row_passes"] == 5 * steps
    assert stats["diffusion_fused_commits"] == 4
    assert stats["diffusion_blocks_committed"] == 4


def test_block_with_no_room_for_a_successor_commits_alone(fns):
    """The block that ends at ``max_seq``: its commit opens nothing (the
    pass says so), the row then lies inert, and through the scheduler
    the request ends as any other, with the reference's tokens."""
    prompt = list(np.random.default_rng(9).integers(0, MASK, MAX_SEQ - 8))
    got, pages, table, run = serve_alone(fns, prompt, 8, 2)
    seq, want = reference(prompt, 8, 2)
    assert_same_records(got, want)
    assert len(seq) == MAX_SEQ and run["steps"] == 4
    start, commit, _, fused = run["last"][2 * B:]
    assert (start, commit, fused) == (MAX_SEQ - B, 1, 0)
    assert not run["last"][B:2 * B].any()
    assert_pages_hold(pages, table, seq)
    others = np.setdiff1d(np.arange(pages.shape[2]), table)
    assert not pages[:, :, others].any()
    scheduler = _scheduler(fns)
    try:
        blocks = [blk for blk, _ in scheduler.submit(
            np.asarray(prompt), 8, denoising_steps=2)]
    finally:
        scheduler.close()
    assert [t for b in blocks for t in b[0]] == [r[1] for r in want]
    assert [p for b in blocks for p in b[2]] == list(range(MAX_SEQ - 8, MAX_SEQ))


@pytest.mark.parametrize("n,lands", [(8, "dropped"), (4, "own_page")])
def test_step_after_the_last_block_writes_nothing_unreserved(fns, n, lands):
    """The step dispatched before the host has seen a request's last
    block commits that block and opens one past the request's end.  Its
    K/V fall in the row's own last page or, past the reserved pages, on
    the table's sentinel: every page outside the row's table is bit-equal
    before and after, and inside it only the two blocks' slots differ."""
    prompt = list(range(20, 28))
    _, pages, table, run = serve_alone(fns, prompt, n, 2)
    start, commit, n_pass, fused = run["last"][2 * B:]
    end = len(prompt) + n
    assert (start, commit, n_pass, fused) == (end, 1, 0, 1)
    assert (end % PAGE == 0) == (lands == "dropped")
    own = table[table < fns["n_pages"]]
    others = np.setdiff1d(np.arange(pages.shape[2]), own)
    assert np.array_equal(pages[:, :, others], run["before"][:, :, others])
    changed = np.flatnonzero(
        (pages[:, :, own] != run["before"][:, :, own]).any(axis=(0, 1, 2, 4, 5)))
    committed = set(range(end - B, end))
    opened = set(range(end, end + B)) if lands == "own_page" else set()
    assert committed <= set(changed) <= committed | opened


def test_committing_and_denoising_rows_share_a_step(fns):
    """Two rows in the same steps, one at ``denoising_steps`` 1 (every
    pass commits a block and opens the next) and one at 4 (a commit every
    fourth): each row's results are what it gets with the other absent."""
    asks = {0: (list(range(40, 48)), 1), 2: (list(range(60, 70)), 4)}

    def drive(slots):
        pages, state = fns["init_cache"](), fns["init_logits"]()
        tables = np.full((SLOTS, fns["pages_per_seq"]), fns["n_pages"],
                         np.int32)
        steps = np.ones((SLOTS,), np.int32)
        for slot in slots:
            pages, state, tables[slot], _ = admit_row(
                fns, pages, state, asks[slot][0], 16, slot)
            steps[slot] = asks[slot][1]
        active = np.isin(np.arange(SLOTS), slots)
        rows = []
        for _ in range(5):
            out, logc, state, pages, _ = fns["step"](
                PARAMS, pages, state, tables, steps,
                np.ones((SLOTS,), np.float32), active)
            rows.append((np.asarray(out), np.asarray(logc)))
        return rows

    together = drive([0, 2])
    commits = np.array([[out[s][2 * B + 1] for s in (0, 2)]
                        for out, _ in together])
    # steps in which one row commits and the other does not
    assert (commits[:, 0] != commits[:, 1]).sum() >= 3
    for slot in asks:
        for (out, logc), (out1, logc1) in zip(together, drive([slot])):
            assert np.array_equal(out[slot], out1[slot])
            np.testing.assert_allclose(logc[slot], logc1[slot], atol=1e-5)


def test_softmax_route_and_layer_without_shared_expert():
    """``_route`` with a softmax over all experts and no bias, and the
    routed layer with no shared expert, against the reference."""
    w = PARAMS["layers"][0]
    x = np.random.default_rng(3).normal(size=(2, 6, 64)).astype(np.float32)
    chosen, weight = llama._route(w, jnp.asarray(x).reshape(12, 64), CFG.moe)
    p = jax.nn.softmax(jnp.asarray(x).reshape(12, 64) @ w["router"], -1)
    top, idx = jax.lax.top_k(p, 2)
    assert np.array_equal(np.asarray(chosen), np.asarray(idx))
    np.testing.assert_allclose(
        np.asarray(weight), np.asarray(top / top.sum(-1, keepdims=True)),
        rtol=1e-5)
    assert "ws_gate" not in w and "router_bias" not in w
    got = llama._moe_ffn(w, jnp.asarray(x), CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_ffn(w, jnp.asarray(x).reshape(12, 64), SHAPE)
    np.testing.assert_allclose(np.asarray(got).reshape(12, 64),
                               np.asarray(want), atol=2e-5)


def test_forward_under_the_block_causal_mask():
    tokens = np.random.default_rng(4).integers(0, MASK, (1, 22))
    got = llama.forward(PARAMS, jnp.asarray(tokens), CFG)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.forward(PARAMS, tokens[0], np.zeros(22, bool), SHAPE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def dense_block_attention(q, k, v, block):
    """q [T, H, D], k/v [T, Hkv, D]: query i sees key j iff
    j < (i // block + 1) * block; GQA by repeat; float64."""
    t, h, _ = q.shape
    k, v = (np.repeat(a, h // a.shape[1], 1).astype(np.float64)
            for a in (k, v))
    s = np.einsum("qhd,khd->hqk", q.astype(np.float64), k) / np.sqrt(
        q.shape[-1])
    i, j = np.arange(t)[:, None], np.arange(k.shape[0])[None, :]
    s = np.where(j < (i // block + 1) * block, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("block,block_q,block_k", [
    (4, 64, 64), (4, 64, 128), (8, 128, 64), (32, 64, 64), (1, 64, 64),
    (4, 256, 128), (4, 128, 256)])
def test_flash_attention_block_causal_matches_dense(block, block_q, block_k):
    rng = np.random.default_rng(5)
    t, h, d = 256, 4, 32
    q, k, v = (rng.normal(size=(1, t, h, d)).astype(np.float32)
               for _ in range(3))
    got = np.asarray(flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
        block_q=block_q, block_k=block_k, block_causal=block))[0]
    np.testing.assert_allclose(
        got, dense_block_attention(q[0], k[0], v[0], block), atol=2e-5)


@pytest.mark.parametrize("n_q,heads,kv_heads", [(4, 8, 4), (4, 8, 1),
                                               (2, 4, 4), (8, 8, 2)])
def test_paged_decode_attention_block_queries_match_dense(n_q, heads,
                                                          kv_heads):
    """The decode kernel with a block of queries a row: every query of a
    row attends all of the row's ``lengths`` keys (no mask among them)."""
    rng = np.random.default_rng(6)
    d, block = 16, 128
    lengths = np.array([n_q, 20, 128, 300, 384], np.int32)
    rows, n_pages, per_seq = len(lengths), 120, 384 // PAGE
    keys = rng.normal(size=(rows, 384, kv_heads, d)).astype(np.float32)
    vals = rng.normal(size=(rows, 384, kv_heads, d)).astype(np.float32)
    q = rng.normal(size=(rows, n_q, heads, d)).astype(np.float32)
    pool = np.zeros((1, 2, n_pages, PAGE, kv_heads, d), np.float32)
    tables = rng.permutation(n_pages)[:rows * per_seq].reshape(
        rows, per_seq).astype(np.int32)
    for r in range(rows):
        for p in range(per_seq):
            pool[0, 0, tables[r, p]] = keys[r, p * PAGE:(p + 1) * PAGE]
            pool[0, 1, tables[r, p]] = vals[r, p * PAGE:(p + 1) * PAGE]
    got = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), 0, jnp.asarray(tables),
        jnp.asarray(lengths), block_k=block))
    assert got.shape == q.shape
    for r, n in enumerate(lengths):
        # a block as long as the row: every query sees every key
        want = dense_block_attention(
            np.concatenate([np.zeros((n - n_q, heads, d), np.float32), q[r]]),
            keys[r, :n], vals[r, :n], int(n))[-n_q:]
        np.testing.assert_allclose(got[r], want, atol=2e-5, err_msg=str(n))


def test_block_step_on_the_paged_kernel_matches_the_dense_path():
    """The same request through the paged decode kernel and the flash
    prefill (``max_seq`` with a 128 block) and through the gather and
    dense path: the same records."""
    cfg = config(max_seq_kernel=True)
    kernel = llama.make_scheduler_fns(cfg, 128, SLOTS, page_size=PAGE)
    assert kernel["decode_attention"] == "paged_kernel"
    prompt = list(range(9, 9 + 18))
    got, *_ = serve_alone(kernel, prompt, 8, 2)
    dense = llama.make_scheduler_fns(CFG, 128, SLOTS, page_size=PAGE)
    assert dense["decode_attention"] == "gather_dense"
    assert_same_records(got, serve_alone(dense, prompt, 8, 2)[0])
    assert_same_records(got, reference(prompt, 8, 2)[1])


def _scheduler(fns):
    return DecodeScheduler(fns, PARAMS, SLOTS, MAX_SEQ)


REFUSED = {
    "park": lambda s: s.submit([1, 2], 4, on_finish=lambda rows: None),
    "resume_cache": lambda s: s.submit(
        [1, 2], 4, resume_cache=np.zeros(1), resume_pos=4),
    "kv_export": lambda s: s.submit([1, 2], 4, generation_id="g",
                                    kv_export=True),
    "kv_attach": lambda s: s.submit([1, 2], 4, attach_cache=np.zeros(1),
                                    attach_pos=1),
    "stream_resume": lambda s: s.resume("g", 0),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_block_configuration_refuses_by_name(fns, what):
    """What assumes one token a row a step is refused with a typed error
    where it is asked for, never served wrong; radix sharing and chunked
    prefill are off."""
    assert not fns["span_safe"]
    assert "gather" not in fns and "prefill_span" not in fns
    scheduler = _scheduler(fns)
    try:
        with pytest.raises(llama.UnsupportedArchitecture, match="blocks"):
            REFUSED[what](scheduler)
    finally:
        scheduler.close()


BAD_ASKS = {
    "steps_over_the_block": (dict(denoising_steps=5), CFG),
    "steps_zero": (dict(denoising_steps=0), CFG),
    "threshold_over_one": (dict(confidence_threshold=1.5), CFG),
    "steps_to_a_one_token_model": (dict(denoising_steps=2),
                                   llama.tiny(vocab=256)),
}


@pytest.mark.parametrize("case", sorted(BAD_ASKS))
def test_bad_block_settings_are_value_errors(case):
    kwargs, cfg = BAD_ASKS[case]
    scheduler = DecodeScheduler(
        llama.make_scheduler_fns(cfg, MAX_SEQ, SLOTS, page_size=PAGE), None,
        SLOTS, MAX_SEQ)
    try:
        with pytest.raises(ValueError):
            scheduler.submit([1, 2, 3], 4, **kwargs)
    finally:
        scheduler.close()


BAD_BUILDS = {
    "page_not_of_whole_blocks": lambda: llama.make_scheduler_fns(
        config(block_len=3), 48, 2, page_size=16),
    "window_layers": lambda: llama.make_scheduler_fns(
        config(layer_types=("window", "full"), window=32), 384, 2,
        page_size=PAGE),
    "single_stream": lambda: LlamaGenerateModel(cfg=CFG, max_slots=1),
    "int8": lambda: LlamaGenerateModel(cfg=CFG, max_slots=2, quantize=True),
}


@pytest.mark.parametrize("case", sorted(BAD_BUILDS))
def test_what_the_block_step_cannot_be_built_for(case):
    with pytest.raises(llama.UnsupportedArchitecture):
        BAD_BUILDS[case]()


@pytest.mark.parametrize("preset", ["tiny", "tiny_afmoe"])
def test_one_token_configuration_never_traces_the_block_step(preset):
    """A configuration with ``block_len`` 0 compiles the step it always
    did: its bundle carries logits, not a block state, and its lowering
    names none of the block step's scopes."""
    cfg = getattr(llama, preset)(vocab=256)
    assert cfg.block_len == 0
    max_seq = 384 if cfg.window_layers else 64
    import dataclasses
    if cfg.window_layers:
        cfg = dataclasses.replace(cfg, decode_impl="pallas")
    one = llama.make_scheduler_fns(cfg, max_seq, 2, page_size=PAGE)
    assert one["block_len"] == 0
    logits = jax.eval_shape(one["init_logits"])
    assert logits.shape == (2, 256) and logits.dtype == jnp.float32
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    b8 = jax.ShapeDtypeStruct((2,), jnp.bool_)
    tables = (i32(2, max_seq // PAGE) if not cfg.window_layers else
              {"full": i32(2, max_seq // PAGE),
               "window": i32(2, one["window_class"]["ring"])})
    text = one["step"].lower(
        params, jax.eval_shape(one["init_cache"]), logits, tables, i32(2), b8,
        i32(2), b8).as_text()
    assert "paged_scheduler_step" in text
    assert "diffusion." not in text and "paged_block_step" not in text


def test_block_step_carries_the_new_scopes(fns):
    params = jax.eval_shape(lambda: PARAMS)
    text = fns["step"].lower(
        params, jax.eval_shape(fns["init_cache"]),
        jax.eval_shape(fns["init_logits"]),
        jax.ShapeDtypeStruct((SLOTS, MAX_SEQ // PAGE), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.float32),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)).as_text(debug_info=True)
    for scope in ("diffusion.embed_block", "diffusion.unmask", "attn.kernel",
                  "attn.kv_write", "moe.route", "moe.experts", "head"):
        assert scope in text, scope
    assert "moe.shared" not in text and "sample" not in text


def test_block_stream_over_http_and_grpc():
    """One response a finished block over both frontends, with the
    request's ``denoising_steps``; the same blocks either way."""
    import tritonclient.grpc as grpcclient
    import tritonclient.http as httpclient
    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend

    core = InferenceServer([LlamaGenerateModel(
        cfg=llama.tiny_sdar(), max_seq=64, max_slots=4)])
    fes = [HttpFrontend(core, port=0).start(),
           GrpcFrontend(core, port=0).start()]
    prompt = [1, 5, 9, 13, 2, 6]
    try:
        client = httpclient.InferenceServerClient(
            fes[0].url.replace("http://", ""))
        over_http = [
            {o["name"]: o["data"] for o in event["outputs"]}
            for event in client.generate_stream(
                "llama_generate", {"PROMPT_IDS": prompt, "MAX_TOKENS": [8]},
                parameters={"denoising_steps": 2}, resume=False)]
        client.close()
        client = grpcclient.InferenceServerClient(fes[1].url)
        p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)], "INT32")
        p_in.set_data_from_numpy(np.asarray(prompt, np.int32))
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([8], np.int32))
        over_grpc = [
            {name: r.as_numpy(name).tolist() for name in (
                "TOKEN", "LOGPROB", "POSITION", "UNMASK_PASS")}
            for r in client.generate_stream(
                "llama_generate", [p_in, m_in], resume=False,
                parameters={"denoising_steps": 2})]
        # a request the block step refuses comes back typed, in band
        from tritonclient.utils import InferenceServerException
        with pytest.raises(InferenceServerException, match="blocks"):
            list(client.generate_stream(
                "llama_generate", [p_in, m_in], resume=False,
                parameters={"kv_park": True, "generation_id": "g1"}))
        client.close()
    finally:
        for fe in fes:
            fe.stop()
        core.close()
    assert [b["POSITION"] for b in over_http] == [[6, 7], [8, 9, 10, 11],
                                                  [12, 13]]
    assert all(max(b["UNMASK_PASS"]) <= 1 for b in over_http)
    for a, b in zip(over_http, over_grpc):
        assert a["TOKEN"] == b["TOKEN"] and a["UNMASK_PASS"] == b["UNMASK_PASS"]
        np.testing.assert_allclose(a["LOGPROB"], b["LOGPROB"], rtol=1e-5)
