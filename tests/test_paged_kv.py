"""Paged KV cache + radix prefix cache tests (ISSUE 11 tentpole).

The contracts under test:

- the host-side allocator/radix structures (``tpuserver.paging``):
  longest-prefix match, ref-count pinning vs LRU eviction, duplicate
  insertion surrendering the redundant page;
- **paged-vs-contiguous identity**: one batched decode step over the
  paged pool (page tables + gather/scatter) produces bitwise-identical
  tokens, logprobs, and cache CONTENT to the slotted step;
- **chunked-vs-one-shot identity**: a prompt prefilled in bounded
  chunks interleaved with decode emits byte-identical greedy tokens;
- page free-list exhaustion is a typed admission shed
  (``AdmissionQueueFull`` → 429 at the wire), never an OOM;
- shared prompt prefixes are served from the radix cache
  (``prefix_hits`` counts the skipped prompt tokens) with identical
  output, and cached pages evict LRU under pressure;
- admission is bounded by free PAGES, not slots: more concurrent
  streams than full-length sequences fit in the same memory.

Everything device-backed runs the tiny config on CPU-sim with small
pinned geometry per the tier-1 runtime budget.
"""

import dataclasses
import json
import os
import time

import numpy as np
import pytest

from tpuserver import faults
from tpuserver.models import llama
from tpuserver.paging import PageAllocator, RadixPrefixCache, pages_for
from tpuserver.scheduler import (
    AdmissionQueueFull,
    DeadlineExceeded,
    DecodeScheduler,
    SlotQuarantined,
)

CFG = llama.tiny(vocab=512)
MAX_SEQ = 64
PAGE = 16
PPSEQ = MAX_SEQ // PAGE


# -- host-side structures (no device) ----------------------------------------


def test_pages_for():
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2


def test_allocator_is_all_or_nothing():
    alloc = PageAllocator(4, 16)
    got = alloc.alloc(3)
    assert len(got) == 3 and alloc.free_count == 1
    # short grant refused outright — nothing leaks
    assert alloc.alloc(2) is None
    assert alloc.free_count == 1
    alloc.free(got)
    assert alloc.free_count == 4


def test_radix_match_pin_and_evict():
    radix = RadixPrefixCache(4)
    toks = list(range(12))
    assert radix.match(toks) == ([], [])
    created, dups, freed = radix.insert_tail([], toks, 0, [10, 11, 12],
                                             pin=False)
    assert [n.page for n in created] == [10, 11, 12]
    assert not dups and not freed
    assert radix.pages == 3 and radix.unreferenced == 3
    path, ids = radix.match(toks)
    assert ids == [10, 11, 12]
    # diverging suffix matches only the common full pages
    _, ids2 = radix.match(toks[:8] + [99, 98, 97, 96])
    assert ids2 == [10, 11]
    # pinned paths are eviction-proof (a live stream's pages)
    radix.acquire(path)
    assert radix.unreferenced == 0
    assert radix.evict(3) == []
    radix.release(path)
    # leaves evict first (page 12), then their parents
    assert radix.evict(1) == [12]
    assert radix.evict(5) == [11, 10]
    assert radix.pages == 0


def test_radix_duplicate_insert_surrenders_page():
    radix = RadixPrefixCache(4)
    toks = list(range(8))
    radix.insert_tail([], toks, 0, [1, 2], pin=False)
    # a concurrent sibling donating the same content loses its pages
    created, dups, freed = radix.insert_tail([], toks, 0, [7, 8],
                                             pin=True)
    assert dups == [(0, 1), (1, 2)]
    assert freed == [7, 8]
    assert radix.pages == 2  # nothing new entered
    # pin=True pinned the EXISTING nodes
    assert radix.unreferenced == 0
    radix.release(created)
    assert radix.unreferenced == 2


def test_radix_evicts_lru_leaf_first():
    radix = RadixPrefixCache(2)
    a, _, _ = radix.insert_tail([], [1, 2], 0, [0], pin=False)
    b, _, _ = radix.insert_tail([], [3, 4], 0, [1], pin=False)
    # touch branch a AFTER b was created: b is now the LRU leaf
    radix.acquire(a)
    radix.release(a)
    assert radix.evict(1) == [1]


# -- device-backed (tiny config, CPU-sim) ------------------------------------


@pytest.fixture(scope="module")
def params():
    import jax

    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def fns(params):
    """One default-geometry bundle shared across tests: the jits (and
    their compiles) are stateless, so schedulers can share them."""
    return llama.make_scheduler_fns(CFG, MAX_SEQ, 2)


@pytest.fixture(scope="module")
def fns_small(params):
    """4 decode rows over a pool that holds ONE full-length sequence:
    page pressure by construction."""
    return llama.make_scheduler_fns(CFG, MAX_SEQ, 4, kv_pages=PPSEQ)


def _collect(sched, prompt, n):
    return [t for t, _ in sched.submit(np.asarray(prompt, np.int32), n)]


def test_paged_step_matches_contiguous_kernel(params):
    """A/B at the kernel layer: admit the same prefilled prompt into
    the slotted cache and the paged pool (identity page tables), run
    one batched step each way, and require bitwise-equal tokens,
    logprobs, next logits, and cache CONTENT."""
    import jax.numpy as jnp

    prompt = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    true_len = len(prompt)
    slots = 2
    slot_cache = llama.init_kv_cache(CFG, 1, MAX_SEQ)
    logits_row, slot_cache = llama.prefill_to_length(
        params, slot_cache, jnp.asarray(prompt)[None, :], true_len, CFG)

    cache = llama.init_kv_cache(CFG, slots, MAX_SEQ)
    logits_c = jnp.zeros((slots, CFG.vocab), jnp.float32)
    cache, logits_c = llama.scheduler_admit(
        cache, logits_c, slot_cache, logits_row, 0)

    pages = llama.init_paged_kv_cache(CFG, slots * PPSEQ, PAGE)
    logits_p = jnp.zeros((slots, CFG.vocab), jnp.float32)
    dest = np.arange(PPSEQ, dtype=np.int32)  # identity mapping, slot 0
    pages, logits_p = llama.paged_admit(
        pages, logits_p, slot_cache, logits_row, dest, 0)

    positions = np.array([true_len, MAX_SEQ], np.int32)
    active = np.array([True, False])
    forced = np.zeros((slots,), np.int32)
    fmask = np.zeros((slots,), bool)
    tables = np.stack([np.arange(PPSEQ),
                       np.arange(PPSEQ, 2 * PPSEQ)]).astype(np.int32)

    for _ in range(3):
        t_c, lp_c, logits_c, cache = llama.scheduler_step(
            params, cache, logits_c, positions, active, forced, fmask,
            CFG)
        t_p, lp_p, logits_p, pages = llama.paged_scheduler_step(
            params, pages, logits_p, tables, positions, active, forced,
            fmask, CFG)
        np.testing.assert_array_equal(np.asarray(t_c), np.asarray(t_p))
        np.testing.assert_array_equal(np.asarray(lp_c), np.asarray(lp_p))
        np.testing.assert_array_equal(
            np.asarray(logits_c), np.asarray(logits_p))
        positions[0] += 1
    row = llama.paged_gather(pages, tables[0])
    np.testing.assert_array_equal(
        np.asarray(row), np.asarray(cache[:, :, 0:1]))


def _cell_geometry(config, dry_run=False):
    """``(max_seq, page_size)`` of a benchmark configuration's served
    model, read from the file the benchmark reads."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs", config)
    with open(path) as f:
        group = json.load(f)
    (entry,) = (group["dry_run"] if dry_run else group)["repository"]
    return entry["max_seq"], entry["page_size"]


# decode_impl (None: the default, "auto"), (max_seq, page), the path
DECODE_PATHS = {
    "kernel_256": ("pallas", (2560, 16), ("paged_kernel", 256)),
    "kernel_128": ("pallas", (384, 16), ("paged_kernel", 128)),
    # a block that would cut a page in two: gather, then the kernel
    "split_page": ("pallas", (384, 48), ("gather_kernel", 128)),
    # no 128-multiple block (every tier-1 MAX_SEQ = 64), or no kernel
    "no_block": ("pallas", (MAX_SEQ, PAGE), ("gather_dense", None)),
    "xla": ("xla", (2560, 16), ("gather_dense", None)),
    # "auto": the cost model's choice (dense for short rows)
    "auto_short": (None, (MAX_SEQ, PAGE), ("gather_dense", None)),
    # the accepted cells, with decode_impl as their model files leave
    # it: benchmark/models/llama_generate.py sets none, so the cost
    # model (decode_crossover_length) decides Mistral's two cells;
    # afmoe_generate.py passes "pallas".  A refit that sent either to
    # the dense path would fail here, not on the chip.
    "mistral_cells": (None, _cell_geometry("mistral-7b-v0.3-l20.json"),
                      ("paged_kernel", 256)),
    "trinity_cell": ("pallas",
                     _cell_geometry("trinity-large-preview-ep8-l5.json"),
                     ("paged_kernel", 256)),
    # the CPU dry run of Mistral's cells does NOT trace the kernel
    "mistral_dry_run": (None,
                        _cell_geometry("mistral-7b-v0.3-l20.json", True),
                        ("gather_dense", None)),
}


@pytest.mark.parametrize("case", sorted(DECODE_PATHS))
def test_paged_decode_path_follows_the_shapes(case):
    """Which decode attention the paged step is built with is read off
    the configuration and the geometry, nothing else."""
    impl, (max_seq, page), path = DECODE_PATHS[case]
    cfg = CFG if impl is None else dataclasses.replace(CFG, decode_impl=impl)
    assert cfg.decode_impl == (impl or "auto")
    assert llama.paged_decode_path(cfg, max_seq, page) == path
    assert llama.make_scheduler_fns(
        cfg, max_seq, 2, page_size=page)["decode_attention"] == path[0]


@pytest.mark.parametrize("max_seq, prompt_len", [
    (256, 40),    # one 256-token block a row (the benchmark's block)
    (384, 130),   # 128-token blocks: the prompt ends in the second,
                  # the third stays dead
])
def test_paged_kernel_step_matches_contiguous_kernel(
        params, max_seq, prompt_len):
    """The same A/B at a kernel-eligible geometry, where the paged step
    is built with the kernel that reads the pool in place
    (``paged_decode_path`` == "paged_kernel") and the contiguous step
    with ``decode_attention``: bitwise-equal tokens, logprobs, next
    logits and cache content over 3 steps, through SHUFFLED page
    tables with the sentinel past each row's reservation."""
    import jax.numpy as jnp

    # tiny resolves "auto" to dense at these lengths: state the kernel
    cfg = dataclasses.replace(CFG, decode_impl="pallas")
    assert llama.paged_decode_path(cfg, max_seq, PAGE)[0] == "paged_kernel"
    ppseq = max_seq // PAGE
    slots = 3
    prompt = (np.arange(1, prompt_len + 1) * 7 % 500).astype(np.int32)
    slot_cache = llama.init_kv_cache(cfg, 1, max_seq)
    logits_row, slot_cache = llama.prefill_to_length(
        params, slot_cache, jnp.asarray(prompt)[None, :], prompt_len, cfg)

    cache = llama.init_kv_cache(cfg, slots, max_seq)
    logits_c = jnp.zeros((slots, cfg.vocab), jnp.float32)
    pages = llama.init_paged_kv_cache(cfg, slots * ppseq, PAGE)
    logits_p = jnp.zeros((slots, cfg.vocab), jnp.float32)
    # rows 0 and 2 hold the prompt (row 1 is inert); a row reserves the
    # pages its prompt and 3 more tokens span, the rest is the sentinel
    physical = np.random.RandomState(3).permutation(
        slots * ppseq).reshape(slots, ppseq).astype(np.int32)
    reserved = np.arange(ppseq) * PAGE < prompt_len + 3
    tables = np.where(reserved[None, :], physical, slots * ppseq)
    tables[1] = slots * ppseq
    for slot in (0, 2):
        cache, logits_c = llama.scheduler_admit(
            cache, logits_c, slot_cache, logits_row, slot)
        pages, logits_p = llama.paged_admit(
            pages, logits_p, slot_cache, logits_row, tables[slot], slot)

    positions = np.array([prompt_len, max_seq, prompt_len], np.int32)
    active = np.array([True, False, True])
    forced = np.array([0, 0, 7], np.int32)   # row 2 replays a token
    fmask = np.array([False, False, True])

    for _ in range(3):
        t_c, lp_c, logits_c, cache = llama.scheduler_step(
            params, cache, logits_c, positions, active, forced, fmask,
            cfg)
        t_p, lp_p, logits_p, pages = llama.paged_scheduler_step(
            params, pages, logits_p, tables, positions, active, forced,
            fmask, cfg)
        np.testing.assert_array_equal(np.asarray(t_c), np.asarray(t_p))
        np.testing.assert_array_equal(np.asarray(lp_c), np.asarray(lp_p))
        np.testing.assert_array_equal(
            np.asarray(logits_c), np.asarray(logits_p))
        positions[[0, 2]] += 1
        fmask[2] = False
    for slot in (0, 2):   # unreserved pages gather as zeros
        row = llama.paged_gather(pages, tables[slot])
        np.testing.assert_array_equal(
            np.asarray(row), np.asarray(cache[:, :, slot:slot + 1]))


def test_chunked_prefill_token_identity(fns, params):
    """A 20-token prompt prefilled in 8-token chunks (interleaved with
    the decode loop) emits byte-identical greedy tokens to the one-shot
    bucketed prefill."""
    prompt = (np.arange(1, 21) * 7 % 500).astype(np.int32)
    one_shot = DecodeScheduler(fns, params, 2, MAX_SEQ,
                               prefill_chunk_tokens=None,
                               prefix_cache=False)
    chunked = DecodeScheduler(fns, params, 2, MAX_SEQ,
                              prefill_chunk_tokens=8,
                              prefix_cache=False)
    try:
        ref = _collect(one_shot, prompt, 8)
        got = _collect(chunked, prompt, 8)
        assert got == ref and len(ref) == 8
    finally:
        one_shot.close()
        chunked.close()


def test_page_exhaustion_sheds_typed(fns_small, params):
    """A pool too small for one more admission sheds TYPED (the
    AdmissionQueueFull → 429 contract), never an OOM — and only while
    live streams pin everything (nothing evictable)."""
    sched = DecodeScheduler(fns_small, params, 4, MAX_SEQ)
    try:
        # 3 of the 4 pages pinned by a live stream
        big = sched.submit(np.array([3, 1, 4, 1, 5], np.int32), 40)
        next(big)
        with pytest.raises(AdmissionQueueFull, match="page pool"):
            list(sched.submit(np.array([9, 8, 7], np.int32), 20))
        # the shed stream's failure must not have corrupted the live one
        assert sched.stats()["live_streams"] == 1
    finally:
        sched.close()


def test_shared_prefix_is_served_from_cache_identically(fns, params):
    """A sibling of an already-served prompt admits with its shared
    full pages served from the radix cache (prefix_hits counts the
    skipped prompt tokens) and emits identical greedy tokens."""
    prompt = (np.arange(1, 25) * 3 % 500).astype(np.int32)  # 24 tokens
    sched = DecodeScheduler(fns, params, 2, MAX_SEQ)
    try:
        cold = _collect(sched, prompt, 6)
        stats0 = sched.stats()
        assert stats0["prefix_hits"] == 0
        assert stats0["pages_cached"] >= 1  # retirement donated
        warm = _collect(sched, prompt, 6)
        assert warm == cold and len(cold) == 6
        stats = sched.stats()
        # at least one full 16-token page of the 24-token prompt shared
        assert stats["prefix_hits"] >= PAGE
        assert stats["prefix_misses"] >= 1
    finally:
        sched.close()


def test_cached_pages_evict_lru_under_pressure(fns_small, params):
    """Donated (unpinned) radix pages are reclaimed LRU when a new
    admission needs their memory — the admission succeeds and the
    eviction counter moves."""
    sched = DecodeScheduler(fns_small, params, 4, MAX_SEQ)
    try:
        prompts = [
            (np.arange(1, 31) * k % 500).astype(np.int32)
            for k in (3, 7, 11)
        ]
        for p in prompts:  # spans of 2 pages each over a 4-page pool
            assert len(_collect(sched, p, 2)) == 2
        stats = sched.stats()
        assert stats["prefix_evictions"] >= 1
        assert stats["pages_total"] == PPSEQ
    finally:
        sched.close()


def test_admission_bounded_by_pages_not_slots(params):
    """6 decode rows over a pool sized for TWO full-length sequences:
    six short streams all admit and decode CONCURRENTLY — the old
    ``max_slots`` slotted cache could never hold more streams than
    full-length rows at this memory."""
    fns6 = llama.make_scheduler_fns(CFG, MAX_SEQ, 6, kv_pages=2 * PPSEQ)
    sched = DecodeScheduler(fns6, params, 6, MAX_SEQ, prefix_cache=False)
    streams = []
    try:
        for i in range(6):
            # span 3 + 8 = 11 tokens -> ONE page each
            streams.append(sched.submit(
                np.array([i + 1, i + 2, i + 3], np.int32), 8))
        firsts = [next(s) for s in streams]
        assert len(firsts) == 6
        assert sched.stats()["live_streams"] == 6  # all live at once
        for s in streams:
            rest = list(s)
            assert len(rest) == 7  # 8 total, first already taken
    finally:
        sched.close()


# -- page accounting after retirement, by every route out of a slot ----------


@pytest.fixture(scope="module", params=["tiny", "tiny_afmoe"])
def pool(request):
    """``(fns, params, max_seq, prompt, n)`` of a one-class pool (with
    its radix cache) and of a two-class pool, whose window is 32 tokens:
    40 prompt tokens and 30 answers move it over two pages."""
    import jax

    if request.param == "tiny":
        return (request.getfixturevalue("fns"),
                request.getfixturevalue("params"), MAX_SEQ,
                (np.arange(1, 25) * 7 % 500).astype(np.int32), 20)
    cfg = dataclasses.replace(llama.tiny_afmoe(vocab=512),
                              attn_impl="pallas", decode_impl="pallas")
    return (llama.make_scheduler_fns(cfg, 384, 2, page_size=PAGE),
            llama.init_params(jax.random.PRNGKey(0), cfg), 384,
            (np.arange(1, 41) * 7 % 500).astype(np.int32), 30)


def _out_by_max_tokens(sched, prompt, n, ref):
    assert _collect(sched, prompt, n) == ref


def _out_by_eos(sched, prompt, n, ref):
    eos = ref[2]
    got = [t for t, _ in sched.submit(prompt, n, eos_id=eos)]
    assert got == ref[:ref.index(eos) + 1]


def _out_by_cancel(sched, prompt, n, ref):
    stream = sched.submit(prompt, n)
    assert next(stream)[0] == ref[0]
    stream.close()  # the consumer walks away mid-generation


def _out_by_deadline(sched, prompt, n, ref):
    stream = sched.submit(prompt, n)
    assert next(stream)[0] == ref[0]
    with sched._cond:
        (st,) = sched._streams
        assert st.emitted < n  # in a slot, mid-generation
        st.deadline = time.monotonic()
    with pytest.raises(DeadlineExceeded):
        list(stream)


def _out_by_quarantine(sched, prompt, n, ref):
    # the fourth step's logits row of slot 0 goes non-finite
    faults.install("scheduler.step", mode="nan", times=1, delay=0, skip=3)
    try:
        with pytest.raises(SlotQuarantined):
            list(sched.submit(prompt, n))
    finally:
        faults.clear("scheduler.step")
    assert sched.stats()["quarantined"] == 1


ROUTES_OUT = {
    "max_tokens": _out_by_max_tokens,
    "eos": _out_by_eos,
    "cancel": _out_by_cancel,
    "deadline": _out_by_deadline,
    "quarantine": _out_by_quarantine,
}


@pytest.mark.parametrize("route", sorted(ROUTES_OUT))
def test_pages_reconcile_after_retirement(pool, route):
    """Whichever way a stream leaves its slot, once the scheduler is
    idle every page of every class is free or held by the radix cache:
    nothing leaks and nothing is freed twice."""
    fns, params, max_seq, prompt, n = pool
    sched = DecodeScheduler(fns, params, 2, max_seq)
    try:
        ref = _collect(sched, prompt, n)
        assert len(ref) == n
        ROUTES_OUT[route](sched, prompt, n, ref)
        until = time.monotonic() + 60
        stats = sched.stats()
        while stats["live_streams"] or stats["pending"]:
            assert time.monotonic() < until, stats
            time.sleep(0.01)
            stats = sched.stats()
        assert stats["restarts"] == 0
        assert (stats["pages_free"] + stats["pages_cached"]
                == stats["pages_total"] > 0)
        assert stats["window_pages_free"] == stats["window_pages_total"]
        assert bool(stats["window_pages_total"]) == bool(fns["window_class"])
        assert stats["pages_cached"] > 0 or not fns["span_safe"]
    finally:
        sched.close()
