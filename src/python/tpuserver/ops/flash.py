"""Pallas flash-attention forward kernel for TPU.

The hot op of the llama serving/training paths, hand-tiled for the MXU:
the grid walks (batch*heads, live block pair), the pairs being the
(query block, K/V block) pairs in which the mask leaves a score
(:func:`flash_schedule`, a scalar-prefetched table), so VMEM only ever
holds one [block_q, D] query tile and one [block_k, D] K/V tile —
sequence length is bounded by HBM, not VMEM — and a K/V block wholly
masked for a query block is never fetched.  The online-softmax state
(running max, normalizer, output accumulator) lives in VMEM scratch
carried across a query block's pairs; accumulation is fp32 (MXU-native
via preferred_element_type) regardless of input dtype.

The decode kernels (``decode_attention`` over a padded cache,
``paged_decode_attention`` over the page pool read in place) answer one
query a row with every head at once and share one body,
:func:`_decode_fold`: two MXU dots a block over the block as it lies in
the cache, grouped-query attention being a mask on the scores.
``latent_decode_attention`` is their sibling over a latent page pool
(multi-head latent attention, absorbed form): one shared key a position,
brought in once, whose leading lanes are also the value.

Kernel mode (Mosaic or the Pallas interpreter) is decided in one place,
:func:`kernel_interpret`: a process states it with
:func:`set_kernel_mode` (chip entry points state Mosaic through
``tpuserver.require_tpu``; an AOT pre-flight lowering for a TPU topology
from a CPU process must state it too), and only a process that stated
nothing gets the mode of its default backend — interpret off-TPU, so
tests pin the kernels against the dense reference on the CPU mesh.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the process-wide kernel mode: None = not stated (follow the default
# backend); True/False = stated by set_kernel_mode
_stated_interpret = None


def set_kernel_mode(interpret):
    """State the Pallas kernel mode for this process: ``False`` =
    Mosaic (a non-TPU backend then fails to lower instead of quietly
    interpreting), ``True`` = the interpreter, ``None`` = follow the
    default backend again.  Kernels read it at trace time, so state it
    before the first trace."""
    global _stated_interpret
    _stated_interpret = interpret


def kernel_interpret(interpret=None):
    """Whether a kernel traced now runs in the Pallas interpreter: the
    call's own ``interpret`` argument if given, else the mode the
    process stated, else by default backend."""
    if interpret is not None:
        return interpret
    if _stated_interpret is not None:
        return _stated_interpret
    return jax.default_backend() != "tpu"


def _online_softmax_fold(s, m_scr, l_scr, acc_scr, pv):
    """One block of the flash recurrence over scores ``s`` [rows, bk].

    Updates the carried (m, l, acc) scratch; ``pv(p)`` supplies the
    probability-value product in whatever block layout the kernel uses.
    Fully-masked rows keep m == -inf, and exp(-inf - -inf) is nan, so
    the shift is pinned to a finite value there.
    """
    m = m_scr[:, 0]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - shift[:, None])
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - shift), 0.0)
    l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
    acc_scr[:] = acc_scr[:] * alpha[:, None] + pv(p)
    m_scr[:, 0] = m_new


def _fold_init(m_scr, l_scr, acc_scr):
    """The carried state before a row's first block."""
    m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _fold_finish(o_ref, m_scr, l_scr, acc_scr):
    """Normalize the carried accumulator into the output block."""
    del m_scr
    l = l_scr[:, 0]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[:] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)


# the flags of a (q-block, k-block) pair in :func:`flash_schedule`'s
# table: the q-block's first pair (start its softmax state), its last
# (normalize into the output block), and a block some score of which is
# masked (only there is the mask built)
FIRST, LAST, PARTIAL = 1, 2, 4


@functools.lru_cache(maxsize=None)
def flash_schedule(t, t_kv, block_q, block_k, causal=True, window=None,
                   block_causal=None):
    """The (q-block, k-block) pairs in which the mask leaves a score,
    in the order :func:`flash_attention` folds them: q-blocks in order,
    and within one its k-blocks ascending.

    Query i sees key j iff ``lo(i) <= j <= hi(i)``: ``hi`` is ``i``
    under ``causal`` (the end of i's block of ``block_causal``
    positions under a block diagonal), else the last key; ``lo`` is
    ``i - window + 1`` under a window, else 0.  Returns three read-only
    int32 arrays of one entry a pair, ``(q_blocks, k_blocks, flags)``,
    ``flags`` holding :data:`FIRST`, :data:`LAST` and :data:`PARTIAL`
    bits.  Pure host arithmetic, cached by its arguments."""
    nq, nk = t // block_q, t_kv // block_k
    rows = np.arange(t)
    if not causal:
        hi = np.full(t, t_kv - 1)
    elif block_causal:
        hi = (rows // block_causal + 1) * block_causal - 1
    else:
        hi = rows
    hi = np.minimum(hi, t_kv - 1).reshape(nq, block_q, 1)
    lo = (np.maximum(rows - window + 1, 0) if window
          else np.zeros(t, np.int64)).reshape(nq, block_q, 1)
    first = np.arange(nk) * block_k
    last = first + block_k - 1
    # [nq, nk]: some row of the q-block sees some key of the k-block /
    # every row sees every key
    live = (np.maximum(lo, first) <= np.minimum(hi, last)).any(axis=1)
    whole = ((lo <= first) & (hi >= last)).all(axis=1)
    if not live.any(axis=1).all():
        raise ValueError("a query block of {} sees no key of {}".format(
            t, t_kv))
    q_blocks, k_blocks = np.nonzero(live)
    new_q = np.ones(len(q_blocks) + 1, bool)
    new_q[1:-1] = q_blocks[1:] != q_blocks[:-1]
    flags = (np.where(new_q[:-1], FIRST, 0) | np.where(new_q[1:], LAST, 0)
             | np.where(whole[q_blocks, k_blocks], 0, PARTIAL))
    table = tuple(a.astype(np.int32) for a in (q_blocks, k_blocks, flags))
    for a in table:
        a.flags.writeable = False
    return table


def _attn_kernel(
    qb_ref, kb_ref, flag_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
    acc_scr, *, scale, block_q, block_k, window=None, block_causal=None):
    """One (batch*head, pair) program: pair ``p`` of
    :func:`flash_schedule`'s table, whose q-block ``qb_ref[p]`` and
    k-block ``kb_ref[p]`` the index maps brought in.

    q_ref: [block_q, D]; k_ref: [block_k, D]; v_ref: [block_k, Dv];
    o_ref: [block_q, Dv]; scratch m/l: [block_q, 1] fp32, acc:
    [block_q, Dv] fp32 — carried across the (sequential) pairs of one
    q-block.  Only a pair flagged :data:`PARTIAL` builds the mask: with
    ``window`` query i sees keys j with 0 <= i - j < window; with
    ``block_causal`` = B the diagonal is one of blocks of B positions,
    query i seeing key j iff ``j < (i // B + 1) * B``.
    """
    p = pl.program_id(1)
    flags = flag_ref[p]

    @pl.when((flags & FIRST) != 0)
    def _init():
        _fold_init(m_scr, l_scr, acc_scr)

    def fold(masked):
        # keep the matmul operands in the INPUT dtype: bf16 x bf16 with
        # fp32 accumulation is the MXU's native full-rate mode — an
        # explicit fp32 upcast before the dot would halve the peak.
        # The softmax state stays fp32 (preferred_element_type).
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = qb_ref[p] * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb_ref[p] * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            last_seen = q_pos
            if block_causal is not None:
                last_seen = (q_pos // block_causal + 1) * block_causal - 1
            seen = k_pos <= last_seen
            if window is not None:
                seen = jnp.logical_and(seen, k_pos > q_pos - window)
            s = jnp.where(seen, s, -jnp.inf)
        _online_softmax_fold(
            s, m_scr, l_scr, acc_scr,
            lambda probs: jnp.dot(
                probs.astype(v.dtype), v,
                preferred_element_type=jnp.float32))

    partial = (flags & PARTIAL) != 0
    pl.when(partial)(functools.partial(fold, True))
    pl.when(jnp.logical_not(partial))(functools.partial(fold, False))

    @pl.when((flags & LAST) != 0)
    def _finish():
        _fold_finish(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret",
                     "window", "block_causal"),
)
def flash_attention(
    q, k, v, causal=True, scale=None, block_q=128, block_k=128,
    interpret=None, window=None, block_causal=None):
    """Exact attention, q/k [B, T, H, D], v [B, T, H, Dv] -> [B, T, H, Dv]
    (Dv = D everywhere but under latent attention's expanded form, whose
    keys carry 192 lanes and whose values 128).

    Drop-in for the XLA attention paths; T must be divisible by
    ``block_q`` and ``block_k`` (pick smaller blocks for short or odd
    sequences).  ``interpret=None`` defers to :func:`kernel_interpret`.
    ``window`` (causal only): query i attends keys j with
    0 <= i - j < window.  ``block_causal`` = B (causal only; generation
    by diffusion over blocks): query i attends keys j with
    ``j < (i // B + 1) * B``, every earlier block and its own block
    whole.  The grid steps through :func:`flash_schedule`'s pairs alone:
    a K/V block wholly masked for a q-block is neither fetched nor
    folded, and only a block the mask's edge crosses is masked.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    interpret = kernel_interpret(interpret)
    b, t, h, d = q.shape
    t_kv, d_v = k.shape[1], v.shape[-1]
    block_q = min(block_q, t)
    block_k = min(block_k, t_kv)
    if t % block_q or t_kv % block_k:
        raise ValueError(
            "sequence lengths ({}, {}) must divide by block sizes "
            "({}, {})".format(t, t_kv, block_q, block_k))
    if (window is not None or block_causal is not None) and not causal:
        raise ValueError("a window or a block diagonal needs causal "
                         "attention")
    if window is not None and block_causal is not None:
        raise ValueError("no window under a block diagonal")

    # [B, T, H, D] -> [B*H, T, D]: one grid row per (batch, head)
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * h, t_kv, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * h, t_kv, d_v)

    table = flash_schedule(t, t_kv, block_q, block_k, causal, window,
                           block_causal)
    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        window=window, block_causal=block_causal)

    def q_index(bh, p, qb_ref, kb_ref, flag_ref):
        return (bh, qb_ref[p], 0)

    def kv_index(bh, p, qb_ref, kb_ref, flag_ref):
        return (bh, kb_ref[p], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(table),
        grid=(b * h, len(table[0])),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_index),
            pl.BlockSpec((None, block_k, d), kv_index),
            pl.BlockSpec((None, block_k, d_v), kv_index),
        ],
        out_specs=pl.BlockSpec((None, block_q, d_v), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, t, d_v), q.dtype),
        interpret=interpret,
    )(*(jnp.asarray(a) for a in table), qh, kh, vh)
    return out.reshape(b, h, t, d_v).transpose(0, 2, 1, 3)


def _decode_fold(
    q_ref, k, v, ki, length, m_scr, l_scr, acc_scr, *, scale, block_k,
    n_rep, start=None, n_q=1):
    """Fold K/V block ``ki`` of a row with ``length`` valid positions
    into the carried softmax state, on the MXU.  The one body of both
    decode kernels: they differ only in how the block got into VMEM.

    ``k``/``v`` are the block as it lies in the cache, viewed 2-D:
    [block_k * Hkv, D], column ``c`` holding position ``c // Hkv`` of KV
    head ``c % Hkv``.  Two dots a block, operands in the cache's dtype,
    fp32 accumulation: scores ``q . k^T`` [H, block_k * Hkv] of EVERY
    query head against every KV head, then the columns of another
    head's group masked to -inf with the positions past ``length`` (and
    before ``start``, the row's first position still inside its
    window).  Their probabilities are exact zeros, so ``p . v`` is
    already the grouped [H, D]: GQA costs the MXU Hkv times the needed
    products and no repeat, relayout or per-head slice of the block.
    (With ONE KV head, a latent pool's, there is no other group to mask.)

    ``n_q`` queries a row (a block of a diffusion step; all see the same
    ``length`` positions) are ``n_q * H`` query rows of the same two
    dots, row ``r`` being head ``r % H`` of query ``r // H``.
    """
    heads, cols = q_ref.shape[0], k.shape[0]
    h_kv = heads // n_q // n_rep
    s = jax.lax.dot_general(
        q_ref[:].astype(k.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [H, bk * Hkv]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    if h_kv > 1:
        head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
        if n_q > 1:
            head = jax.lax.rem(head, heads // n_q)
        group = jax.lax.div(head, n_rep)
    # position c // Hkv < length  <=>  c < (length - first) * Hkv
    first = ki * block_k
    seen = col < (length - first) * h_kv
    if start is not None:
        seen = jnp.logical_and(seen, col >= (start - first) * h_kv)
    if h_kv > 1:
        seen = jnp.logical_and(seen, jax.lax.rem(col, h_kv) == group)
    s = jnp.where(seen, s, -jnp.inf)
    _online_softmax_fold(
        s, m_scr, l_scr, acc_scr,
        lambda p: jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32))


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale,
    block_k, n_rep):
    """One (batch, k-block) program of single-query decode attention.

    len_ref: scalar-prefetch [batch] int32 valid lengths; q_ref: [H, D]
    (every query head of this batch row); k_ref/v_ref:
    [block_k * Hkv, D] cache slices (:func:`_decode_fold`'s 2-D view);
    scratch m/l: [H, 1] fp32, acc: [H, D] fp32 carried across k blocks.
    """
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        _fold_init(m_scr, l_scr, acc_scr)

    # skip blocks entirely past the valid cache prefix
    @pl.when(ki * block_k < length)
    def _fold():
        _decode_fold(
            q_ref, k_ref[:], v_ref[:], ki, length, m_scr, l_scr, acc_scr,
            scale=scale, block_k=block_k, n_rep=n_rep)

    @pl.when(ki == nk - 1)
    def _finish():
        _fold_finish(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(
    q, k_cache, v_cache, lengths, scale=None, block_k=256,
    interpret=None):
    """Single-token decode attention over a padded KV cache.

    q: [B, H, D] (the current token's queries); k_cache/v_cache:
    [B, S, Hkv, D] with valid prefix ``lengths`` [B] int32; GQA
    (H = Hkv * n_rep) is a mask on the scores (:func:`_decode_fold`) —
    no expanded cache exists anywhere.  Returns [B, H, D].
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    interpret = kernel_interpret(interpret)
    b, h, d = q.shape
    s = k_cache.shape[1]
    h_kv = k_cache.shape[2]
    if h % h_kv:
        raise ValueError(
            "query heads ({}) must be a multiple of kv heads ({})".format(
                h, h_kv))
    n_rep = h // h_kv
    block_k = min(block_k, s)
    if s % block_k:
        raise ValueError(
            "cache length {} must divide by block_k {}".format(s, block_k))

    def _kv_index(b, ki, len_ref):
        # clamp dead iterations (past the valid prefix) onto the last
        # live block: Pallas elides the re-fetch of an already-resident
        # block, so padded cache tail bytes are never DMA'd from HBM
        live_blocks = jax.lax.div(
            len_ref[b] + (block_k - 1), block_k)
        ki_eff = jnp.minimum(ki, jnp.maximum(live_blocks - 1, 0))
        return (b, ki_eff, 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_rep=n_rep)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // block_k),
        in_specs=[
            pl.BlockSpec((None, h, d), lambda b, ki, *refs: (b, 0, 0)),
            pl.BlockSpec((None, block_k * h_kv, d), _kv_index),
            pl.BlockSpec((None, block_k * h_kv, d), _kv_index),
        ],
        out_specs=pl.BlockSpec(
            (None, h, d), lambda b, ki, *refs: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    # the fold's 2-D view of a block: merging adjacent dims moves nothing
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache.reshape(b, s * h_kv, d),
      v_cache.reshape(b, s * h_kv, d))


def _paged_decode_kernel(
    len_ref, tbl_ref, layer_ref, *refs, scale, block_k, n_rep, windowed,
    n_q=1):
    """One (row, k-block) program of decode attention over the page pool.

    The fold is :func:`_decode_kernel`'s; only the way a K/V block gets
    into VMEM differs.  len_ref [rows], tbl_ref [rows * pages_per_seq]
    (row-major page table, entries in [0, n_pages)) and layer_ref [1]
    are scalar-prefetched; pages_ref is the WHOLE pool, left where it
    lives, viewed [L, 2, n_pages, page * Hkv, D].  k_buf/v_buf
    [2, block_k * Hkv, D] are two VMEM slots, each filled by one DMA a
    page; sems [2 (k, v), 2 (slot)]; slot_ref [1] SMEM says which slot
    holds the current block.  Both grid dimensions run in order, so the
    block after this one, the next row's first where this is the row's
    last, is on its way while this one folds.  A row's first block is
    always brought in (even at length 0, where nothing folds), so every
    row has a block to wait for and the hand-over stays regular.

    ``windowed`` (static): a fourth prefetched ref, start_ref [rows],
    gives each row's first position still inside its window, and a row's
    table is a RING: logical block ``lb`` of the sequence lives in table
    block ``lb % nk``, so a row holds one window (plus a block) of pages
    however long it has grown.  Program ``ki`` of a row then folds
    logical block ``start // block_k + ki``: blocks wholly behind the
    window are never brought in.  Without it a row's first block is 0
    and the table is read straight.
    """
    start_ref = refs[0] if windowed else None
    (q_ref, pages_ref, o_ref, k_buf, v_buf, sems, slot_ref, m_scr, l_scr,
     acc_scr) = refs[1:] if windowed else refs
    b = pl.program_id(0)
    ki = pl.program_id(1)
    rows = pl.num_programs(0)
    nk = pl.num_programs(1)
    page_rows = pages_ref.shape[3]                    # page * Hkv
    pages_per_block = k_buf.shape[1] // page_rows
    pages_per_seq = nk * pages_per_block
    layer = layer_ref[0]
    length = len_ref[b]
    live_blocks = jax.lax.div(length + (block_k - 1), block_k)
    # first_blk: the row's first live logical block; lb: this program's
    start, first_blk, lb = None, 0, ki
    if windowed:
        start = start_ref[b]
        first_blk = jax.lax.div(start, block_k)
        live_blocks = live_blocks - first_blk
        lb = first_blk + ki
    live_blocks = jnp.maximum(live_blocks, 1)

    def block_copies(row, blk, slot):
        entry = jax.lax.rem(blk, nk) if windowed else blk
        first = row * pages_per_seq + entry * pages_per_block
        return [
            pltpu.make_async_copy(
                pages_ref.at[layer, kv, tbl_ref[first + j]],
                buf.at[slot, pl.ds(j * page_rows, page_rows)],
                sems.at[kv, slot])
            for j in range(pages_per_block)
            for kv, buf in ((0, k_buf), (1, v_buf))
        ]

    @pl.when(ki == 0)
    def _init():
        _fold_init(m_scr, l_scr, acc_scr)

    @pl.when(jnp.logical_and(b == 0, ki == 0))
    def _first():
        slot_ref[0] = 0
        for copy in block_copies(0, first_blk, 0):
            copy.start()

    @pl.when(ki < live_blocks)
    def _block():
        slot = slot_ref[0]
        more = ki + 1 < live_blocks
        nxt_row = jnp.where(more, b, b + 1)
        nxt_first = 0
        if windowed:
            nxt_first = jax.lax.div(
                start_ref[jnp.minimum(b + 1, rows - 1)], block_k)
        nxt_blk = jnp.where(more, lb + 1, nxt_first)

        @pl.when(nxt_row < rows)
        def _prefetch():
            for copy in block_copies(nxt_row, nxt_blk, 1 - slot):
                copy.start()

        for copy in block_copies(b, lb, slot):
            copy.wait()
        slot_ref[0] = 1 - slot

        @pl.when(lb * block_k < length)
        def _fold():
            _decode_fold(
                q_ref, k_buf[slot], v_buf[slot], lb, length, m_scr, l_scr,
                acc_scr, scale=scale, block_k=block_k, n_rep=n_rep,
                start=start, n_q=n_q)

    @pl.when(ki == nk - 1)
    def _finish():
        _fold_finish(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret", "n_q"))
def paged_decode_attention(
    q, pages, layer, page_tables, lengths, scale=None, block_k=256,
    interpret=None, starts=None, n_q=1):
    """:func:`decode_attention` over a page pool, read in place.

    q: [B, H, D], or [B, Q, H, D] for Q queries a row that all attend
    the row's ``lengths`` positions (a block of a diffusion step: no
    mask among them; the fold's dots then carry Q * H query rows);
    pages: the whole pool [L, 2, n_pages, page, Hkv, D]
    (``models.llama.init_paged_kv_cache``), of which layer ``layer``
    (int32 scalar, may be traced) is attended; page_tables
    [B, pages_per_seq] int32 names each row's physical pages, every
    entry in [0, n_pages) — the kernel copies pages by id, and an id out
    of range is a wild read, not a dropped one; lengths [B] int32 valid
    positions.  The kernel brings each row's live blocks into VMEM page
    by page: no gathered ``[B, S, Hkv, D]`` view and no per-layer slice
    of the pool ever exists in HBM.  Same blocks, same order, same fold
    as ``decode_attention`` over the gathered view: bit-equal to it.

    With ``starts`` [B] int32 (the first position of each row still
    inside its attention window; positions before it are masked and
    their blocks never read) the page table is a ring of
    ``pages_per_seq`` pages: logical page ``p`` of a row is table entry
    ``p % pages_per_seq`` (:func:`_paged_decode_kernel`), so
    ``lengths`` may pass ``pages_per_seq * page`` while ``lengths -
    starts`` fits the ring less one block.
    Returns q's shape.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    interpret = kernel_interpret(interpret)
    if q.ndim == 4:
        b, n_q, h, d = q.shape
        return paged_decode_attention(
            q.reshape(b, n_q * h, d), pages, layer, page_tables, lengths,
            scale=scale, block_k=block_k, interpret=interpret,
            starts=starts, n_q=n_q).reshape(q.shape)
    b, rows, d = q.shape
    h = rows // n_q
    page, h_kv = pages.shape[3], pages.shape[4]
    s = page_tables.shape[1] * page
    if h % h_kv:
        raise ValueError(
            "query heads ({}) must be a multiple of kv heads ({})".format(
                h, h_kv))
    block_k = min(block_k, s)
    if s % block_k or block_k % page:
        raise ValueError(
            "block_k {} must divide the row length {} and hold whole "
            "pages of {}".format(block_k, s, page))

    windowed = starts is not None
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_k=block_k,
        n_rep=h // h_kv, windowed=windowed, n_q=n_q)
    prefetch = [lengths.astype(jnp.int32),
                page_tables.astype(jnp.int32).reshape(-1),
                jnp.asarray(layer, jnp.int32).reshape(1)]
    if windowed:
        prefetch.append(starts.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, s // block_k),
        in_specs=[
            pl.BlockSpec((None, rows, d), lambda b, ki, *refs: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, rows, d), lambda b, ki, *refs: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_k * h_kv, d), pages.dtype),
            pltpu.VMEM((2, block_k * h_kv, d), pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    # the fold's 2-D view of a page: merging adjacent dims moves nothing
    # (the compiled step holds a bitcast of the pool, no copy)
    pages = pages.reshape(*pages.shape[:3], page * h_kv, d)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        # the slot hand-over needs every program to run in grid order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_decode_attention",
        interpret=interpret,
    )(*prefetch, q, pages)


def _latent_decode_kernel(
    len_ref, tbl_ref, layer_ref, q_ref, pages_ref, o_ref, buf, sems,
    slot_ref, m_scr, l_scr, acc_scr, *, scale, block_k, d_v):
    """One (row, block) program of decode attention over a LATENT page
    pool (multi-head latent attention, absorbed form): every head's
    query [H, W] against the row's cached latents, ONE shared key a
    position, whose first ``d_v`` lanes are also its value.

    :func:`_paged_decode_kernel`'s hand-over with one buffer where that
    has two: pages_ref is the whole pool [L, n_pages, page, W], a block
    [block_k, W] is brought into VMEM once, a DMA a page, and
    :func:`_decode_fold` reads it as the key (all W lanes) and as the
    value (a lane slice of the same block): ``H`` query rows against one
    KV head."""
    b = pl.program_id(0)
    ki = pl.program_id(1)
    rows = pl.num_programs(0)
    nk = pl.num_programs(1)
    page = pages_ref.shape[2]
    pages_per_block = block_k // page
    pages_per_seq = nk * pages_per_block
    layer = layer_ref[0]
    length = len_ref[b]
    live_blocks = jnp.maximum(
        jax.lax.div(length + (block_k - 1), block_k), 1)

    def block_copies(row, blk, slot):
        first = row * pages_per_seq + blk * pages_per_block
        return [
            pltpu.make_async_copy(
                pages_ref.at[layer, tbl_ref[first + j]],
                buf.at[slot, pl.ds(j * page, page)],
                sems.at[slot])
            for j in range(pages_per_block)
        ]

    @pl.when(ki == 0)
    def _init():
        _fold_init(m_scr, l_scr, acc_scr)

    @pl.when(jnp.logical_and(b == 0, ki == 0))
    def _first():
        slot_ref[0] = 0
        for copy in block_copies(0, 0, 0):
            copy.start()

    @pl.when(ki < live_blocks)
    def _block():
        slot = slot_ref[0]
        more = ki + 1 < live_blocks
        nxt_row = jnp.where(more, b, b + 1)
        nxt_blk = jnp.where(more, ki + 1, 0)

        @pl.when(nxt_row < rows)
        def _prefetch():
            for copy in block_copies(nxt_row, nxt_blk, 1 - slot):
                copy.start()

        for copy in block_copies(b, ki, slot):
            copy.wait()
        slot_ref[0] = 1 - slot

        @pl.when(ki * block_k < length)
        def _fold():
            rows_kv = buf[slot]
            _decode_fold(
                q_ref, rows_kv, rows_kv[:, :d_v], ki, length, m_scr, l_scr,
                acc_scr, scale=scale, block_k=block_k,
                n_rep=q_ref.shape[0])

    @pl.when(ki == nk - 1)
    def _finish():
        _fold_finish(o_ref, m_scr, l_scr, acc_scr)


@functools.partial(
    jax.jit, static_argnames=("d_v", "scale", "block_k", "interpret"))
def latent_decode_attention(
    q, pages, layer, page_tables, lengths, d_v, scale, block_k=256,
    interpret=None):
    """Decode attention in the latent space over a latent page pool,
    read in place: the absorbed form of multi-head latent attention.

    q: [B, H, W], every head's query already carried into the latent
    space (``[W_UK q_nope ; q_pe]``, zero in the row's padding lanes);
    pages: the whole pool [L, n_pages, page, W]
    (``models.llama.init_paged_kv_cache`` of a latent configuration),
    a row ``[c_kv ; k_pe ; padding]`` a cached token, of which layer
    ``layer`` is attended; page_tables [B, pages_per_seq] int32 with
    every entry in [0, n_pages) and lengths [B] int32 as
    :func:`paged_decode_attention` takes them.  A row's live blocks come
    into VMEM once, page by page, and serve as key (all W lanes, scores
    times ``scale``) and as value (the first ``d_v`` lanes).  Returns
    ``sum_j softmax_j(s) c_kv(j)`` [B, H, d_v]: the caller carries it
    back through the value up-projection."""
    interpret = kernel_interpret(interpret)
    b, h, w = q.shape
    if pages.ndim != 4 or pages.shape[3] != w or d_v > w:
        raise ValueError(
            "a latent pool is [L, n_pages, page, {}] (got {}) and holds "
            "its value in the first d_v ({}) lanes".format(
                w, pages.shape, d_v))
    page = pages.shape[2]
    s = page_tables.shape[1] * page
    block_k = min(block_k, s)
    if s % block_k or block_k % page:
        raise ValueError(
            "block_k {} must divide the row length {} and hold whole "
            "pages of {}".format(block_k, s, page))
    kernel = functools.partial(
        _latent_decode_kernel, scale=scale, block_k=block_k, d_v=d_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, s // block_k),
        in_specs=[
            pl.BlockSpec((None, h, w), lambda b, ki, *refs: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (None, h, d_v), lambda b, ki, *refs: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_k, w), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d_v), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d_v), q.dtype),
        # the slot hand-over needs every program to run in grid order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="latent_decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32),
      page_tables.astype(jnp.int32).reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pages)
