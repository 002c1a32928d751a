"""Grouped expert matmul for TPU: ``moe_grouped_matmul``.

The one kernel of a routed feed-forward layer, for prefill and decode
alike.  Rows of ``lhs`` are token-expert pairs SORTED by expert; group
``g`` (``group_sizes[g]`` consecutive rows) multiplies ``rhs[g]``.  The
grid walks only the (row tile, group) pairs that hold rows: group
offsets, the group and the row tile of every grid step are
scalar-prefetched, the grid's length is the number of such pairs, and
the block index of ``rhs`` follows the group: an expert no row chose
reads no weight, and rows past ``sum(group_sizes)`` (pairs routed to
experts held elsewhere) are never touched (their output rows are
unwritten memory: the caller masks them).

The tiling of rows among groups is the published Megablox scheme
(``jax.experimental.pallas.ops.tpu.megablox``): a row tile shared by
several groups is visited once per group and each visit stores only its
own rows.  Accumulation is float32 whatever the operands are.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from tpuserver.ops.flash import kernel_interpret

# preferred (rows, k, n) tile: 2 MiB of bf16 weights a grid step, two in
# flight, so the weight stream of a decode step stays ahead of the MXU
TILING = (128, 1024, 1024)


def _tile(pref, size, align):
    """Largest tile <= ``pref`` that divides ``size`` in steps of
    ``align``; the whole of a ``size`` no such tile divides."""
    t = min(pref, size)
    t -= t % align
    while t >= align and size % t:
        t -= align
    return t if t >= align else size


def _gmm_kernel(offsets_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref,
                acc_scr, *, tm, tiles_k):
    """One (n tile, row-tile visit, k tile) program: accumulate over k,
    then store the rows of this visit's group."""
    visit = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jnp.dot(
        lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32)

    @pl.when(ki == tiles_k - 1)
    def _store():
        group = group_ref[visit]
        row = tile_ref[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_scr.shape, 0)
        mine = jnp.logical_and(row >= offsets_ref[group],
                               row < offsets_ref[group + 1])
        out_ref[...] = jnp.where(
            mine, acc_scr[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def moe_grouped_matmul(lhs, rhs, group_sizes, tiling=TILING, interpret=None):
    """``out[rows of group g] = lhs[rows of group g] @ rhs[g]``.

    lhs: [M, K] pairs sorted by group; rhs: [G, K, N]; group_sizes: [G]
    int32 with ``sum <= M``.  Returns [M, N] in ``lhs.dtype``; rows past
    the sum are NOT written.  M is padded here to a whole number of row
    tiles (a multiple of 8)."""
    interpret = kernel_interpret(interpret)
    m, k = lhs.shape
    groups, _, n = rhs.shape
    tm = min(tiling[0], -(-m // 8) * 8)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tk, tn = _tile(tiling[1], k, 128), _tile(tiling[2], n, 128)
    tiles_k, tiles_n = k // tk, n // tn
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m_pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=False)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles_n, visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ni, v, ki, off, grp, tile: (tile[v], ki)),
                pl.BlockSpec((None, tk, tn),
                             lambda ni, v, ki, off, grp, tile:
                             (grp[v], ki, ni)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda ni, v, ki, off, grp, tile: (tile[v], ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(offsets, group_ids, tile_ids, lhs, rhs)
    return out[:m]
