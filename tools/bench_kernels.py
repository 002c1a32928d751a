"""Pallas kernels on the real chip.

The flash prefill kernel over tile sizes at the shapes the benchmark's
cells prefill, and decode_attention against the padded-cache dense
decode at serving KV lengths — the two hot ops of the llama path
(tpuserver/ops/flash.py).  Prints one JSON line per (op, shape, impl).

A flash row is one (shape, T, block_q x block_k): milliseconds a call,
the block pairs the kernel steps through against the square grid's
(``ops.flash.flash_schedule``), the share of the chip's bf16 peak that
the seen scores' work (QK^T and PV over the pairs of positions the mask
leaves) takes at that time, and the largest difference from float32
dense attention over the first ``--ref-heads`` heads.  ``--against
FILE`` loads another version of ``ops/flash.py`` (another commit's,
say), times it at the same tiles and says whether the two outputs are
bit-equal.

Measurement hygiene (see docs/benchmarking.md): the op loop runs as a
lax.scan INSIDE one dispatch, two scan lengths are differenced to
cancel fixed dispatch cost, the clock stops on a host fetch of result
values, every timed round draws fresh input values, and fixed operands
(K, V, caches) are arguments of the scan, not constants baked into it.
Needs a TPU (``tpuserver.require_tpu``); an arm that fails is reported
on stderr and the exit code is non-zero.

Usage: python tools/bench_kernels.py [--quick] [--ops flash,decode]
       [--shapes gqa32,latent128] [--tiles 256x512,512x1024]
       [--against FILE]
"""

import argparse
import functools
import importlib.util
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src", "python"))

import numpy as np  # noqa: E402

import tpuserver  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpuserver.models.llama import LlamaConfig  # noqa: E402
from tpuserver.ops import decode_attention, flash, perf  # noqa: E402


def _dense_attn(q, k, v, scale, window=None, block_causal=None):
    """float32 attention of [1, T, H, D] inputs under the flash kernel's
    causal mask (and window, or block diagonal)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    t = q.shape[1]
    # iota comparison, not jnp.tril: a materialized [T, T] mask becomes
    # a T^2-byte constant baked into the executable
    i = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    last = i if not block_causal else (i // block_causal + 1) * block_causal - 1
    seen = j <= last
    if window:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _dense_decode(q, kc, vc, length):
    """XLA fallback for single-query decode over a padded cache."""
    n_rep = q.shape[1] // kc.shape[2]
    k = jnp.repeat(kc, n_rep, axis=2).astype(jnp.float32)
    v = jnp.repeat(vc, n_rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k) / np.sqrt(
        q.shape[-1])
    mask = jnp.arange(kc.shape[1])[None, None, :] < length[:, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, v).astype(q.dtype)


def _time_scanned(step, make_input, n_lo, n_hi, repeats=3, operands=()):
    """Per-call seconds for `step` (x -> x-shaped output), measured as a
    lax.scan of the op INSIDE one jit dispatch at two lengths and
    differenced: (t(n_hi) - t(n_lo)) / (n_hi - n_lo).  A per-dispatch
    wall-clock at microsecond op sizes is dominated by fixed
    dispatch+fence overhead; the difference of two scan lengths cancels
    every per-dispatch cost and leaves pure on-device op time.  The scan
    carry chains iterations, so nothing can be elided or overlapped.

    `make_input(i)` returns fresh values per round.  Within a round the
    two lengths may share an input (distinct executables).  `operands`
    are passed to `step(x, *operands)` as arguments of the scan's
    executable, not baked into it as constants.
    """
    from jax import lax

    def scanned(n):
        return jax.jit(
            lambda x, *ops: lax.scan(
                lambda c, _: (step(c, *ops), None), x, None, length=n)[0])

    f_lo, f_hi = scanned(n_lo), scanned(n_hi)

    def run(f, x):
        y = f(x, *operands)
        np.asarray(jax.tree_util.tree_leaves(y)[0]).ravel()[:2]

    warm = make_input(repeats)
    run(f_lo, warm)  # compile both
    run(f_hi, warm)

    best = None
    for r in range(repeats):
        x = make_input(r)
        # the input's host->device upload must complete BEFORE the
        # clock: an MB-scale operand's upload otherwise lands inside
        # t_lo only (the hi run reuses the resident buffer), making
        # t_hi < t_lo and the difference meaningless
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        run(f_lo, x)
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(f_hi, x)
        t_hi = time.perf_counter() - t0
        per = (t_hi - t_lo) / (n_hi - n_lo)
        if per > 0 and (best is None or per < best):
            best = per
    return best if best is not None else float("nan")


# (name, heads, T values, key head size, value head size, options):
# Mistral's 32 heads, Trinity's 48 (window and full layers), DeepSeek-V3's
# expanded 128 at 192 / 128, SDAR's block-causal 32, LFM2's 64-wide heads
FLASH_SHAPES = [
    ("gqa32", 32, (1024, 2048), 128, 128, {}),
    ("window48", 48, (1024, 8192), 128, 128, {"window": 4096}),
    ("full48", 48, (8192,), 128, 128, {}),
    ("latent128", 128, (4096, 8192), 192, 128, {"scale": 192 ** -0.5}),
    ("blocks32", 32, (1024, 4096), 128, 128, {"block_causal": 4}),
    ("narrow32", 32, (256, 1024), 64, 64, {}),
]
FLASH_TILES = [(128, 256), (256, 256), (256, 512), (512, 256), (512, 512),
               (512, 1024), (1024, 512)]


def seen_scores(t, window=None, block_causal=None):
    """(query, key) pairs the causal mask leaves at length t."""
    rows = np.arange(t)
    hi = rows if not block_causal else np.minimum(
        (rows // block_causal + 1) * block_causal - 1, t - 1)
    lo = np.maximum(rows - window + 1, 0) if window else 0
    return int((hi - lo + 1).sum())


def load_flash(path):
    """Another version of ``ops/flash.py``, on the chip's kernels."""
    spec = importlib.util.spec_from_file_location("other_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.set_kernel_mode(False)
    return mod


def bench_flash(name, heads, t, d, d_v, opts, tiles, peak, other=None,
                ref_heads=2, seconds=0.3):
    """One row a tile of ``tiles`` that divides t; returns the number of
    tiles that failed (reported on stderr)."""
    opts = dict(opts)
    scale = opts.pop("scale", d ** -0.5)
    window, block_causal = opts.get("window"), opts.get("block_causal")
    rng = np.random.default_rng(t * 7 + heads)
    q, k = (jnp.asarray(rng.standard_normal((1, t, heads, d)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, t, heads, d_v)), jnp.bfloat16)
    h_ref = min(ref_heads, heads)
    want = _dense_attn(q[:, :, :h_ref], k[:, :, :h_ref], v[:, :, :h_ref],
                       scale, window, block_causal)
    work = 2 * heads * (d + d_v) * seen_scores(t, window, block_causal)
    # the longer scan holds ~`seconds` of device work at 30 % of peak
    n_hi = min(2000, max(2, math.ceil(seconds / (work / (0.3 * peak)))))
    n_lo = max(1, n_hi // 8)

    def make_q(i):
        r = np.random.default_rng(t * 131 + i)
        return jnp.asarray(r.standard_normal((1, t, heads, d)), jnp.bfloat16)

    def timed(call):
        # the scan carries q; the output lands in its first lanes
        sec = _time_scanned(
            lambda x, k, v: x.at[..., :d_v].set(call(x, k, v).astype(x.dtype)),
            make_q, n_lo, n_hi, repeats=2, operands=(k, v))
        return round(sec * 1e3, 4), round(100 * work / sec / peak, 2)

    failed = 0
    for bq, bk in tiles:
        if t % bq or t % bk:
            continue
        row = dict(opts, op="flash_attention", shape=name, heads=heads, T=t,
                   d=d, d_v=d_v, block_q=bq, block_k=bk)
        try:
            kernels = [("", flash)] + ([("other_", other)] if other else [])
            outs = {}
            for prefix, mod in kernels:
                call = jax.jit(functools.partial(
                    mod.flash_attention, causal=True, scale=scale,
                    block_q=bq, block_k=bk, **opts))
                outs[prefix] = call(q, k, v)
                row[prefix + "ms"], row[prefix + "roofline"] = timed(call)
            out = outs[""]
            row.update(
                pairs=len(flash.flash_schedule(
                    t, t, bq, bk, True, window, block_causal)[0]),
                grid_pairs=(t // bq) * (t // bk),
                ref_max_abs=float(jnp.max(jnp.abs(
                    out[:, :, :h_ref].astype(jnp.float32) - want))))
            if other:
                row["bit_equal"] = bool(jnp.array_equal(out, outs["other_"]))
            print(json.dumps(row), flush=True)
        except Exception as e:  # noqa: BLE001 — later tiles still run;
            # the failure is reported and fails the exit code
            failed += 1
            row["error"] = str(e)[:300]
            print(json.dumps(row), file=sys.stderr, flush=True)
    return failed


def bench_decode(S, length_frac, heads, kv_heads, d, scan_lens, spec):
    rng = np.random.RandomState(S % 9973)
    kc = jnp.asarray(
        rng.standard_normal((1, S, kv_heads, d)).astype(np.float32),
        jnp.bfloat16)
    vc = jnp.asarray(
        rng.standard_normal((1, S, kv_heads, d)).astype(np.float32),
        jnp.bfloat16)
    length = jnp.asarray([int(S * length_frac)], jnp.int32)
    # bytes actually needed: the valid prefix of K and V (the pallas
    # kernel's length-clamped index map skips the dead tail; dense
    # streams the whole padded cache)
    live_bytes = 2 * int(S * length_frac) * kv_heads * d * 2
    padded_bytes = 2 * S * kv_heads * d * 2

    dense_step = lambda q: _dense_decode(q, kc, vc, length)  # noqa: E731
    pallas_step = lambda q: decode_attention(  # noqa: E731
        q, kc, vc, length, block_k=256)
    def make_q(i):
        r = np.random.RandomState(S * 137 + i)
        return jnp.asarray(
            r.standard_normal((1, heads, d)).astype(np.float32),
            jnp.bfloat16)

    results = {}
    for name, fn, nbytes in (
            ("xla_dense", dense_step, padded_bytes),
            ("pallas_decode", pallas_step, live_bytes)):
        dt = _time_scanned(fn, make_q, scan_lens[0], scan_lens[1])
        results[name] = dt
        print(json.dumps({
            "op": "decode_attention", "S": S,
            "valid": int(S * length_frac), "impl": name,
            "us": round(dt * 1e6, 1),
            "mbu": round(perf.mbu(nbytes, dt, spec), 4),
        }), flush=True)
    print(json.dumps({
        "op": "decode_attention", "S": S,
        "pallas_speedup": round(results["xla_dense"] /
                                results["pallas_decode"], 3),
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="one flash row (Mistral's heads at T=2048, the "
                         "preferred tile) and one decode case")
    ap.add_argument("--ops", default="flash,decode")
    ap.add_argument("--shapes", help="names of FLASH_SHAPES, comma-separated")
    ap.add_argument("--tiles", help="flash tiles, e.g. 256x512,512x1024")
    ap.add_argument("--against", help="another version of ops/flash.py")
    ap.add_argument("--ref-heads", type=int, default=2)
    args = ap.parse_args()
    tpuserver.enable_compile_cache()
    spec = perf.chip_spec(tpuserver.require_tpu())
    ops = args.ops.split(",")
    failed = 0

    if "flash" in ops:
        other = load_flash(args.against) if args.against else None
        tiles = FLASH_TILES
        shapes = FLASH_SHAPES
        if args.quick:
            tiles = [(LlamaConfig.flash_block_q, LlamaConfig.flash_block_k)]
            shapes = [("gqa32", 32, (2048,), 128, 128, {})]
        if args.tiles:
            tiles = [tuple(int(b) for b in tile.split("x"))
                     for tile in args.tiles.split(",")]
        if args.shapes:
            shapes = [s for s in shapes if s[0] in args.shapes.split(",")]
        for name, heads, ts, d, d_v, opts in shapes:
            for t in ts:
                failed += bench_flash(name, heads, t, d, d_v, opts, tiles,
                                      spec.peak_bf16_flops, other,
                                      args.ref_heads)

    if "decode" in ops:
        heads, kv_heads, d = 16, 8, 128  # llama3-class head geometry
        decode_cases = (
            [(2048, 0.5)] if args.quick
            else [(2048, 0.25), (8192, 0.25), (8192, 1.0),
                  (32768, 0.25), (32768, 1.0)])
        decode_lens = (512, 4096) if args.quick else (512, 8192)
        for S, frac in decode_cases:
            try:
                bench_decode(S, frac, heads, kv_heads, d, decode_lens, spec)
            except Exception as e:  # noqa: BLE001 — later arms still run;
                # the failure is reported and fails the exit code
                failed += 1
                print(json.dumps(dict(S=S, frac=frac, op="decode_attention",
                                      error=str(e)[:200])),
                      file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
