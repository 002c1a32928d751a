"""Pallas kernels vs their XLA fallbacks on the real chip.

Times flash_attention against the dense jnp attention at serving
sequence lengths, and decode_attention against the padded-cache dense
decode at serving KV lengths — the two hot ops of the llama path
(tpuserver/ops/flash.py).  Prints one JSON line per (op, shape, impl).

Measurement hygiene (see docs/benchmarking.md): the op loop runs as a
lax.scan INSIDE one dispatch, two scan lengths are differenced to
cancel fixed dispatch cost, the clock stops on a host fetch of result
values, and every timed round draws fresh input values.  Needs a TPU
(``tpuserver.require_tpu``); an arm that fails is reported on stderr and
the exit code is non-zero.

Usage: python tools/bench_kernels.py [--quick]
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src", "python"))

import numpy as np  # noqa: E402

import tpuserver  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tpuserver.ops import decode_attention, flash_attention  # noqa: E402
from tpuserver.ops import perf  # noqa: E402


def _dense_attn(q, k, v, causal=True):
    """The XLA fallback: one fused softmax(QK^T)V."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[1]
        # iota comparison, not jnp.tril: a materialized [T, T] mask
        # becomes a T^2-byte constant baked into the executable (1 GB
        # at T=32768)
        rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        s = jnp.where((cols <= rows)[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


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


def _time_scanned(step, make_input, n_lo, n_hi, repeats=3):
    """Per-call seconds for `step` (x -> x-shaped output), measured as a
    lax.scan of the op INSIDE one jit dispatch at two lengths and
    differenced: (t(n_hi) - t(n_lo)) / (n_hi - n_lo).  A per-dispatch
    wall-clock at microsecond op sizes is dominated by fixed
    dispatch+fence overhead; the difference of two scan lengths cancels
    every per-dispatch cost and leaves pure on-device op time.  The scan
    carry chains iterations, so nothing can be elided or overlapped.

    `make_input(i)` returns fresh values per round.  Within a round the
    two lengths may share an input (distinct executables).
    """
    from jax import lax

    def scanned(n):
        return jax.jit(
            lambda x: lax.scan(
                lambda c, _: (step(c), None), x, None, length=n)[0])

    f_lo, f_hi = scanned(n_lo), scanned(n_hi)

    def run(f, x):
        y = f(x)
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


def bench_flash(T, heads, d, scan_lens, spec):
    rng = np.random.RandomState(T % 9973)
    k = jnp.asarray(
        rng.standard_normal((1, T, heads, d)).astype(np.float32),
        jnp.bfloat16)
    v = jnp.asarray(
        rng.standard_normal((1, T, heads, d)).astype(np.float32),
        jnp.bfloat16)
    # chain on q: out has q's shape; k/v stay fixed
    flops = 4 * T * T // 2 * heads * d  # causal QK^T + PV

    dense_step = lambda q: _dense_attn(q, k, v)  # noqa: E731
    flash_step = lambda q: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=256, block_k=256)
    def make_q(i):
        r = np.random.RandomState(T * 131 + i)
        return jnp.asarray(
            r.standard_normal((1, T, heads, d)).astype(np.float32),
            jnp.bfloat16)

    results = {}
    for name, fn in (("xla_dense", dense_step),
                     ("pallas_flash", flash_step)):
        dt = _time_scanned(fn, make_q, scan_lens[0], scan_lens[1])
        results[name] = dt
        print(json.dumps({
            "op": "flash_attention", "T": T, "heads": heads, "d": d,
            "impl": name, "ms": round(dt * 1e3, 3),
            "mfu": round(perf.mfu(flops, dt, spec), 4),
        }), flush=True)
    print(json.dumps({
        "op": "flash_attention", "T": T,
        "pallas_speedup": round(results["xla_dense"] /
                                results["pallas_flash"], 3),
    }), flush=True)


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
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    tpuserver.enable_compile_cache()
    spec = perf.chip_spec(tpuserver.require_tpu())
    heads, kv_heads, d = 16, 8, 128  # llama3-class head geometry

    # scan lengths sized so the long run holds >=~0.5 s of device work,
    # dwarfing dispatch noise
    flash_lens = {2048: (64, 1024), 8192: (8, 128), 32768: (1, 8)}
    if args.quick:
        flash_lens = {2048: (64, 512)}
    decode_cases = (
        [(2048, 0.5)] if args.quick
        else [(2048, 0.25), (8192, 0.25), (8192, 1.0),
              (32768, 0.25), (32768, 1.0)])
    decode_lens = (512, 4096) if args.quick else (512, 8192)
    arms = [("flash_attention", {"T": T},
             functools.partial(bench_flash, T, heads, d, lens, spec))
            for T, lens in flash_lens.items()]
    arms += [("decode_attention", {"S": S, "frac": frac},
              functools.partial(bench_decode, S, frac, heads, kv_heads, d,
                                decode_lens, spec))
             for S, frac in decode_cases]
    failed = 0
    for op, shape, run in arms:
        try:
            run()
        except Exception as e:  # noqa: BLE001 — later arms still run;
            # the failure is reported and fails the exit code
            failed += 1
            print(json.dumps(dict(shape, op=op, error=str(e)[:200])),
                  file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
