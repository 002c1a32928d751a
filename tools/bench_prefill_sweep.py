"""End-to-end llama prefill/decode sweep on the real chip.

The tuning companion to bench_full's config-5 rows: sweeps the arms that
decide the serving defaults —

- prefill: dense XLA vs the flash kernel at the configuration's tiles,
  plus an attention-IDENTITY arm (flash patched out) that decomposes
  prefill time into "matmul+elementwise" vs "attention";
- decode: xla vs pallas vs auto at several contexts and chunk sizes,
  bf16 vs int8 weights.

Hygiene (docs/benchmarking.md): every timed arm chains K dispatches with
DISTINCT inputs (each consuming the previous result) and stops the clock
on ONE np.asarray value fence, so fixed dispatch cost amortizes K ways
and nothing can be answered from a content cache.

Usage:
  python tools/bench_prefill_sweep.py [--config llama3_3b] [--t 2048]
      [--prefill-only | --decode-only] [--rounds 4]
"""

import argparse
import dataclasses
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

from tpuserver.models import llama  # noqa: E402
from tpuserver.ops import perf  # noqa: E402


def time_prefill(cfg, params, T, max_seq, rounds, seed0):
    """Mean seconds per prefill: `rounds` chained prefills with distinct
    prompts (each prompt's first token depends on the previous logits)
    + one value fence."""
    prefill_j = jax.jit(functools.partial(llama.prefill, cfg=cfg))
    cache = llama.init_kv_cache(cfg, 1, max_seq)
    prompts = [
        jnp.asarray(np.random.RandomState(seed0 + i).randint(
            0, cfg.vocab, (1, T)).astype(np.int32))
        for i in range(rounds + 1)
    ]
    lg, cache = prefill_j(params, cache, prompts[-1])  # compile
    np.asarray(lg)
    # warm the chaining helper ops outside the window (hygiene rule 5)
    warm = prompts[-1].at[0, 0].set(
        jnp.argmax(lg[0]).astype(jnp.int32) % cfg.vocab)
    lg, cache = prefill_j(params, cache, warm)
    np.asarray(lg)
    t0 = time.perf_counter()
    for toks in prompts[:rounds]:
        chained = toks.at[0, 0].set(
            jnp.argmax(lg[0]).astype(jnp.int32) % cfg.vocab)
        lg, cache = prefill_j(params, cache, chained)
    np.asarray(lg)
    return (time.perf_counter() - t0) / rounds


def time_decode(cfg, params, ctx, chunk, max_seq, rounds, seed0):
    """tokens/sec: prefill to `ctx`, then chain `rounds` decode_chunk
    dispatches + one fence."""
    prefill_j = jax.jit(functools.partial(llama.prefill, cfg=cfg))
    decode_j = jax.jit(
        functools.partial(llama.decode_chunk, cfg=cfg, chunk=chunk),
        donate_argnums=(1,),
    )
    cache = llama.init_kv_cache(cfg, 1, max_seq)
    prompt = jnp.asarray(np.random.RandomState(seed0).randint(
        0, cfg.vocab, (1, ctx)).astype(np.int32))
    logits, cache = prefill_j(params, cache, prompt)
    toks, lps, logits, cache = decode_j(params, cache, logits, ctx)
    np.asarray(toks)  # compile + settle
    pos = ctx + chunk
    n = min(rounds, (max_seq - pos) // chunk)
    if n < 1:
        raise ValueError("no room to decode past ctx")
    t0 = time.perf_counter()
    for _ in range(n):
        toks, lps, logits, cache = decode_j(params, cache, logits, pos)
        pos += chunk
    np.asarray(toks)
    dt = time.perf_counter() - t0
    return n * chunk / dt, ctx + chunk * (n // 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama3_3b")
    ap.add_argument("--t", type=int, default=2048)
    ap.add_argument("--max-seq", type=int, default=3072)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--prefill-only", action="store_true")
    ap.add_argument("--decode-only", action="store_true")
    args = ap.parse_args()

    tpuserver.enable_compile_cache()
    spec = perf.chip_spec(tpuserver.require_tpu())
    base = getattr(llama, args.config)()
    params = llama.init_params(jax.random.PRNGKey(0), base)
    jax.block_until_ready(params)
    pf = perf.prefill_flops(base, args.t)
    failed = 0  # arms that raised: reported as rows, and in the exit code

    if not args.decode_only:
        # decomposition arm: attention replaced by identity (patched
        # flash) — isolates the matmul+elementwise cost
        import tpuserver.ops as ops_mod

        real_flash = ops_mod.flash_attention
        arms = [
            ("xla_dense", dict(attn_impl="xla"), None),
            # the flash kernel at the configuration's preferred tiles
            # (tools/bench_kernels.py sweeps the kernel's tiles alone)
            ("flash", dict(attn_impl="pallas"), None),
            ("attention_identity", dict(attn_impl="pallas"),
             lambda q, k, v, **kw: q),
        ]
        for i, (name, overrides, patch) in enumerate(arms):
            cfg = dataclasses.replace(base, **overrides)
            if patch is not None:
                ops_mod.flash_attention = patch
            try:
                dt = time_prefill(
                    cfg, params, args.t, args.max_seq, args.rounds,
                    seed0=1000 * (i + 1))
            except Exception as e:  # noqa: BLE001 — report arm failures
                failed += 1
                print(json.dumps({
                    "phase": "prefill", "arm": name,
                    "error": str(e)[:200]}), flush=True)
                continue
            finally:
                ops_mod.flash_attention = real_flash
            print(json.dumps({
                "phase": "prefill", "config": args.config, "T": args.t,
                "arm": name, "ms": round(dt * 1e3, 2),
                "mfu": round(perf.mfu(pf, dt, spec), 4),
            }), flush=True)

    if not args.prefill_only:
        qparams = llama.quantize_params(params)
        jax.block_until_ready(qparams)
        for wname, wparams, wbytes in (
                ("bf16", params, 2), ("int8", qparams, 1)):
            for impl in ("xla", "pallas", "auto"):
                for chunk in (32, 64):
                    for ctx in (512, 2048):
                        cfg = dataclasses.replace(base, decode_impl=impl)
                        try:
                            rate, ctx_mid = time_decode(
                                cfg, wparams, ctx, chunk, args.max_seq,
                                2 * args.rounds,
                                seed0=hash((wname, impl, chunk, ctx))
                                % 100000)
                        except Exception as e:  # noqa: BLE001
                            failed += 1
                            print(json.dumps({
                                "phase": "decode", "arm": impl,
                                "weights": wname, "chunk": chunk,
                                "ctx": ctx, "error": str(e)[:200],
                            }), flush=True)
                            continue
                        bpt = perf.decode_bytes_per_token(
                            base, ctx_mid, weight_bytes_per_param=wbytes)
                        mbu = perf.mbu(bpt * rate, 1.0, spec)
                        print(json.dumps({
                            "phase": "decode", "config": args.config,
                            "weights": wname, "impl": impl,
                            "chunk": chunk, "ctx": ctx_mid,
                            "tokens_per_sec": round(rate, 1),
                            "mbu": round(mbu, 4),
                        }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
