#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip.  NOT a benchmark: every time it prints is a smoke timing.

    python chip_smoke.py            # on a machine with one TPU chip

ONE process (a chip belongs to one process at a time): the server, both
frontends and every client run as threads of it.  No arguments, no
network beyond loopback, fixed seeds, random weights.  In order:

  platform   place the compile cache, initialise JAX, fail unless the
             first device is a TPU the peaks table knows
  kernels    flash_attention and decode_attention, Mosaic, at the
             llama3_3b head geometry, and paged_decode_attention at the
             benchmark cells' (16 rows x 2560, pages of 16, 32/8 heads
             of 128; 48/8 heads with ``starts=`` over a wrapped ring of
             a 4,096-token window), against float32 host references
  setup      InferenceServer(default models + ResNet-50 + llama3_3b on
             the continuous-batching scheduler) behind real HTTP and
             gRPC frontends; warm-up requests carry the compiles
  simple     `simple` over the HTTP client
  resnet50   over gRPC in-band, then through XLA shared memory with a
             jax.Array in and a device-resident region out
  llama      8 concurrent decoupled gRPC generations (flash- and
             dense-length prompts) through the scheduler
  reference  each prompt's first token against llama.forward(xla)
  mosaic     tpu_custom_call count of the served step and prefills
  metrics    /metrics, scheduler restarts, peak HBM

Any failed phase is a non-zero exit.  The last stdout line is the result,
one JSON object with exactly two keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

`"ok": true` only when every phase passed; the device is what JAX
reports.  Everything else (per-phase pass/fail, set-up and serving
seconds, versions, custom-call counts, peak HBM) is the `summary` log
line just above it.  Off-chip it exits non-zero naming the platform it
found and prints no result.  `--dry-run-cpu` is an explicit opt-in,
never a fallback: the same control flow at the `tiny` config with the
kernels in the Pallas interpreter, its summary marked `"dry_run": true`
and its result line naming the platform it ran on (`cpu`).
"""

import argparse
import concurrent.futures
import dataclasses
import functools
import importlib.metadata
import json
import os
import sys
import time
import traceback
import urllib.request

T_START = time.monotonic()
REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src", "python"))

# bf16 keeps 8 mantissa bits.  Both kernels round their output to bf16
# (relative 2^-9) and flash_attention also rounds the probabilities
# before the PV product; with O(1) values that is <= ~2e-2 absolute,
# while a wrong mask, scale or block index moves outputs by O(0.1-1).
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# The served first token comes from a padded prefill through the flash
# kernel and the KV cache; the reference is llama.forward with dense
# attention on the same bf16 weights.  The two differ by bf16 rounding
# accumulated over the layers, so a near-tie in the top logits may
# legitimately flip: the served token must score within this many logit
# units of the reference's best (random-weight logits are ~N(0,1) over
# the vocabulary, so a wrong token scores several units lower), and its
# served log-probability must agree with the reference's to the same.
FIRST_TOKEN_TOL = 0.25


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything that differs between the chip run and the dry run."""
    heads: int          # kernel phase: query heads
    kv_heads: int
    head_dim: int
    seq: int            # kernel phase: flash T and decode cache length
    paged: tuple        # kernel phase, paged decode: (rows, table
                        # tokens, page, query heads, window or None)
                        # each, over kv_heads of head_dim; with a window
                        # the table is a ring and rows grow to twice it
    max_seq: int        # served llama
    prompt_lens: tuple  # (flash, flash, dense) prompt lengths
    max_tokens: int


CHIP = Size(heads=24, kv_heads=8, head_dim=128, seq=2048,
            paged=((16, 2560, 16, 32, None), (16, 4352, 16, 48, 4096)),
            max_seq=2048,
            prompt_lens=(512, 1536, 200), max_tokens=32)
DRY = Size(heads=4, kv_heads=2, head_dim=32, seq=512,
           paged=((10, 512, 16, 4, None), (12, 512, 16, 6, 200)),
           max_seq=512,
           prompt_lens=(128, 384, 50), max_tokens=8)
MAX_SLOTS = 8


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print("[smoke +{:7.1f}s] {}".format(time.monotonic() - T_START, msg),
          flush=True)


# -- phases -------------------------------------------------------------------


def phase_platform(dry_run):
    import jax
    import jaxlib

    import tpuserver
    from tpuserver.ops import flash

    cache_dir = tpuserver.enable_compile_cache()
    if dry_run:
        flash.set_kernel_mode(interpret=True)
        device = jax.devices()[0]
    else:
        device = tpuserver.require_tpu()
    info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    versions = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
    }
    log("device {} | versions {} | compile cache {} ({})".format(
        info, versions, cache_dir,
        "from JAX_COMPILATION_CACHE_DIR"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "repo default"))
    return device, info, versions


def _dense_attention_f32(q, k, v):
    """Causal softmax(QK^T/sqrt(d))V on the host in float32.
    q/k/v: [T, H, D] numpy float32."""
    import numpy as np

    t = q.shape[0]
    s = np.matmul(q.transpose(1, 0, 2), k.transpose(1, 2, 0))
    s /= np.sqrt(q.shape[-1])
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.matmul(p, v.transpose(1, 0, 2)).transpose(1, 0, 2)


def phase_kernels(size, interpret):
    """The Pallas kernels, called directly with the mode stated, at the
    served geometries, against float32 host references."""
    import jax.numpy as jnp
    import numpy as np

    from tpuserver.ops import (
        decode_attention, flash_attention, paged_decode_attention)

    rng = np.random.RandomState(0)
    h, hkv, d, t = size.heads, size.kv_heads, size.head_dim, size.seq

    def bf16(shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32),
                           jnp.bfloat16)

    # flash takes K/V already expanded to the query head count (the
    # model's GQA expansion happens before the kernel)
    q, k, v = bf16((1, t, h, d)), bf16((1, t, h, d)), bf16((1, t, h, d))
    want = _dense_attention_f32(
        *(np.asarray(x[0], np.float32) for x in (q, k, v)))
    for bq, bk in ((256, 512), (128, 128)):
        got = np.asarray(
            flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                            interpret=interpret)[0], np.float32)
        check(np.isfinite(got).all(), "flash {}x{}: non-finite".format(bq, bk))
        err = float(np.abs(got - want).max())
        log("flash_attention T={} H={} D={} tiles {}x{}: max|err| {:.4f}"
            .format(t, h, d, bq, bk, err))
        check(np.allclose(got, want, rtol=KERNEL_RTOL, atol=KERNEL_ATOL),
              "flash_attention {}x{} disagrees with the float32 dense "
              "reference: max|err| {}".format(bq, bk, err))

    # decode: 8 cache rows of mixed valid length, block edges included
    lengths = np.array(
        [1, 17, 256, 257, t // 2 - 24, 3 * t // 4, t - 1, t], np.int32)
    qd = bf16((len(lengths), h, d))
    kc, vc = bf16((len(lengths), t, hkv, d)), bf16((len(lengths), t, hkv, d))
    got = np.asarray(
        decode_attention(qd, kc, vc, jnp.asarray(lengths),
                         interpret=interpret), np.float32)
    kf, vf = np.asarray(kc, np.float32), np.asarray(vc, np.float32)
    _check_decode_rows(
        "decode_attention over S={}".format(t), got, qd, lengths,
        lambda b: (kf[b, :lengths[b]], vf[b, :lengths[b]]))

    # the served decode attention: the same fold over a page pool read
    # in place, at the benchmark cells' geometries; every row's pages are
    # scattered over the pool, layer 1 of 2 is attended.  Straight table:
    # entries past a row's length are the clipped sentinel (the last
    # page).  With a window the table is a ring (logical page p in entry
    # p % entries), rows reach twice the window, and ``starts=`` masks
    # what lies before it.
    for rows, seq, page, h, window in size.paged:
        ppseq = seq // page
        n_pages = rows * ppseq
        top = seq if window is None else 2 * window + 512
        lengths = np.array(
            ([1, page, page + 1, 255, 256, 257, seq // 2 - 24, seq - 1, seq,
              top - 255, top]
             + [int(n) for n in rng.randint(1, top, rows)])[:rows], np.int32)
        starts = np.maximum(lengths - (window or top), 0)
        tables = rng.permutation(n_pages).reshape(
            rows, ppseq).astype(np.int32)
        if window is None:
            live = np.arange(ppseq)[None, :] * page < lengths[:, None]
            tables = np.where(live, tables, n_pages - 1)
        qd = bf16((rows, h, d))
        pool = bf16((2, 2, n_pages, page, hkv, d))
        got = np.asarray(
            paged_decode_attention(
                qd, pool, 1, jnp.asarray(tables), jnp.asarray(lengths),
                interpret=interpret,
                starts=None if window is None else jnp.asarray(starts)),
            np.float32)
        pf = np.asarray(pool[1], np.float32)

        def row_kv(b):
            pos = np.arange(starts[b], lengths[b])
            entry = tables[b][pos // page % ppseq]
            return pf[0][entry, pos % page], pf[1][entry, pos % page]

        _check_decode_rows(
            "paged_decode_attention over {} pages of {}{}".format(
                n_pages, page,
                "" if window is None else ", window {}".format(window)),
            got, qd, lengths, row_kv)


def _check_decode_rows(name, got, q, lengths, row_kv):
    """``got`` [rows, H, D] against single-query softmax attention on
    the host in float32; ``row_kv(b)`` -> K and V [n, Hkv, D] float32 of
    the positions row ``b`` attends (``lengths[b]`` is for the log)."""
    import numpy as np

    check(np.isfinite(got).all(), "{}: non-finite".format(name))
    qf = np.asarray(q, np.float32)
    h, d = qf.shape[1:]
    worst = 0.0
    for b, n in enumerate(lengths):
        kb, vb = (np.repeat(x, h // x.shape[1], axis=1)       # [n, H, D]
                  for x in row_kv(b))
        s = np.einsum("hd,nhd->hn", qf[b], kb) / np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want_b = np.einsum("hn,nhd->hd", p, vb)
        worst = max(worst, float(np.abs(got[b] - want_b).max()))
        check(np.allclose(got[b], want_b, rtol=KERNEL_RTOL,
                          atol=KERNEL_ATOL),
              "{} row {} (valid {}) disagrees with the float32 dense "
              "reference".format(name, b, n))
    log("{}, {} rows, H={}: max|err| {:.4f}".format(
        name, len(lengths), h, worst))


def make_prompts(size, vocab):
    """8 seeded prompts: the three lengths, then the same three prompts
    REPEATED (they must generate the same tokens), then two more."""
    import numpy as np

    lens = size.prompt_lens
    rng = np.random.RandomState(1)
    fresh = [rng.randint(0, vocab, (n,)).astype(np.int32)
             for n in lens + lens[:2]]
    return fresh[:3] + fresh[:3] + fresh[3:]


def generate(grpc_url, prompt, max_tokens):
    """One decoupled generation through the public gRPC client.
    ``generate_stream`` returns only on the stream's final response and,
    with ``resume=False``, raises on any drop instead of reconnecting."""
    import numpy as np
    import tritonclient.grpc as grpcclient

    client = grpcclient.InferenceServerClient(grpc_url)
    try:
        p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)], "INT32")
        p_in.set_data_from_numpy(np.asarray(prompt, np.int32))
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([max_tokens], np.int32))
        tokens, logprobs = [], []
        for result in client.generate_stream(
                "llama_generate", [p_in, m_in], resume=False):
            tokens.append(int(result.as_numpy("TOKEN")[0]))
            logprobs.append(float(result.as_numpy("LOGPROB")[0]))
        return tokens, logprobs
    finally:
        client.close()


def phase_setup(size, llama_cfg):
    """Build the server behind both frontends and send the warm-up
    requests that carry the compiles.  ``LlamaGenerateModel.warmup()``
    only builds the weights in scheduler mode — the step and prefill
    executables compile inside the first requests, so those go over
    gRPC (the HTTP client's 60 s network timeout is shorter than a cold
    compile) and count as set-up."""
    import jax
    import numpy as np
    import tritonclient.grpc as grpcclient

    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models import default_models
    from tpuserver.models.llama_serving import LlamaGenerateModel
    from tpuserver.models.vision import ResNet50Model

    llama_model = LlamaGenerateModel(
        cfg=llama_cfg, max_seq=size.max_seq, max_slots=MAX_SLOTS)
    core = InferenceServer(
        default_models() + [ResNet50Model(), llama_model])
    frontends = [HttpFrontend(core, port=0).start(),
                 GrpcFrontend(core, port=0).start()]
    http_url, grpc_url = frontends[0].url, frontends[1].url
    log("serving on http {} grpc {}".format(http_url, grpc_url))

    t0 = time.monotonic()
    llama_model.warmup()
    jax.block_until_ready(llama_model._params)
    log("llama weights built in {:.1f}s".format(time.monotonic() - t0))
    for n in size.prompt_lens:
        t0 = time.monotonic()
        tokens, _ = generate(
            grpc_url, np.full((n,), 7, np.int32), 2)
        check(len(tokens) == 2, "warm-up generation returned {} tokens"
              .format(len(tokens)))
        log("warm-up prompt length {}: {:.1f}s".format(
            n, time.monotonic() - t0))
    t0 = time.monotonic()
    client = grpcclient.InferenceServerClient(grpc_url)
    try:
        inp = grpcclient.InferInput("INPUT", [1, 224, 224, 3], "FP32")
        inp.set_data_from_numpy(np.zeros((1, 224, 224, 3), np.float32))
        client.infer("resnet50", [inp], client_timeout=900)
    finally:
        client.close()
    log("warm-up resnet50 b1: {:.1f}s".format(time.monotonic() - t0))
    return core, llama_model, frontends, http_url, grpc_url


def phase_simple(http_url):
    import numpy as np
    import tritonclient.http as httpclient

    client = httpclient.InferenceServerClient(http_url)
    try:
        a = np.arange(16, dtype=np.int32).reshape(1, 16)
        b = np.full((1, 16), 3, np.int32)
        in0 = httpclient.InferInput("INPUT0", [1, 16], "INT32")
        in1 = httpclient.InferInput("INPUT1", [1, 16], "INT32")
        in0.set_data_from_numpy(a)
        in1.set_data_from_numpy(b)
        result = client.infer("simple", [in0, in1])
        check((result.as_numpy("OUTPUT0") == a + b).all(), "simple: bad sum")
        check((result.as_numpy("OUTPUT1") == a - b).all(),
              "simple: bad difference")
    finally:
        client.close()
    log("simple over HTTP: sums and differences correct")


def phase_resnet(grpc_url, device):
    """ResNet-50 in-band, then the north-star path: a jax.Array parked
    in an XLA shm region in, a region out, nothing staged through the
    host — and the two answers agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import tritonclient.grpc as grpcclient
    from tritonclient.utils import xla_shared_memory as xshm

    img = np.random.RandomState(2).standard_normal(
        (1, 224, 224, 3)).astype(np.float32)
    img_bytes, out_bytes = img.nbytes, 1000 * 4
    client = grpcclient.InferenceServerClient(grpc_url)
    h_in = h_out = None
    try:
        inp = grpcclient.InferInput("INPUT", list(img.shape), "FP32")
        inp.set_data_from_numpy(img)
        inband = client.infer(
            "resnet50", [inp], client_timeout=900).as_numpy("OUTPUT")
        check(inband.shape == (1, 1000), "resnet50 in-band shape {}".format(
            inband.shape))
        check(np.isfinite(inband).all(), "resnet50 in-band: non-finite")
        check(abs(float(inband.sum()) - 1.0) < 1e-3,
              "resnet50 in-band: probabilities sum to {}".format(
                  inband.sum()))

        h_in = xshm.create_shared_memory_region("smoke_in", img_bytes)
        h_out = xshm.create_shared_memory_region("smoke_out", out_bytes)
        client.register_xla_shared_memory(
            "smoke_in", xshm.get_raw_handle(h_in), 0, img_bytes)
        client.register_xla_shared_memory(
            "smoke_out", xshm.get_raw_handle(h_out), 0, out_bytes)
        xshm.set_shared_memory_region_from_jax(h_in, [jnp.asarray(img)])
        inp = grpcclient.InferInput("INPUT", list(img.shape), "FP32")
        inp.set_shared_memory("smoke_in", img_bytes)
        out = grpcclient.InferRequestedOutput("OUTPUT")
        out.set_shared_memory("smoke_out", out_bytes)
        client.infer("resnet50", [inp], outputs=[out], client_timeout=900)
        check(h_out.get_jax_segment(0) is not None,
              "resnet50 xla-shm: the output was staged through the host "
              "window, not delivered as a device segment")
        via_shm = xshm.get_contents_as_jax(h_out, "FP32", [1, 1000])
        check(isinstance(via_shm, jax.Array)
              and via_shm.devices() == {device},
              "resnet50 xla-shm: output lives on {}, expected {}".format(
                  getattr(via_shm, "devices", lambda: "?")(), device))
        # same executable, same input: only the delivery differs
        check(np.allclose(np.asarray(via_shm), inband, rtol=0, atol=1e-6),
              "resnet50: xla-shm and in-band answers differ by {}".format(
                  np.abs(np.asarray(via_shm) - inband).max()))
        log("resnet50 over gRPC: in-band == xla-shm (top class {}), "
            "output resident on {}".format(int(inband.argmax()), device))
    finally:
        for name, handle in (("smoke_in", h_in), ("smoke_out", h_out)):
            if handle is not None:
                client.unregister_xla_shared_memory(name)
                xshm.destroy_shared_memory_region(handle)
        client.close()


def phase_llama(grpc_url, prompts, size, vocab):
    """8 concurrent generations through the scheduler; returns the
    per-stream (tokens, logprobs)."""
    import numpy as np

    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        futures = [pool.submit(generate, grpc_url, p, size.max_tokens)
                   for p in prompts]
        streams = [f.result(timeout=900) for f in futures]
    for i, (tokens, logprobs) in enumerate(streams):
        check(len(tokens) == size.max_tokens,
              "stream {}: {} tokens, expected {}".format(
                  i, len(tokens), size.max_tokens))
        check(all(0 <= t < vocab for t in tokens),
              "stream {}: token out of range".format(i))
        check(np.isfinite(logprobs).all() and max(logprobs) <= 0.0,
              "stream {}: logprobs not finite and <= 0: {}".format(
                  i, logprobs))
    for i in range(3):
        check(streams[i][0] == streams[i + 3][0],
              "streams {} and {} share a prompt but not their tokens:\n"
              "{}\n{}".format(i, i + 3, streams[i][0], streams[i + 3][0]))
    log("llama_generate: {} concurrent streams x {} tokens, prompt "
        "lengths {}, repeated prompts reproduce".format(
            len(streams), size.max_tokens, [len(p) for p in prompts]))
    return streams


def phase_reference(llama_model, llama_cfg, prompts, streams):
    """Each distinct prompt's first served token against
    ``llama.forward`` with dense XLA attention on the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuserver.models import llama

    cfg_xla = dataclasses.replace(llama_cfg, attn_impl="xla")
    last_logits = jax.jit(
        lambda params, tokens: llama.forward(params, tokens, cfg_xla)[0, -1])
    # the weights the server runs on (no second 7 GB copy fits beside
    # them); private access, as the repo's own tests do
    params = llama_model._params
    for i in (0, 1, 2, 6, 7):
        logits = np.asarray(
            last_logits(params, jnp.asarray(prompts[i])[None, :]))
        check(np.isfinite(logits).all(), "reference logits non-finite")
        token, logprob = streams[i][0][0], streams[i][1][0]
        best = int(logits.argmax())
        gap = float(logits[best] - logits[token])
        ref_logprob = float(
            logits[token] - logits.max()
            - np.log(np.exp(logits - logits.max()).sum()))
        top2 = np.sort(logits)[-2:]
        log("prompt {} (T={}): served {} reference {} | served token is "
            "{:.4f} below the reference's best (top-2 margin {:.4f}) | "
            "logprob {:.4f} vs {:.4f}".format(
                i, len(prompts[i]), token, best, gap,
                float(top2[1] - top2[0]), logprob, ref_logprob))
        check(gap <= FIRST_TOKEN_TOL,
              "prompt {}: served first token {} scores {:.4f} below the "
              "reference's {} (tolerance {})".format(
                  i, token, gap, best, FIRST_TOKEN_TOL))
        check(abs(logprob - ref_logprob) <= FIRST_TOKEN_TOL,
              "prompt {}: served logprob {:.4f} vs reference {:.4f}".format(
                  i, logprob, ref_logprob))


def phase_mosaic(llama_model, llama_cfg, size, dry_run):
    """Proof the kernels were Mosaic on the served path: the compiled
    step and the flash-length prefills hold one ``tpu_custom_call`` per
    layer; the dense-length prefill holds none.  Compiles the
    scheduler's own jitted functions at the served shapes (a persistent
    cache hit after serving)."""
    import jax
    import jax.numpy as jnp

    fns = llama_model._scheduler._fns

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def count(jitted, *args):
        text = jitted.lower(*args).compile().as_text()
        return text.count('custom_call_target="tpu_custom_call"')

    params = jax.tree_util.tree_map(struct, llama_model._params)
    pages = jax.eval_shape(fns["init_cache"])
    logits = jax.eval_shape(fns["init_logits"])
    slot_cache = jax.eval_shape(fns["init_slot_cache"])
    rows = jax.ShapeDtypeStruct((MAX_SLOTS,), jnp.int32)
    flags = jax.ShapeDtypeStruct((MAX_SLOTS,), jnp.bool_)
    tables = jax.ShapeDtypeStruct(
        (MAX_SLOTS, fns["pages_per_seq"]), jnp.int32)
    counts = {"step": count(fns["step"], params, pages, logits, tables,
                            rows, flags, rows, flags)}
    for n in size.prompt_lens:
        bucket = fns["prefill_bucket"](n)
        counts["prefill_T{}".format(n)] = count(
            fns["prefill"], params, slot_cache,
            jax.ShapeDtypeStruct((1, bucket), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32, weak_type=True))
    log("tpu_custom_call counts (n_layers={}), decode attention {}: {}"
        .format(llama_cfg.n_layers, fns["decode_attention"], counts))
    # the served step reads the page pool in place; the dry run's tiny
    # geometry resolves to dense attention over the gathered view
    check(fns["decode_attention"]
          == ("gather_dense" if dry_run else "paged_kernel"),
          "the step was built with decode attention {}".format(
              fns["decode_attention"]))
    expect = 0 if dry_run else llama_cfg.n_layers
    flash_a, flash_b, _dense = size.prompt_lens
    for name in ("step", "prefill_T{}".format(flash_a),
                 "prefill_T{}".format(flash_b)):
        check(counts[name] == expect,
              "{} holds {} tpu_custom_call(s), expected {}".format(
                  name, counts[name], expect))
    return counts


def phase_metrics(http_url, llama_model, device, dry_run):
    from tpuserver.metrics import parse_prometheus_text

    with urllib.request.urlopen(
            "http://{}/metrics".format(http_url), timeout=60) as resp:
        families = parse_prometheus_text(resp.read().decode())

    def sample(family, name, **labels):
        return next(
            (value for n, lab, value in
             families.get(family, {"samples": ()})["samples"]
             if n == name and lab == labels), None)

    steps = sample("tpu_scheduler_step_seconds",
                   "tpu_scheduler_step_seconds_count",
                   model="llama_generate")
    check(steps, "/metrics shows no scheduler steps")
    stats = llama_model.scheduler_stats()
    # a supervised restart would have healed the streams silently
    check(stats["restarts"] == 0 and stats["quarantined"] == 0,
          "scheduler restarted or quarantined during the smoke: {}".format(
              stats))
    used = sample("nv_gpu_memory_used_bytes", "nv_gpu_memory_used_bytes",
                  tpu="0")
    if not dry_run:
        check(used is not None and used > 0,
              '/metrics nv_gpu_memory_used_bytes{tpu="0"} is ' + str(used))
    memory = device.memory_stats() or {}
    peak = memory.get("peak_bytes_in_use")
    log("/metrics: {} scheduler steps, {} tokens, device memory in use "
        "{} | peak HBM {} of {} bytes".format(
            int(steps), stats["tokens"], used, peak,
            memory.get("bytes_limit")))
    return peak


# -- driver -------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--dry-run-cpu", action="store_true",
        help="explicit opt-in: the same control flow at the tiny config "
             "with interpreted kernels, on whatever device jax has")
    dry_run = ap.parse_args().dry_run_cpu

    # built from what git would commit: nothing under build/ is trusted
    # (the loader compares mtimes) — libcshm.so rebuilds from src/c++
    stale = os.path.join(REPO, "build", "lib", "libcshm.so")
    if os.path.exists(stale):
        os.remove(stale)

    try:
        device, info, versions = phase_platform(dry_run)
    except RuntimeError as e:
        print("chip_smoke: {}".format(e), file=sys.stderr)
        return 1

    from tpuserver.models import llama

    size = DRY if dry_run else CHIP
    llama_cfg = (dataclasses.replace(llama.tiny(vocab=2048),
                                     attn_impl="pallas")
                 if dry_run else llama.llama3_3b())
    prompts = make_prompts(size, llama_cfg.vocab)
    phases, seconds, state = {}, {}, {}

    def run(name, fn):
        """Run one phase; a failure is recorded and fails the exit
        code, and later independent phases still run so one chip call
        reports everything."""
        t0 = time.monotonic()
        try:
            state[name] = fn()
            phases[name] = "pass"
        except Exception:  # noqa: BLE001 — recorded as a FAILED phase
            traceback.print_exc()
            phases[name] = "fail"
        seconds[name] = round(time.monotonic() - t0, 1)
        log("phase {}: {} ({}s)".format(name, phases[name], seconds[name]))
        return phases[name] == "pass"

    seconds["platform"] = round(time.monotonic() - T_START, 1)
    phases["platform"] = "pass"
    run("kernels", functools.partial(phase_kernels, size, dry_run))
    frontends = []
    try:
        if run("setup", functools.partial(phase_setup, size, llama_cfg)):
            core, llama_model, frontends, http_url, grpc_url = state["setup"]
            run("simple", functools.partial(phase_simple, http_url))
            run("resnet50", functools.partial(
                phase_resnet, grpc_url, device))
            if run("llama", functools.partial(
                    phase_llama, grpc_url, prompts, size, llama_cfg.vocab)):
                run("reference", functools.partial(
                    phase_reference, llama_model, llama_cfg, prompts,
                    state["llama"]))
            run("mosaic", functools.partial(
                phase_mosaic, llama_model, llama_cfg, size, dry_run))
            run("metrics", functools.partial(
                phase_metrics, http_url, llama_model, device, dry_run))
    finally:
        for frontend in frontends:
            frontend.stop()

    expected = ("platform", "kernels", "setup", "simple", "resnet50",
                "llama", "reference", "mosaic", "metrics")
    for name in expected:
        phases.setdefault(name, "not run")
    ok = all(phases[name] == "pass" for name in expected)
    serving = ("simple", "resnet50", "llama")
    summary = {
        "ok": ok,
        "dry_run": dry_run,
        "phases": phases,
        "versions": versions,
        "timings": "smoke, not benchmark",
        # init + weights + every compile, apart from answering requests
        "setup_s": round(seconds["platform"] + seconds.get("setup", 0), 1),
        "serving_s": round(sum(seconds.get(n, 0) for n in serving), 1),
        "phase_s": seconds,
        "total_s": round(time.monotonic() - T_START, 1),
        "custom_calls": state.get("mosaic"),
        "peak_hbm_bytes": state.get("metrics"),
    }
    log("summary " + json.dumps(summary))
    # the result line, last on stdout: exactly these keys, the device as
    # jax reports it — whoever runs the smoke parses this and nothing else
    print(json.dumps({"ok": ok, "device": info}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
