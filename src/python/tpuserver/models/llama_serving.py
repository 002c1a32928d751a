"""Decoupled llama generation serving model (BASELINE config #5: token-by-
token generate streaming with TPU-shm KV handles).

One request carries the prompt ids; the model prefills the KV cache in one
batched pass, then streams one sampled token per response over the
decoupled channel (ModelStreamInfer).  Generation runs as a jitted
decode_step per token — static shapes, cache donated, so steady-state cost
is one device dispatch per token.

Execution modes:

- **single-device** (default): plain jits on the default device.
- **tensor-parallel** (``mesh=`` with a ``tp`` axis): the same compute
  via ``llama.make_tp_serving`` — Megatron column/row-split weights,
  kv-head-sharded cache (``llama.cache_spec``), XLA-inserted collectives.
  The served model IS the sharded jit; no separate "distributed backend".
- **int8 weights** (``quantize=True``): weights quantize on load
  (``llama.quantize_params``) so the 8B preset (16 GB bf16) serves within
  a single 16 GB-HBM v5e chip.

KV-cache persistence: a request parameter ``kv_cache_region`` naming a
registered XLA shared-memory region makes the model park the finished KV
cache (a device-resident ``jax.Array`` — sharded across the mesh in tp
mode) in that region and, on a follow-up request with the same parameter
and ``kv_cache_resume=True``, continue generation from it without
re-prefilling — the TPU-shm analogue of the reference's CUDA-shm tensor
passing, applied to generation state.

Continuous batching (``max_slots > 1``): generation routes through the
``tpuserver.scheduler.DecodeScheduler`` — a block-paged KV pool
(``page_size``-token pages, ``kv_pages`` bound, radix prefix cache
deduplicating shared prompt prefixes, chunked prefill past
``prefill_chunk_tokens``) and a background loop running one batched
decode step for ALL in-flight streams per iteration, admitting waiting
requests into freed slots mid-flight as long as pages remain.  Greedy
tokens are identical to the single-stream path (test-enforced);
``max_slots=1`` (the default) keeps the original single-stream
pipelined path byte-for-byte, so existing tests and BENCH numbers stay
reproducible.  An optional ``eos_id`` request parameter ends a
generation early on that token (emitted, then the slot retires and is
reused), on both paths.  See docs/resilience.md "Paged KV cache &
radix prefix cache".

Generation by diffusion over blocks (a configuration with
``block_len`` > 0; continuous batching only): one streamed response a
FINISHED BLOCK, carrying its tokens in position order (``TOKEN``,
``LOGPROB``, ``POSITION``, ``UNMASK_PASS``, each of the block's length),
sent by the pass that unmasks the block's last position.  Request
parameters ``denoising_steps`` (1..block length; default the block
length, one token a pass) and ``confidence_threshold`` (0..1; default 1,
the static schedule alone) are the caller's trade of quality for speed.
"""

import threading

import numpy as np

from tpuserver.core import Model, TensorSpec
from tpuserver.models import llama


class LlamaGenerateModel(Model):
    """PROMPT_IDS int32[-1], MAX_TOKENS int32[1] -> stream of
    (TOKEN int32[1], LOGPROB fp32[1]) responses."""

    name = "llama_generate"
    platform = "jax"
    backend = "jax"
    max_batch_size = 0
    decoupled = True
    inputs = (
        TensorSpec("PROMPT_IDS", "INT32", [-1]),
        TensorSpec("MAX_TOKENS", "INT32", [1]),
    )
    outputs = (
        TensorSpec("TOKEN", "INT32", [1]),
        TensorSpec("LOGPROB", "FP32", [1]),
    )

    # tokens greedy-decoded per device dispatch on the single-stream
    # path: a scanned chunk amortizes the per-dispatch host cost over
    # several tokens (each token still streams as its own decoupled
    # response)
    decode_chunk = 8

    def __init__(self, cfg=None, max_seq=512, server=None,
                 decode_chunk=None, mesh=None, quantize=False,
                 max_slots=1, max_pending=None, fault_scope=None,
                 step_timeout_s=None, max_restarts=5,
                 restart_window_s=60.0, restart_backoff_s=0.05,
                 replay_ttl_s=60.0, replay_capacity=256,
                 page_size=16, kv_pages=None, prefill_chunk_tokens=256,
                 prefix_cache=True, kv_export=False,
                 target_queue_ms=None, shed_interval_ms=100.0,
                 params=None, kv_window_pages=None):
        self._cfg = cfg or llama.tiny(vocab=2048)
        if self._cfg.conv_layers and max_slots < 2:
            # the single-stream path parks and resumes K/V rows alone
            raise llama.UnsupportedArchitecture(
                "conv layers are served by the continuous-batching "
                "scheduler, whose pool carries their windows: max_slots "
                "must be > 1")
        if self._cfg.block_len:
            if max_slots < 2:
                raise llama.UnsupportedArchitecture(
                    "generation by diffusion over blocks is the "
                    "scheduler's block step: max_slots must be > 1")
            # one response a finished block, its tokens in position order
            self.outputs = tuple(
                TensorSpec(name, dtype, [-1]) for name, dtype in (
                    ("TOKEN", "INT32"), ("LOGPROB", "FP32"),
                    ("POSITION", "INT32"), ("UNMASK_PASS", "INT32")))
        # the weights, handed in: a pytree in ``llama.init_params``'s
        # layout for ``cfg`` (already on the device, in the served
        # type), or a callable that returns one when the model loads.
        # None: the model makes its own from PRNGKey(0).  A tree handed
        # as a callable is held under ``_params`` alone, so dropping
        # that frees the device memory
        self._params_source = params
        if params is not None and (quantize or mesh is not None):
            raise ValueError(
                "params= hands over weights as they are served: not with "
                "quantize=True or a mesh, which build their own layout")
        if not self._cfg.plain and (quantize or mesh is not None):
            raise llama.UnsupportedArchitecture(
                "int8 weights and tensor-parallel serving are written "
                "for the plain Llama / Mistral block")
        # replica identity threaded to the scheduler's fault-injection
        # points (multi-replica chaos harnesses)
        self._fault_scope = fault_scope
        self._max_seq = max_seq
        self._server = server  # for kv_cache_region xla-shm lookups
        self._mesh = mesh  # tensor-parallel serving when set (tp axis)
        self._quantize = bool(quantize)
        self._params = None
        self._prefill = None
        self._decode = None
        self._decode_chunk = None
        if max_slots < 1:
            raise ValueError(
                "max_slots must be >= 1 (got {})".format(max_slots))
        self._max_slots = int(max_slots)
        self._max_pending = max_pending  # admission-queue bound override
        # adaptive (CoDel-style) queue shedding, threaded to
        # DecodeScheduler — None keeps the fixed max_pending cliff only
        self._target_queue_ms = target_queue_ms
        self._shed_interval_ms = shed_interval_ms
        # supervisor / replay-buffer knobs, threaded to DecodeScheduler
        # (docs/resilience.md "Self-healing & stream resume")
        self._step_timeout_s = step_timeout_s
        self._max_restarts = max_restarts
        self._restart_window_s = restart_window_s
        self._restart_backoff_s = restart_backoff_s
        self._replay_ttl_s = replay_ttl_s
        self._replay_capacity = replay_capacity
        # paged-KV geometry (continuous batching only): fixed-size KV
        # pages, pool bound (None = max_slots full-length sequences —
        # byte-identical capacity to the old slotted cache), chunked-
        # prefill bound, and the radix prefix-cache toggle
        self._page_size = page_size
        self._kv_pages = kv_pages
        # bound of the window class where the configuration has window
        # layers (None = every slot a full ring of pages)
        self._kv_window_pages = kv_window_pages
        self._prefill_chunk_tokens = prefill_chunk_tokens
        self._prefix_cache = prefix_cache
        # default for the per-request ``kv_park`` parameter: park a
        # disconnected resumable generation's gathered KV pages as a
        # server-owned XLA-shm region, so a same-host resume attaches
        # and re-scatters instead of re-prefilling prompt + history
        self._kv_export = bool(kv_export)
        self._scheduler = None  # DecodeScheduler when max_slots > 1
        # continuous-batching models interleave many streams' responses;
        # the frontends must not serialize their stream requests
        self.concurrent_decoupled = self._max_slots > 1
        if decode_chunk is not None:
            if decode_chunk < 1:
                raise ValueError(
                    "decode_chunk must be >= 1 (got {})".format(
                        decode_chunk))
            self.decode_chunk = decode_chunk
        if mesh is not None and "tp" not in mesh.shape:
            raise ValueError(
                "llama serving mesh needs a 'tp' axis (got {})".format(
                    dict(mesh.shape)))
        self._lock = threading.Lock()

    def attach_server(self, server):
        self._server = server

    def _ensure_compiled(self):
        if self._params is not None:
            return
        with self._lock:
            if self._params is None:
                import jax

                if self._quantize:
                    # quantize-on-load: init + quantize on HOST so the
                    # bf16 weights never exist in HBM — the point for
                    # the 8B preset, whose 16 GB of bf16 exceeds a v5e
                    # chip but whose ~8 GB int8 form fits.  Needs the
                    # cpu backend BESIDE the chip: JAX_PLATFORMS unset
                    # or "tpu,cpu" (plain "tpu" raises here)
                    cpu = jax.devices("cpu")[0]
                    with jax.default_device(cpu):
                        params = llama.quantize_params(
                            llama.init_params(
                                jax.random.PRNGKey(0), self._cfg
                            )
                        )
                    if self._mesh is None:
                        params = jax.device_put(
                            params, jax.devices()[0])
                elif self._params_source is not None:
                    source = self._params_source
                    params = source() if callable(source) else source
                    if params is None:
                        raise ValueError(
                            "model '{}': params= returned no weights "
                            "(a callable source hands its tree over "
                            "once)".format(self.name))
                else:
                    params = llama.init_params(
                        jax.random.PRNGKey(0), self._cfg
                    )
                if self._mesh is not None:
                    param_sh, _, _ = llama.serving_shardings(
                        self._mesh, self._cfg, quantized=self._quantize
                    )
                    params = jax.device_put(params, param_sh)
                if self._max_slots > 1:
                    # continuous batching: a background loop owns a
                    # slotted cache and all device state; the fns below
                    # stay None (the single-stream path is not built)
                    from tpuserver.scheduler import DecodeScheduler

                    fns = llama.make_scheduler_fns(
                        self._cfg, self._max_seq, self._max_slots,
                        mesh=self._mesh, quantized=self._quantize,
                        page_size=self._page_size,
                        kv_pages=self._kv_pages,
                        kv_window_pages=self._kv_window_pages,
                    )
                    server = self._server
                    kv_hooks = {}
                    if server is not None:
                        # the park-attach data plane rides the server's
                        # XLA-shm registry; per-request ``kv_park``
                        # (or the model-level default) turns it on
                        kv_hooks = dict(
                            kv_export=server.export_kv_region,
                            kv_import=server.import_kv_region,
                            kv_discard=server.drop_kv_region)
                    self._scheduler = DecodeScheduler(
                        fns, params, self._max_slots, self._max_seq,
                        max_pending=self._max_pending,
                        **kv_hooks,
                        fault_scope=self._fault_scope,
                        step_timeout_s=self._step_timeout_s,
                        max_restarts=self._max_restarts,
                        restart_window_s=self._restart_window_s,
                        restart_backoff_s=self._restart_backoff_s,
                        replay_ttl_s=self._replay_ttl_s,
                        replay_capacity=self._replay_capacity,
                        prefill_chunk_tokens=self._prefill_chunk_tokens,
                        prefix_cache=self._prefix_cache,
                        target_queue_ms=self._target_queue_ms,
                        shed_interval_ms=self._shed_interval_ms,
                        # queue-wait/step latency histograms land in
                        # the attached server's /metrics registry
                        # (lock-free observes — the decode loop never
                        # pays a lock to be observable)
                        metrics=getattr(self._server, "metrics", None),
                        metric_labels={"model": self.name},
                    )
                elif self._mesh is not None:
                    init_cache, prefill_fn, chunk_fn = (
                        llama.make_tp_serving(
                            self._mesh, self._cfg,
                            chunk=self.decode_chunk,
                            quantized=self._quantize,
                        )
                    )
                    step_fn = llama.make_tp_step(
                        self._mesh, self._cfg,
                        quantized=self._quantize,
                    )
                    self._init_cache = (
                        lambda: init_cache(1, self._max_seq)
                    )
                    self._prefill = prefill_fn
                    self._decode = step_fn
                    self._decode_chunk = chunk_fn
                else:
                    self._init_cache = lambda: llama.init_kv_cache(
                        self._cfg, 1, self._max_seq
                    )
                    self._prefill = jax.jit(
                        llama.named_partial(llama.prefill, cfg=self._cfg)
                    )
                    self._decode = jax.jit(
                        llama.named_partial(
                            llama.decode_step, cfg=self._cfg),
                        donate_argnums=(1,),
                    )
                    self._decode_chunk = jax.jit(
                        llama.named_partial(
                            llama.decode_chunk, cfg=self._cfg,
                            chunk=self.decode_chunk),
                        donate_argnums=(1,),
                    )
                self._params = params

    def warmup(self):
        self._ensure_compiled()

    def _kv_region(self, request):
        from tpuserver.core import ServerError

        name = request.parameters.get("kv_cache_region")
        if not name:
            return None
        if self._server is None:
            raise ServerError(
                "model '{}' has no server attached; kv_cache_region "
                "requires a registered XLA shm region".format(self.name)
            )
        return self._server.xla_shm_region(name)

    @staticmethod
    def _resume_state(request, region):
        """(parked cache segment or None, resume position) for a
        ``kv_cache_resume`` request — the one copy of the resume
        parameter contract, shared by both serving paths."""
        if region is None or not request.parameters.get("kv_cache_resume"):
            return None, 0
        parked = region.handle.get_jax_segment(0)
        if parked is None:
            return None, 0
        if "kv_cache_position" not in request.parameters:
            raise ValueError(
                "kv_cache_resume requires kv_cache_position (the "
                "sequence position the parked cache was left at)"
            )
        return parked, int(request.parameters["kv_cache_position"])

    def _ring_writer(self, request):
        """``(region_name, write, seq_guarded)`` for a request carrying
        a token-ring descriptor (``shm_ring_region`` +
        ``shm_ring_slots`` [+ ``shm_ring_offset`` base]), or None.
        ``write(seq, token, logprob)`` lands the step in its ring slot
        (``seq %% slots``) through the server's bounds-checked shm
        plumbing and returns the slot's byte offset — the descriptor
        the decoupled event carries instead of the tensors.

        ``shm_ring_seq_base`` opts the request into seqlock write-
        completeness markers (tpuserver.shm_ring): every payload write
        is bracketed by a begin/commit word in the parallel seq-word
        array at that base offset, so a reader can detect a torn or
        stale slot and fall back to the in-band payload — which the
        events then also carry (``seq_guarded=True``)."""
        name = request.parameters.get("shm_ring_region")
        if not name:
            return None
        server = self._server
        if server is None:
            from tpuserver.core import ServerError

            raise ServerError(
                "model '{}' has no server attached; shm_ring_region "
                "requires a registered shared-memory region".format(
                    self.name)
            )
        slots = int(request.parameters.get("shm_ring_slots") or 0)
        if slots < 1:
            raise ValueError(
                "shm_ring_region requires shm_ring_slots >= 1 (the "
                "ring geometry travels with the request)")
        base = int(request.parameters.get("shm_ring_offset") or 0)
        slot_bytes = server.SHM_RING_SLOT_BYTES
        seq_base = request.parameters.get("shm_ring_seq_base")

        if seq_base is None:
            def write(seq, token, logprob):
                off = base + (seq % slots) * slot_bytes
                server.write_shm_ring_slot(name, off, token, logprob)
                return off

            return name, write, False

        from tpuserver import shm_ring

        seq_base = int(seq_base)

        def write(seq, token, logprob):
            off = base + (seq % slots) * slot_bytes
            word_off = shm_ring.seq_word_offset(seq, slots, seq_base)
            server.write_shm_ring_seq_word(
                name, word_off, shm_ring.begin_word(seq))
            server.write_shm_ring_slot(name, off, token, logprob)
            server.write_shm_ring_seq_word(
                name, word_off, shm_ring.commit_word(seq))
            return off

        return name, write, True

    @staticmethod
    def _emit_token(token, logprob, seq, ring_write, seq_guarded=False):
        """One decoupled response: the TOKEN/LOGPROB tensors in-band,
        or — on the shm token ring — just the slot descriptor (the
        event shrinks to ``seq -> offset``; the tensors live in the
        client-registered region).  A seq-guarded ring keeps the
        tensors in-band too: the payload a reader that detects a torn
        slot falls back to."""
        if ring_write is None:
            return {
                "TOKEN": np.array([token], dtype=np.int32),
                "LOGPROB": np.array([logprob], dtype=np.float32),
            }
        from tpuserver.core import RESPONSE_PARAMS_KEY

        off = ring_write(seq, int(token), float(logprob))
        params = {"seq": seq}
        params["shm_ring_offset"] = off
        event = {RESPONSE_PARAMS_KEY: params}
        if seq_guarded:
            event["TOKEN"] = np.array([token], dtype=np.int32)
            event["LOGPROB"] = np.array([logprob], dtype=np.float32)
        return event

    def execute_stream(self, inputs, request):
        import jax

        self._ensure_compiled()
        raw_prompt = inputs["PROMPT_IDS"]
        prompt_dev = None
        if isinstance(raw_prompt, jax.Array):
            # the zero-copy request plane: a device-resident XLA-shm
            # segment view feeds prefill directly — the ids are never
            # staged through the host on the single-stream path, and
            # the scheduler's cold prefill consumes the view on device
            prompt_dev = (raw_prompt if raw_prompt.ndim == 1
                          else raw_prompt.reshape(-1))
            prompt = None
            prompt_len = int(prompt_dev.shape[0])
        else:
            prompt = np.asarray(raw_prompt).reshape(-1).astype(np.int32)
            prompt_len = len(prompt)
        max_tokens = int(np.asarray(inputs["MAX_TOKENS"]).reshape(-1)[0])
        if prompt_len == 0:
            raise ValueError("PROMPT_IDS must be non-empty")
        eos_id = request.parameters.get("eos_id")
        eos_id = int(eos_id) if eos_id is not None else None

        ring = self._ring_writer(request)
        if ring is not None and self._cfg.block_len:
            raise llama.UnsupportedArchitecture(
                "the shm token ring holds one token a slot; a block "
                "configuration sends a block a response")
        ring_write = ring[1] if ring is not None else None
        seq_guarded = ring[2] if ring is not None else False
        # pin every referenced region for the stream's lifetime: a
        # concurrent unregister becomes a typed 409 conflict instead of
        # a crash (or a silent write into freed memory) mid-generation
        pinned = []
        server = self._server
        try:
            if server is not None:
                names = {n for n in (
                    ring[0] if ring is not None else None,
                    request.parameters.get("kv_cache_region"),
                ) if n}
                # regions the frontend resolved inputs from (the
                # prompt's live device view) pin too
                names.update(getattr(request, "shm_input_regions", ()))
                for name in names:
                    server.pin_shm_region(name)
                    pinned.append(name)
            if self._scheduler is not None:
                # continuous batching: hand the request to the shared
                # decode loop; tokens stream back as the batched steps
                # produce them
                if prompt is None:
                    # the scheduler's bookkeeping (radix keys, replay
                    # history) needs host ids; ONE device->host read —
                    # the prefill itself still consumes the device view
                    prompt = np.asarray(prompt_dev).reshape(-1).astype(
                        np.int32)
                yield from self._execute_scheduled(
                    prompt, max_tokens, eos_id, request, ring_write,
                    prompt_dev=prompt_dev, seq_guarded=seq_guarded,
                )
            else:
                yield from self._execute_single(
                    prompt, prompt_dev, prompt_len, max_tokens, eos_id,
                    request, ring_write, seq_guarded,
                )
        finally:
            for name in pinned:
                server.unpin_shm_region(name)

    def _execute_single(self, prompt, prompt_dev, prompt_len, max_tokens,
                        eos_id, request, ring_write,
                        seq_guarded=False):
        import jax
        import jax.numpy as jnp

        region = self._kv_region(request)
        parked, pos = self._resume_state(request, region)
        cache = None
        if parked is not None:
            # decode_step donates its cache argument; copy so the parked
            # array in the region registry stays valid even if this
            # stream dies mid-generation.
            cache = jnp.copy(parked)
        if cache is None:
            cache = self._init_cache()
            pos = 0
        if pos + prompt_len + max_tokens > self._max_seq:
            raise ValueError(
                "position ({}) + prompt ({}) + max_tokens ({}) exceeds max "
                "sequence {}".format(
                    pos, prompt_len, max_tokens, self._max_seq
                )
            )

        if prompt_dev is not None:
            # zero-copy: the XLA-shm segment view IS the prefill input
            # (row axis added on device; no host staging)
            tokens = (prompt_dev if prompt_dev.dtype == jnp.int32
                      else prompt_dev.astype(jnp.int32))[None, :]
        else:
            tokens = jnp.asarray(prompt)[None, :]
        if pos == 0:
            logits, cache = self._prefill(self._params, cache, tokens)
            pos = prompt_len
        else:
            # resumed: feed the new prompt tokens one at a time from pos
            for t in range(prompt_len):
                logits, cache = self._decode(
                    self._params, cache, tokens[:, t], pos
                )
                pos += 1

        # Software-pipelined emission: decode chunks are CHAINED on
        # device (each consumes the previous dispatch's logits/cache
        # futures), so the device→host fetch of chunk i overlaps chunk
        # i+1's compute — the dispatch/fence round trip is paid once,
        # not per chunk.  The first token is fetched straight
        # from the prefill logits (a tiny argmax dispatched BEFORE the
        # first chunk), so time-to-first-token is prefill + one round
        # trip instead of prefill + a whole chunk.
        from collections import deque

        emitted = 0
        dispatched = 0
        inflight = deque()  # (tokens, logps, count, skip_first) device/host

        if max_tokens >= self.decode_chunk:
            # early first token: argmax of the prefill logits, dispatched
            # ahead of chunk 0 so it never waits behind chunk compute
            early_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            early_lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1),
                early_tok[:, None], axis=-1)[:, 0]
            tokens_dev, logps_dev, logits, cache = self._decode_chunk(
                self._params, cache, logits, pos
            )
            pos += self.decode_chunk
            dispatched += self.decode_chunk
            # chunk 0's tokens[0] IS the early token; skip it on fetch
            inflight.append((tokens_dev, logps_dev,
                             self.decode_chunk - 1, True))
            t0, l0 = jax.device_get((early_tok, early_lp))
            yield self._emit_token(t0[0], l0[0], emitted, ring_write,
                                   seq_guarded)
            emitted += 1
            if eos_id is not None and int(t0[0]) == eos_id:
                if region is not None:
                    region.put_device_array(0, cache)
                return

        while emitted < max_tokens:
            # keep one chunk computing behind the one being fetched
            while dispatched < max_tokens and len(inflight) < 2:
                n = min(self.decode_chunk, max_tokens - dispatched)
                if n == self.decode_chunk:
                    tokens_dev, logps_dev, logits, cache = (
                        self._decode_chunk(
                            self._params, cache, logits, pos)
                    )
                    pos += n
                    dispatched += n
                    inflight.append((tokens_dev, logps_dev, n, False))
                else:
                    # tail shorter than the compiled chunk: per-token
                    # steps (host-driven, so values are already local)
                    tokens_host = np.empty((n,), np.int32)
                    logps_host = np.empty((n,), np.float32)
                    for i in range(n):
                        logp = jax.nn.log_softmax(logits, axis=-1)
                        token = jnp.argmax(
                            logits, axis=-1).astype(jnp.int32)
                        tokens_host[i] = int(token[0])
                        logps_host[i] = float(logp[0, tokens_host[i]])
                        if i + 1 < n or region is not None:
                            logits, cache = self._decode(
                                self._params, cache, token, pos
                            )
                            pos += 1
                    dispatched += n
                    inflight.append((tokens_host, logps_host, n, False))
            tokens_res, logps_res, n, skip_first = inflight.popleft()
            if isinstance(tokens_res, np.ndarray):
                tokens_host, logps_host = tokens_res, logps_res
            else:
                # one device->host transfer for both arrays: each
                # fetch is a sync
                tokens_all, logps_all = jax.device_get(
                    (tokens_res, logps_res))
                start = 1 if skip_first else 0
                tokens_host = tokens_all[start:, 0]
                logps_host = logps_all[start:, 0]
            for i in range(n):
                yield self._emit_token(
                    tokens_host[i], logps_host[i], emitted, ring_write,
                    seq_guarded)
                emitted += 1
                if eos_id is not None and int(tokens_host[i]) == eos_id:
                    # the EOS token is emitted, then generation stops;
                    # chunks already in flight carry tokens past EOS —
                    # the parked cache's extra rows stay masked behind
                    # the resume position, same as the scheduler's
                    # one-step retirement lag
                    if region is not None:
                        region.put_device_array(0, cache)
                    return

        if region is not None:
            # park the device-resident cache in the XLA region (zero-copy
            # in-process; host-staged cross-process).  In tp mode the
            # parked array stays sharded across the mesh.
            region.put_device_array(0, cache)

    def _execute_scheduled(self, prompt, max_tokens, eos_id, request,
                           ring_write=None, prompt_dev=None,
                           seq_guarded=False):
        """Continuous-batching path: submit to the shared decode loop and
        fan its per-step tokens back out to this stream.

        Every generation here is *resumable*: it gets an id (the
        ``generation_id`` request parameter, or a fresh uuid) and every
        response carries ``generation_id`` + a 0-based ``seq`` in its
        response parameters (SSE ``id:`` lines / gRPC response fields).
        A request carrying ``resume_generation_id`` (+
        ``resume_from_seq``, the first sequence number not yet seen)
        instead continues a parked generation: buffered tokens replay
        first, then live tokens splice in — no duplicates, no gaps.
        Resume is same-endpoint only (replay state is replica-local).

        A request whose client reads multi-token responses
        (``request.multi_token``) gets every token of the generation
        already waiting in one response (``TOKEN`` / ``LOGPROB`` of
        shape ``[k]``, ``seq`` the first token's), marked mergeable for
        the frontend; a block configuration (a block is already one
        response), the shm token ring (a token a slot) and a request
        that names its outputs (their delivery is per response) never
        are."""
        import uuid

        import jax.numpy as jnp

        from tpuserver.core import (
            EMITTED_AT_KEY,
            MERGEABLE_KEY,
            RESPONSE_PARAMS_KEY,
            TOKEN_COUNT_PARAM,
        )
        from tpuserver.scheduler import SchedulerClosed

        scheduler = self._scheduler
        if scheduler is None:
            # close() nulled the scheduler after this request was
            # admitted: same typed outcome as racing submit into it
            raise SchedulerClosed("scheduler is shut down")

        batched = (getattr(request, "multi_token", False)
                   and not self._cfg.block_len and ring_write is None
                   and not request.requested_outputs)
        resume_id = request.parameters.get("resume_generation_id")
        if resume_id:
            from_seq = int(request.parameters.get("resume_from_seq", 0))
            gen_id = str(resume_id)
            # the reconnect's OWN deadline governs the continuation —
            # the original request's bound died with its connection
            stream = scheduler.resume(
                gen_id, from_seq,
                deadline=getattr(request, "deadline", None),
                batched=batched)
            seq = from_seq
        else:
            region = self._kv_region(request)
            parked, pos = self._resume_state(request, region)
            # the pos+prompt+max_tokens overflow check lives in
            # DecodeScheduler.submit — one copy, same wording as this
            # class's single-stream path
            on_finish = None
            if region is not None:
                def on_finish(cache_rows):
                    # the slot's rows in the single-stream park shape,
                    # so a later request may resume on either path
                    region.put_device_array(0, cache_rows)

            gen_id = str(request.parameters.get("generation_id")
                         or uuid.uuid4().hex)
            kv_park = request.parameters.get("kv_park")
            # disaggregated phase split: a prefill-leg admission
            # (kv_phase=prefill) exports its KV when it finishes so a
            # decode replica can attach it; a decode-leg admission
            # (kv_attach=<descriptor>) imports that export and scatters
            # instead of re-prefilling (docs/resilience.md
            # "Disaggregated prefill/decode")
            kv_prefill = request.parameters.get("kv_phase") == "prefill"
            attach_cache, attach_pos = self._attach_from_params(request)
            blocks = {k: request.parameters[k] for k in (
                "denoising_steps", "confidence_threshold")
                if request.parameters.get(k) is not None}
            stream = scheduler.submit(
                prompt, max_tokens, eos_id=eos_id, **blocks,
                resume_cache=(jnp.asarray(parked)
                              if parked is not None else None),
                resume_pos=pos, on_finish=on_finish,
                # the deadline the core resolved (timeout parameter /
                # gRPC context): the scheduler expires pending
                # admissions before prefill and retires in-flight slots
                # past it
                deadline=getattr(request, "deadline", None),
                generation_id=gen_id,
                prompt_dev=prompt_dev,
                # park-export opt-in: the request's kv_park parameter,
                # defaulting to the model-level kv_export flag
                kv_export=(True if kv_prefill
                           else (self._kv_export if kv_park is None
                                 else bool(kv_park))),
                kv_export_on_finish=kv_prefill,
                attach_cache=attach_cache,
                attach_pos=attach_pos,
                batched=batched,
            )
            seq = 0
        for item in stream:
            if batched:
                # every token of the generation waiting now, one response
                params = {"generation_id": gen_id, "seq": seq}
                if len(item) > 1:
                    params[TOKEN_COUNT_PARAM] = len(item)
                yield {
                    "TOKEN": np.array([t for t, _ in item], dtype=np.int32),
                    "LOGPROB": np.array([lp for _, lp in item],
                                        dtype=np.float32),
                    RESPONSE_PARAMS_KEY: params,
                    EMITTED_AT_KEY: [getattr(p, "emitted_at", None)
                                     for p in item],
                    MERGEABLE_KEY: True,
                }
                seq += len(item)
                continue
            token, logprob = item
            if self._cfg.block_len:
                # a finished block; seq counts responses
                toks, lps, at, passes = token
                event = {
                    "TOKEN": np.array(toks, dtype=np.int32),
                    "LOGPROB": np.array(lps, dtype=np.float32),
                    "POSITION": np.array(at, dtype=np.int32),
                    "UNMASK_PASS": np.array(passes, dtype=np.int32),
                    RESPONSE_PARAMS_KEY: {
                        "generation_id": gen_id, "seq": seq,
                    },
                }
            elif ring_write is not None:
                # the shm token ring: tensors land in the client's
                # region slot; the event shrinks to its descriptor.
                # Replayed tokens on resume REWRITE their slots (seq
                # numbering is preserved), so the router's sticky-
                # resume and handoff invariants hold unmodified.
                off = ring_write(seq, int(token), float(logprob))
                params = {"generation_id": gen_id, "seq": seq}
                params["shm_ring_offset"] = off
                event = {RESPONSE_PARAMS_KEY: params}
                if seq_guarded:
                    # seqlock lane: keep the tensors in-band too — the
                    # fallback a reader uses on a torn/stale slot
                    event["TOKEN"] = np.array([token], dtype=np.int32)
                    event["LOGPROB"] = np.array(
                        [logprob], dtype=np.float32)
            else:
                event = {
                    "TOKEN": np.array([token], dtype=np.int32),
                    "LOGPROB": np.array([logprob], dtype=np.float32),
                    RESPONSE_PARAMS_KEY: {
                        "generation_id": gen_id, "seq": seq,
                    },
                }
            # when the decode loop queued a live token (a replayed one
            # carries no stamp): the frontend counts the wait from there
            event[EMITTED_AT_KEY] = [getattr(item, "emitted_at", None)]
            yield event
            seq += 1

    def _attach_from_params(self, request):
        """``(imported cache, position)`` for a ``kv_attach``
        descriptor — the decode leg of a phase-split admission — or
        ``(None, 0)`` when the parameter is absent or the export is no
        longer importable (dropped, expired, malformed): the admission
        then runs the ordinary prefill path, token-identical, just
        slower.  The typed 404/409 edges live on the descriptor FETCH
        (``/v2/kvexport/<gid>``); by attach time the orchestrator
        already holds a claim, so degrading gracefully here is what
        makes a mid-handoff export death user-invisible."""
        desc = request.parameters.get("kv_attach")
        if not desc or self._server is None:
            return None, 0
        if isinstance(desc, (bytes, str)):
            import json

            try:
                desc = json.loads(desc)
            except ValueError:
                return None, 0
        from tpuserver.errors import KvExportNotFound

        try:
            return self._server.import_kv_descriptor(desc)
        except KvExportNotFound:
            return None, 0

    def healthy(self):
        """Readiness probe hook: False once the decode loop tripped
        permanently (restart budget exhausted) or the scheduler is
        closed (``InferenceServer.server_ready``/``model_ready`` report
        it).  Bound once: a concurrent close() nulls ``_scheduler``
        between reads."""
        scheduler = self._scheduler
        return scheduler is None or scheduler.healthy

    def scheduler_stats(self):
        """The decode scheduler's ``stats()`` dict (restart and
        quarantine counters ops alert on), or None before first use /
        in single-stream mode."""
        scheduler = self._scheduler
        return scheduler.stats() if scheduler is not None else None

    def drain(self, timeout=30.0):
        """Stop admission and let in-flight generations finish within
        ``timeout`` seconds (called by ``InferenceServer.drain``);
        no-op for max_slots=1."""
        scheduler = self._scheduler
        if scheduler is not None:
            scheduler.drain(timeout)

    def close(self):
        """Stop the continuous-batching loop (no-op for max_slots=1).
        Called by ``InferenceServer.close``.  Compiled state is reset so
        a server re-opened by a later frontend attach rebuilds a FRESH
        scheduler on the next request instead of failing every
        generation against the closed one forever."""
        if self._scheduler is not None:
            self._scheduler.close()
            with self._lock:
                self._scheduler = None
                self._params = None
