"""Continuous-batching decode scheduler: interleaved served generation.

The round-5 verdict's own decomposition puts the remaining decode-MBU
lever at *batching across rows*: a single-stream decode step streams the
whole weight set from HBM to produce ONE token, so served throughput
equals single-stream throughput while every concurrent gRPC stream
queues on the model's lock.  This module is the missing subsystem: a
per-model background decode loop that owns a block-paged KV pool
(a K/V class ``[n_layers, 2, kv_pages, page_size, n_kv_heads,
head_dim]``, kv-head sharded over the tp mesh when present, or under
latent attention a latent class ``[n_layers, kv_pages, page_size,
row]``) and runs **one batched decode
step for all active slots per iteration**, so the weight stream is paid
once per step and amortized over every in-flight generation.  Each
generation's KV lives in fixed-size pages named by a per-slot page
table (``tpuserver.paging``): admission is bounded by *free pages*,
not slot count, shared prompt prefixes deduplicate into ref-counted
radix-cache pages (a shared-system-prompt admission prefills only its
unique suffix), and long prefills chunk into bounded steps interleaved
with decode — see docs/resilience.md "Paged KV cache & radix prefix
cache".

Lifecycle of a request (vLLM-style continuous batching, TPU-shaped):

1. **admit** — between decode steps, a waiting request reserves a free
   slot row and its whole page span, matches its prompt against the
   radix prefix cache (shared full pages restore via
   ``llama.paged_gather``; only the unique suffix prefills — in one
   bucketed pass, or chunk-by-chunk interleaved with decode when it
   exceeds ``prefill_chunk_tokens``), and scatters the prefilled
   single-row cache into its physical pages
   (``llama.paged_admit``).  A resumed request (``kv_cache_region``
   park/resume) instead scatters its parked cache into the reserved
   pages and replays its new prompt tokens through the batched step
   as *forced* tokens (fed, not emitted).
2. **step** — every iteration runs ``llama.paged_scheduler_step``:
   greedy sample per slot from the slot's logits row, then one batched
   decode dispatch following the per-slot page tables, writing each
   row's K/V at its own position with per-row length masks.  Steps are
   software-pipelined one deep: step *i+1* is dispatched before step
   *i*'s tokens are fetched, so the device→host fetch overlaps the
   next step's compute.
3. **retire** — a slot finishes on its max_tokens budget or its
   ``eos_id``; the slot (and its pages — full ones donate back to the
   radix cache) frees immediately, so a waiting request joins
   **mid-flight** while other slots keep decoding.  A finishing
   request that asked for cache parking gets its pages gathered
   (``llama.paged_gather`` — the same ``[L, 2, 1, S, Hkv, hd]`` shape
   the single-stream path parks) and handed to its ``on_finish``
   callback.

Because of the one-deep pipeline, retirement lags its trigger token by
one step: the slot rides one extra "wasted" dispatch whose token is
discarded.  Correctness is preserved by construction — the wasted write
lands beyond the slot's valid prefix (masked on any later resume), rows
with no live request carry the out-of-bounds sentinel position so their
writes drop, and emission matches snapshot state by object identity so
a re-admitted slot can never receive a predecessor's stale token.

Greedy per-row math in the batched step is identical to the
single-stream ``decode_step``'s, so N interleaved streams produce
token-identical output to N sequential single-stream runs
(test-enforced in tests/test_continuous_batching.py).

Self-healing (tests/test_self_healing.py, docs/resilience.md):

- **Per-slot quarantine.**  A slot whose own step output is poisoned
  (non-finite logprob — NaN logits from a poison request) retires with
  a typed :class:`SlotQuarantined` while every co-batched slot keeps
  decoding; greedy tokens of the survivors are byte-identical to a
  fault-free run (the batched step's math is row-independent).
- **Supervised restart.**  The decode thread runs under a supervisor:
  an unattributable step/fetch failure kills the loop, and the
  supervisor rebuilds device state and *re-admits* every live stream by
  re-prefilling ``prompt + tokens_emitted_so_far`` (greedy decode is
  deterministic, so the continuation is token-identical), under a
  bounded restart budget with exponential backoff.  A hung-step
  watchdog (``step_timeout_s``) treats a wedged device dispatch the
  same way, demoting the stuck thread via an epoch counter so a waking
  zombie can never double-deliver into re-admitted streams.  Budget
  exhausted ⇒ the scheduler trips permanently: unhealthy to readiness
  probes (pools rotate the replica out), every stream failed typed,
  new submits rejected, drain/close still deterministic.
- **Resumable generations.**  ``submit(generation_id=...)`` records
  every emitted ``(token, logprob)``; a disconnected (or completed)
  generation parks in a bounded, TTL'd replay buffer and
  :meth:`DecodeScheduler.resume` replays ``history[from_seq:]`` then
  splices live tokens from a re-admitted continuation — no duplicated
  or missing tokens.  Replay state is replica-local: resume is
  same-endpoint only.
"""

import contextlib
import math
import queue as _queue
import threading
import time
import weakref
from collections import OrderedDict, deque

import numpy as np

from tpuserver import faults
from tpuserver._trace import span
from tpuserver.paging import PageAllocator, RadixPrefixCache, pages_for

# The wire-mapped stream failures are the CANONICAL tpuserver.errors
# types (one definition site, tpulint R4-enforced): DeadlineExceeded
# (504) for an expired per-request bound — while waiting for admission
# or mid-generation; SlotQuarantined (422) for a stream whose OWN
# decode output went non-finite (only the offender retires, co-batched
# streams keep decoding); UnknownGeneration (404) for a resume id this
# replica does not hold.  Re-exported here so the historical
# ``from tpuserver.scheduler import SlotQuarantined`` keeps working.
from tpuserver.errors import (  # noqa: F401 — re-exported
    DeadlineExceeded,
    SlotQuarantined,
    UnknownGeneration,
)


class SchedulerClosed(Exception):
    """Raised on submit after the scheduler has been shut down (or while
    it is draining), and into streams the shutdown failed.  Scheduler-
    local (not a ServerError): the core maps it to ShuttingDown (503)."""


class AdmissionQueueFull(RuntimeError):
    """Raised on submit when the pending queue is at capacity (the
    hard ``max_pending`` backstop), when the KV page pool is
    exhausted, or when the adaptive sojourn-time controller sheds —
    the scheduler-level overload signal (RuntimeError subclass for
    backward compatibility; the core maps it to Overloaded — HTTP 429
    / RESOURCE_EXHAUSTED).  ``retry_after`` (seconds, or None for the
    frontend default) rides into the Overloaded's ``Retry-After``
    header: the adaptive controller computes it from its current
    control interval, so clients back off at the pace the queue is
    actually draining."""

    def __init__(self, msg, retry_after=None):
        super().__init__(msg)
        self.retry_after = retry_after


class _CodelShedController:
    """Sojourn-time admission shedding — the CoDel control law applied
    to the scheduler's pending queue (Nichols & Jacobson, "Controlling
    Queue Delay"), replacing the *fixed* ``max_pending`` cliff with an
    adaptive valve.

    A long queue is not the problem — a queue that STAYS long is.  The
    controller watches the admission queue's sojourn (the head
    stream's wait, i.e. exactly what ``tpu_scheduler_queue_wait_-
    seconds`` histograms at admission): once it has exceeded
    ``target_s`` continuously for a full ``interval_s``, the scheduler
    sheds the NEWEST arrival with the existing typed 429 and keeps
    shedding one arrival per control interval, tightening the interval
    as ``interval / sqrt(shed_count)`` while overload persists
    (standard CoDel acceleration) and relaxing the moment sojourn
    drops back under target.  ``Retry-After`` is the ceiling of the
    current control interval — the pace the queue is draining at.

    Plain state machine, no locking of its own: every method runs
    under the scheduler's ``_cond`` (submit holds it to shed; the
    decode loop holds it where it notes sojourn), and all time flows
    in as ``now`` so unit tests drive it clock-free.  With the
    controller off (``target_queue_ms=None``) the submit path is
    byte-identical to the pre-controller scheduler; ``max_pending``
    stays as the hard backstop either way."""

    __slots__ = ("target_s", "interval_s", "above_since", "shedding",
                 "shed_next", "shed_count")

    def __init__(self, target_s, interval_s):
        self.target_s = float(target_s)
        self.interval_s = float(interval_s)
        self.above_since = None  # first instant sojourn exceeded target
        self.shedding = False
        self.shed_next = 0.0     # next shed instant while shedding
        self.shed_count = 0      # sheds in the current overload episode

    def current_interval(self):
        return self.interval_s / math.sqrt(max(1, self.shed_count))

    def note_sojourn(self, sojourn_s, now):
        """One queue-delay observation (the head-of-queue wait: the
        FIFO maximum, so 'head under target' means the whole queue
        is).  Below target ⇒ relax completely; above ⇒ start (or keep)
        the overload clock."""
        if sojourn_s < self.target_s:
            self.above_since = None
            self.shedding = False
            self.shed_count = 0
        elif self.above_since is None:
            self.above_since = now

    def on_arrival(self, now, queue_len):
        """Shed verdict for one new submit: the ``Retry-After``
        seconds to shed with, or None to admit.  Never sheds an empty
        queue (nothing is waiting — sojourn is a stale signal), never
        sheds before the sojourn has been above target for one full
        interval, and while shedding drops one arrival per (shrinking)
        control interval rather than every arrival — the valve sheds
        at the rate that brings sojourn back to target, not to zero
        throughput."""
        if queue_len <= 0 or self.above_since is None:
            return None
        if now - self.above_since < self.interval_s:
            return None
        if not self.shedding:
            self.shedding = True
            self.shed_count = 1
        elif now >= self.shed_next:
            self.shed_count += 1
        else:
            return None
        interval = self.current_interval()
        self.shed_next = now + interval
        return max(1, int(math.ceil(interval)))


class Emitted(tuple):
    """A live ``(token, logprob)`` pair off a stream's queue that also
    carries ``emitted_at``: the ``time.monotonic()`` at which the decode
    loop put it there.  The frontend counts a token's wait from there
    to the wire by it (``tpu_frontend_token_handoff_seconds_total``);
    pairs replayed from a generation's history are plain tuples."""

    def __new__(cls, pair, emitted_at):
        self = super().__new__(cls, pair)
        self.emitted_at = emitted_at
        return self


class _Stream:
    """One in-flight generation bound to a cache slot."""

    __slots__ = (
        "prompt", "max_tokens", "eos_id", "queue", "forced", "pos",
        "emitted", "on_finish", "resume_cache", "resume_pos", "finished",
        "cancelled", "deadline", "generation_id", "history", "incarnation",
        "enqueued_at",
        # paged-KV state, owned by the decode loop that admitted the
        # stream (reset for re-admission when a loop dies): the np
        # page-table row, the pinned radix path (table[:len(nodes)]
        # are tree pages, the rest up to span_pages are owned), and
        # the reserved span in pages
        "table", "radix_nodes", "span_pages",
        # the window class of a two-class pool: the ring table row
        # (logical page p -> entry p % ring) and the first logical page
        # whose fall behind the window has not been handled yet
        "table_w", "w_done",
        # zero-copy data plane (ISSUE 12): the device-resident prompt
        # view (an XLA-shm segment — cold prefills consume it without
        # host staging), the park-export opt-in, and the attach-resume
        # state a same-host resume scatters instead of re-prefilling
        "prompt_dev", "kv_export", "attach_cache", "attach_pos",
        # disaggregated prefill phase (ISSUE 16): export the KV on
        # FINISH (not just cancel-reap) and keep the export alive past
        # the completed park — a decode-role replica attaches it
        "kv_export_on_finish",
        # generation by diffusion over blocks: the request's
        # ``denoising_steps`` and confidence threshold, and the block in
        # progress, position -> (token, logprob, pass that unmasked it)
        "steps", "tau", "block",
    )

    def __init__(self, prompt, max_tokens, eos_id, resume_cache,
                 resume_pos, on_finish, deadline=None, generation_id=None,
                 prompt_dev=None, kv_export=False,
                 kv_export_on_finish=False):
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.eos_id = eos_id
        self.queue = _queue.Queue()
        self.forced = deque()
        self.pos = 0
        self.emitted = 0
        self.on_finish = on_finish
        self.resume_cache = resume_cache
        self.resume_pos = resume_pos
        self.finished = False   # terminal queue event delivered
        self.cancelled = False  # consumer abandoned the token iterator
        self.deadline = deadline  # time.monotonic() bound, or None
        self.generation_id = generation_id  # resumable when set
        # every emitted (token, logprob): the replay buffer for
        # client resume AND the re-admission feed for supervised restart
        self.history = []
        # bumped on every admission: step snapshots record it, so a
        # pipelined step dispatched for a PREVIOUS admission of this
        # same stream (cancelled, parked, resumed, re-admitted into the
        # same slot) can never deliver its stale token
        self.incarnation = 0
        # monotonic stamp of the latest (re-)enqueue: the scheduler's
        # queue-wait histogram measures submit -> slot admission
        self.enqueued_at = time.monotonic()
        self.table = None        # np [pages_per_seq] page-table row
        self.radix_nodes = None  # pinned radix path (prefix pages)
        self.span_pages = 0      # reserved logical pages
        self.table_w = None      # np [ring] window-class ring row
        self.w_done = 0          # window pages below this were handled
        self.prompt_dev = prompt_dev  # device prompt view, or None
        self.kv_export = bool(kv_export)
        self.kv_export_on_finish = bool(kv_export_on_finish)
        self.attach_cache = None  # imported KV export (device array)
        self.attach_pos = 0       # its valid-prefix end position
        self.steps = 0            # denoising passes a block (block step)
        self.tau = 1.0            # confidence threshold (1 = schedule)
        self.block = {}           # the block in progress

    def expired(self, now):
        return self.deadline is not None and now >= self.deadline


class _HungStep(Exception):
    """Internal: the watchdog's synthesized loop-death cause."""


class _PrefillTask:
    """A chunked admission in progress.

    The stream's slot is reserved (it sits in ``slots`` un-``ready``)
    while its padded prompt prefills ``chunk`` tokens per loop
    iteration — so one 2k-token prompt costs each co-batched decode
    stream a chunk's latency per step, never a whole-prompt stall.
    ``dest`` is the page-scatter vector for the final admit and
    ``full`` the token prefix the radix tree indexes on completion."""

    __slots__ = ("stream", "slot", "slot_cache", "padded", "start",
                 "logits_at", "chunk", "dest", "full", "done", "total")

    def __init__(self, stream, slot, slot_cache, padded, start,
                 logits_at, chunk, dest, full):
        self.stream = stream
        self.slot = slot
        self.slot_cache = slot_cache
        self.padded = padded        # np [pad_len] suffix token ids
        self.start = start          # absolute position of padded[0]
        self.logits_at = logits_at  # pad-relative last-prompt-token
        self.chunk = chunk
        self.dest = dest            # np [pages_per_seq] scatter ids
        self.full = full            # np full token prefix (radix key)
        self.done = 0               # padded positions prefilled
        self.total = len(padded)


# The decode loop's phases; every moment of the loop thread's life
# falls in exactly one (docs/observability.md "Tracing").
LOOP_PHASES = ("idle", "sweep", "admit", "dispatch", "fetch", "deliver")


class _LoopClock:
    """The decode loop's account of its own time, by phase.

    ``with phase("fetch"):`` opens the profiler span ``sched.fetch``
    (inert unless a profiler session runs, and then on the device
    trace's clock) and, on the way out, adds the elapsed
    ``time.monotonic()`` to the scheduler's ``fetch`` float.  The
    phases TILE the thread's life: a phase is charged from the moment
    the one before it closed (the few statements between two ``with``
    blocks belong to the later one; the loop's start-up, pool
    allocation included, to its first ``sweep``), and a nested phase
    (``idle`` inside ``sweep``) stops its parent's clock.  So the
    floats sum to the thread's wall time, and the host's milliseconds
    per step are a ratio of counters.  ONE loop thread owns a clock
    and its floats: plain adds, no lock, like the loop's histograms.

    Each charge also reads the thread's own CPU clock
    (``time.thread_time()``, ``CLOCK_THREAD_CPUTIME_ID``) and adds the
    part of the wall time the thread did NOT run to the phase's
    ``offcpu`` float: waiting for the GIL, a lock, a transfer or the
    device.  Where a host's CPU clock ticks coarsely it can charge a
    phase more CPU than wall time; that excess is kept and taken off
    the phase's next charges, so the float only grows, its total is
    the phase's wall less its CPU seconds, and it never exceeds the
    phase's wall seconds.  The clock is built in the loop thread,
    whose CPU it reads.
    """

    __slots__ = ("_seconds", "_offcpu", "_cpu_over", "_mark", "_cpu_mark",
                 "_open")

    def __init__(self, seconds, offcpu):
        self._seconds = seconds  # phase -> float, this thread's to add to
        self._offcpu = offcpu    # phase -> float, likewise
        # phase -> CPU seconds charged beyond the wall, still to take off
        self._cpu_over = dict.fromkeys(offcpu, 0.0)
        self._mark = time.monotonic()
        self._cpu_mark = time.thread_time()
        self._open = []          # enclosing phases, innermost last

    def _charge(self, phase):
        now, cpu = time.monotonic(), time.thread_time()
        wall = now - self._mark
        self._seconds[phase] += wall
        off = wall - (cpu - self._cpu_mark) - self._cpu_over[phase]
        if off >= 0.0:
            self._offcpu[phase] += off
            self._cpu_over[phase] = 0.0
        else:
            self._cpu_over[phase] = -off
        self._mark, self._cpu_mark = now, cpu

    @contextlib.contextmanager
    def __call__(self, phase):
        if self._open:
            self._charge(self._open[-1])
        self._open.append(phase)
        try:
            with span("sched." + phase):
                yield
        finally:
            self._open.pop()
            self._charge(phase)


class _ControlledStep:
    """A configuration's step (``fns["step"]``) behind one transfer each
    way.

    What a step is told besides the weights, the pool and the logits is
    ONE int32 array ``[max_slots, width]``, a row a slot (``picture``):
    the page-table row, the window-class ring row of a two-class pool,
    then the row's vector, ``position, active, forced token, forced``
    (a token a step) or ``denoising steps, threshold bits, active`` (a
    block a step).  The device keeps its copy from one step to the next
    and advances it as the host does (a live row's position + 1, a
    forced token spent), so a step sends the host's picture only where
    it differs from what the device holds: an admission, a retirement,
    a forced token, a window move, or a new loop, which starts with no
    copy.  The step's results (tokens, logprobs, and a routed
    configuration's counts) come back as ONE int32 array (``unpack``).

    One instance a step function and geometry (``of``), so every loop
    over one function bundle, a restart's or another scheduler's,
    reuses the compiled program, which the profile names after the
    step's function; a loop's own copy travels as ``held``, never on
    the instance, so a demoted loop that wakes cannot touch its
    successor's."""

    _instances = weakref.WeakKeyDictionary()  # step -> {geometry: self}

    @classmethod
    def of(cls, step, pages_per_seq, ring, blk):
        by_geometry = cls._instances.setdefault(step, {})
        key = (pages_per_seq, ring, blk)
        if key not in by_geometry:
            by_geometry[key] = cls(step, *key)
        return by_geometry[key]

    def __init__(self, step, pages_per_seq, ring, blk):
        import jax

        # held weakly: the instance lives as long as its step does
        self._step = weakref.ref(step)
        self._tables = pages_per_seq  # columns of the page-table row
        self._ring = ring             # window-class ring columns, or 0
        self._blk = blk
        self._layout = None           # [(shape, dtype)] of the results

        def run(params, pages, logits, control):
            return self._control_step(params, pages, logits, control)

        run.__name__ = run.__qualname__ = getattr(step, "__name__", "step")
        self._run = jax.jit(run, donate_argnums=(1, 2))

    def picture(self, tables, tables_w, *vectors):
        """The host's picture of a step: the tables, then each
        ``[max_slots]`` vector as a column (a float32 one by its bits)."""
        cols = [v.view(np.int32) if v.dtype == np.float32
                else v.astype(np.int32) for v in vectors]
        return np.concatenate(
            [tables] + ([tables_w] if self._ring else [])
            + [c[:, None] for c in cols], axis=1)

    def _advance(self, picture):
        """What the device's copy holds after a step on ``picture``."""
        if self._blk:
            return picture
        n = self._tables + self._ring
        held = picture.copy()
        held[:, n] += held[:, n + 1]
        held[:, n + 2:] = 0
        return held

    def _control_step(self, params, pages, logits, control):
        import jax.numpy as jnp
        from jax import lax

        n = self._tables
        tables = control[:, :n]
        if self._ring:
            tables = {"full": tables, "window": control[:, n:n + self._ring]}
            n += self._ring
        if self._blk:
            steps, taus, active = (control[:, n + k] for k in range(3))
            args = (steps, lax.bitcast_convert_type(taus, jnp.float32),
                    active.astype(bool))
            advanced = control
        else:
            pos, active, forced, forced_mask = (
                control[:, n + k] for k in range(4))
            args = (pos, active.astype(bool), forced,
                    forced_mask.astype(bool))
            advanced = control.at[:, n].add(active).at[:, n + 2:].set(0)
        tokens, logps, logits, pages, *counts = self._step()(
            params, pages, logits, tables, *args)
        results = (tokens, logps, *counts)
        self._layout = [(r.shape, np.dtype(r.dtype)) for r in results]
        packed = jnp.concatenate([
            (lax.bitcast_convert_type(r, jnp.int32)
             if r.dtype == jnp.float32 else r.astype(jnp.int32)).reshape(-1)
            for r in results])
        return packed, logits, pages, advanced

    def __call__(self, params, pages, logits, picture, held):
        """Dispatch one step on the host's ``picture``.  ``held`` is
        ``(device copy, what it holds)`` after this loop's last step, or
        None.  Returns the packed results (a device array), the logits,
        the pages, the new ``held`` and whether the picture was sent."""
        sent = held is None or not np.array_equal(picture, held[1])
        packed, logits, pages, device = self._run(
            params, pages, logits, picture if sent else held[0])
        return packed, logits, pages, (device, self._advance(picture)), sent

    def unpack(self, packed):
        """The step's results as host arrays, from its fetched array."""
        out, at = [], 0
        for shape, dtype in self._layout:
            part = packed[at:at + math.prod(shape)]
            at += len(part)
            out.append((part.view(np.float32) if dtype == np.float32
                        else part.astype(dtype)).reshape(shape))
        return out


class DecodeScheduler:
    """The per-model continuous-batching loop.

    ``fns`` is the compiled bundle from ``llama.make_scheduler_fns`` and
    ``params`` the (possibly sharded/quantized) weight pytree.  One
    background thread owns ALL device state — the slotted cache and the
    per-slot logits are threaded (and donated) through its dispatches,
    so frontend threads never touch the device: they block on per-stream
    queues that the loop fans tokens into.

    A supervisor thread watches the loop: loop death (an unattributable
    step/fetch failure) restarts it with live streams re-admitted
    (``max_restarts`` per ``restart_window_s``, exponential backoff from
    ``restart_backoff_s``); a step stalled past ``step_timeout_s``
    (None = watchdog off; leave it off, or warm up first, where the
    first step's XLA compile could exceed it) is treated the same.
    Budget exhausted ⇒ permanent trip (unhealthy + typed failures).
    """

    def __init__(self, fns, params, max_slots, max_seq, max_pending=None,
                 fault_scope=None, step_timeout_s=None, max_restarts=5,
                 restart_window_s=60.0, restart_backoff_s=0.05,
                 replay_ttl_s=60.0, replay_capacity=256,
                 metrics=None, metric_labels=None,
                 prefill_chunk_tokens=256, prefix_cache=True,
                 kv_export=None, kv_import=None, kv_discard=None,
                 target_queue_ms=None, shed_interval_ms=100.0):
        if max_slots < 1:
            raise ValueError(
                "max_slots must be >= 1 (got {})".format(max_slots)
            )
        # replica identity at the shared fault-injection points, so a
        # multi-server chaos harness can fail ONE scheduler's decode
        # loop while its pool siblings keep serving
        self.fault_scope = fault_scope
        self._fns = fns
        self._params = params
        self._max_slots = max_slots
        self._max_seq = max_seq
        # admission backpressure: before continuous batching, decoupled
        # requests serialized (implicit backpressure); an unbounded
        # pending deque would let one client enqueue arbitrarily many
        # generations (each also holding a frontend thread)
        self._max_pending = (
            max_pending if max_pending is not None else max(32, 8 * max_slots)
        )
        # adaptive queue shedding (docs/resilience.md "Tail-latency
        # defense"): None = controller off, submit path byte-identical
        # to the fixed-cliff scheduler.  When set, admissions shed
        # (typed 429 + Retry-After from the control interval) once the
        # queue's sojourn exceeds target_queue_ms for a sustained
        # shed_interval_ms — max_pending stays as the hard backstop.
        # State is written by submit and the decode loop, both under
        # _cond (the loop notes sojourn inside its already-held locked
        # region: zero new lock acquisitions).  # guarded-by: _cond
        self._shed_ctl = (
            _CodelShedController(float(target_queue_ms) / 1e3,
                                 float(shed_interval_ms) / 1e3)
            if target_queue_ms else None
        )
        self._codel_sheds = 0  # guarded-by: _cond
        self._step_timeout_s = step_timeout_s
        self._max_restarts = int(max_restarts)
        self._restart_window_s = float(restart_window_s)
        self._restart_backoff_s = float(restart_backoff_s)
        self._replay_ttl_s = float(replay_ttl_s)
        self._replay_capacity = int(replay_capacity)
        self._cond = threading.Condition()
        self._pending = deque()  # guarded-by: _cond
        self._thread = None      # guarded-by: _cond
        self._supervisor = None  # guarded-by: _cond
        self._closed = False     # guarded-by: _cond
        self._draining = False   # guarded-by: _cond
        # restart budget exhausted: permanent  # guarded-by: _cond
        self._tripped = False
        # epoch demotes superseded (wedged) loop threads: every delivery
        # into stream queues checks it under _cond, so a zombie waking
        # after a watchdog restart can never double-emit into a stream
        # the new loop re-admitted  # guarded-by: _cond
        self._epoch = 0
        # (epoch, monotonic start) of the current device op, or None —
        # epoch-tagged so a demoted zombie's stale stamps can neither
        # trip the watchdog against a healthy successor loop nor erase
        # the successor's own beat  # guarded-by: _cond
        self._heartbeat = None
        # set by a dying loop for the supervisor  # guarded-by: _cond
        self._loop_error = None
        self._restarts = 0       # lifetime count (stats/ops)  # guarded-by: _cond
        # timestamps inside the window  # guarded-by: _cond
        self._recent_restarts = deque()
        # lifetime SlotQuarantined count  # guarded-by: _cond
        self._quarantined = 0
        # generation_id -> (stream, completed, expires_monotonic):
        # the bounded, TTL'd replay buffer  # guarded-by: _cond
        self._replay = OrderedDict()
        # every live (not yet terminally-delivered) stream, pending or
        # slotted: close() fails exactly this set when the loop cannot
        # (join timeout), and drain() waits on it  # guarded-by: _cond
        self._streams = set()
        # cumulative observability counters (stats() + /metrics).
        # Written only by the decode loop / resume path with _cond
        # already held where it is held anyway — never a NEW lock
        # acquisition on the hot path (open item 3's regression
        # lesson); they only ever grow, so a racing stats() read can
        # lag one step but never see a decrease.
        self._admitted_total = 0
        self._tokens_total = 0
        self._replay_hits = 0
        # paged-KV knobs: prompts whose padded prefill exceeds
        # ``prefill_chunk_tokens`` prefill in chunks of that many
        # tokens, ONE chunk per loop iteration, so a long prompt never
        # stalls co-batched decode for its whole length (None disables
        # chunking); ``prefix_cache`` enables the radix tree that
        # deduplicates shared prompt prefixes into shared pages.  Both
        # engage only when the model's fns say chunked/span prefill is
        # kernel-choice-safe (``span_safe``) — the same determinism
        # guard prefill_bucket applies to padding.
        self._prefill_chunk_tokens = (
            int(prefill_chunk_tokens) if prefill_chunk_tokens else None
        )
        self._prefix_cache = bool(prefix_cache)
        # prefix-cache accounting in TOKENS (hits = prompt tokens
        # served from shared pages, misses = prompt tokens prefilled)
        # and EVICTIONS in pages.  Same discipline as the counters
        # above: loop-written, only ever grow, racy reads may lag one
        # step but never decrease.
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_evictions = 0
        # what the steps attended and routed (loop-written, grow-only):
        # key positions of all attention layers, those a window layer
        # did not have to read, and the routed layers' counts fetched
        # with each step's tokens
        self._context_tokens = 0
        self._context_bytes = 0
        self._window_skipped_tokens = 0
        self._moe_layer_steps = 0
        self._moe_local_pairs = 0
        self._moe_experts_hit = 0
        # what the block steps did (generation by diffusion over blocks;
        # loop-written, grow-only, 0 for every other configuration): row
        # passes fetched, those that did nothing but commit a block,
        # commits that rode on the next block's first denoise pass, and
        # positions unmasked
        self._diffusion_row_passes = 0
        self._diffusion_commit_passes = 0
        self._diffusion_fused_commits = 0
        self._diffusion_tokens_unmasked = 0
        # steps dispatched that sent their control to the device (the
        # rest ran on the device's own advanced copy: _ControlledStep)
        self._control_uploads = 0
        # block length of a configuration that generates by diffusion
        # over blocks (llama.make_scheduler_fns "block_len"), else 0:
        # the step then carries a block a row, and what does not know
        # blocks yet is refused by name in submit
        self._block_len = int((fns or {}).get("block_len") or 0)
        # a pool of two page classes (window layers:
        # llama.make_scheduler_fns "window_class"): what still assumes
        # one table a sequence is refused by name in submit
        self._window_class = (fns or {}).get("window_class")
        # a latent page class (latent attention:
        # llama.make_scheduler_fns "latent_class"): what copies K/V rows
        # out of the pool is refused by name in submit
        self._latent_class = (fns or {}).get("latent_class")
        # the conv layers' windows beside the pool (llama.make_scheduler_fns
        # "conv_state"): what copies K/V rows alone is refused by name in
        # submit
        self._conv_state = (fns or {}).get("conv_state")
        # the bytes of windows the steps' rows read, and the windows
        # admissions wrote (loop-written, grow-only; 0 without conv
        # layers)
        self._state_bytes = 0
        self._state_writes = 0
        # what the whole-prompt prefills' flash kernel stepped through:
        # the block pairs it folds and those a square grid would
        # (llama.flash_prefill_pairs of the padded length; loop-written,
        # grow-only, 0 where prefills attend densely)
        self._flash_pairs_of = (fns or {}).get("flash_pairs")
        self._flash_pairs = 0
        self._flash_grid_pairs = 0
        # park-attach KV export hooks (tentpole 3 of ISSUE 12): a
        # disconnected resumable stream's gathered pages are handed to
        # ``kv_export(generation_id, cache, valid_pos)`` (the server
        # parks them in an XLA-shm region keyed by the id);
        # ``kv_import(generation_id)`` -> (cache, valid_pos) | None is
        # consulted on resume — hit means the re-admission SCATTERS the
        # parked pages and force-feeds one token instead of
        # re-prefilling prompt + history; ``kv_discard(generation_id)``
        # releases the export when its replay entry dies.  All three
        # optional: absent hooks keep the pre-export behavior exactly.
        self._kv_export = kv_export
        self._kv_import = kv_import
        self._kv_discard = kv_discard
        # (allocator, radix) of the CURRENT loop, for stats/gauges
        # (a restart rebuilds both with the device pool)
        self._pager = None  # guarded-by: _cond
        # the CURRENT loop's window-class allocator (two-class pools)
        self._window_alloc = None  # guarded-by: _cond
        # optional tpuserver.metrics latency histograms: the decode
        # loop is their ONLY writer, so single_writer children observe
        # lock-free (exact, and never a lock acquisition in _loop)
        self._queue_hist = None
        self._step_hist = None
        self._admit_hist = None
        self._first_token_hist = None
        if metrics is not None:
            labels = dict(metric_labels or {})
            names = tuple(sorted(labels))

            def loop_histogram(name):
                return metrics.histogram(
                    name, labelnames=names, single_writer=True,
                ).labels(**labels)

            self._queue_hist = loop_histogram(
                "tpu_scheduler_queue_wait_seconds")
            self._step_hist = loop_histogram("tpu_scheduler_step_seconds")
            self._admit_hist = loop_histogram("tpu_scheduler_admit_seconds")
            self._first_token_hist = loop_histogram(
                "tpu_scheduler_first_token_seconds")
        # seconds of the decode loop thread's life by phase, and of
        # them those it spent off the CPU (_LoopClock;
        # stats()["loop_seconds"] / ["loop_offcpu_seconds"],
        # tpu_scheduler_loop_seconds_total /
        # tpu_scheduler_loop_offcpu_seconds_total)
        self._loop_seconds = dict.fromkeys(LOOP_PHASES, 0.0)
        self._loop_offcpu_seconds = dict.fromkeys(LOOP_PHASES, 0.0)

    def _unsupported(self, what):
        from tpuserver.models.llama import UnsupportedArchitecture

        if self._block_len:
            return UnsupportedArchitecture(
                "{} is not served for a configuration that generates by "
                "diffusion over blocks: it assumes one token a row a "
                "step".format(what))
        if self._latent_class:
            return UnsupportedArchitecture(
                "{} is not served over a latent page class (latent "
                "attention): it copies K and V rows, and a latent row is "
                "neither".format(what))
        if self._conv_state:
            return UnsupportedArchitecture(
                "{} is not served for a configuration with conv layers: "
                "it copies K and V rows, and a sequence's conv windows "
                "would be left behind".format(what))
        return UnsupportedArchitecture(
            "{} is not served over a pool of two page classes (window "
            "layers): it assumes one page table a sequence".format(what))

    # -- frontend side -----------------------------------------------------

    def submit(self, prompt, max_tokens, eos_id=None, resume_cache=None,
               resume_pos=0, on_finish=None, deadline=None,
               generation_id=None, prompt_dev=None, kv_export=False,
               kv_export_on_finish=False, attach_cache=None,
               attach_pos=0, denoising_steps=None,
               confidence_threshold=None, batched=False):
        """Enqueue one generation; returns an iterator of
        ``(token, logprob)`` pairs that blocks as the decode loop
        produces them (each an :class:`Emitted`, stamped when the loop
        queued it).  ``batched`` yields instead, each time, the list of
        every pair already waiting (at least one; never waits for a
        second): a reader that sends what waits in one response.

        A configuration that generates by diffusion over blocks yields
        ``(block, None)`` pairs instead, one a finished block: ``block``
        is ``(tokens, logprobs, positions, unmask passes)``, four lists
        in position order, sent by the pass that unmasks the block's
        last position.  ``denoising_steps`` (1..block length, default
        the block length: one token a pass) and ``confidence_threshold``
        (0..1, default 1: the static schedule alone) are its per-request
        settings; generation runs in whole blocks and delivery stops at
        ``max_tokens``.

        ``resume_cache``/``resume_pos`` continue from a parked KV cache
        (the prompt replays through the batched step without emission);
        ``on_finish(cache_rows)`` receives the slot's final cache copy —
        the park hook.  ``deadline`` is a ``time.monotonic()`` bound:
        past it, a still-pending request fails before prefill and an
        in-flight one retires mid-generation, both with
        :class:`DeadlineExceeded`.  ``generation_id`` makes the
        generation *resumable*: its tokens are retained in the replay
        buffer after disconnect or completion and
        :meth:`resume` continues it with no duplicated or missing
        tokens.

        Disaggregated-serving hooks (ISSUE 16): ``kv_export_on_finish``
        exports the KV through the ``kv_export`` hook when the
        generation FINISHES (the prefill-phase leg completes after one
        token) and keeps the export alive past the completed park so a
        decode-role replica can attach it; ``attach_cache`` /
        ``attach_pos`` admit over an imported KV export — the cache
        scatters into a fresh page span and only ``prompt[attach_pos
        - 1:]`` force-feeds, skipping the re-prefill entirely (the
        decode-phase leg).  An out-of-range ``attach_pos`` falls back
        to the ordinary prefill path, gracefully."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("PROMPT_IDS must be non-empty")
        blk = self._block_len
        if not blk and (denoising_steps is not None
                        or confidence_threshold is not None):
            raise ValueError(
                "denoising_steps / confidence_threshold are settings of "
                "generation by diffusion over blocks; this model "
                "generates one token a step")
        steps = blk if denoising_steps is None else int(denoising_steps)
        tau = (1.0 if confidence_threshold is None
               else float(confidence_threshold))
        if blk and not (1 <= steps <= blk and 0.0 <= tau <= 1.0):
            raise ValueError(
                "denoising_steps must lie in 1..{} (got {}) and "
                "confidence_threshold in 0..1 (got {})".format(
                    blk, steps, tau))
        if (self._window_class or blk or self._latent_class
                or self._conv_state):
            for asked, what in (
                    (resume_cache is not None or on_finish is not None,
                     "park / resume of a KV cache (kv_cache_region)"),
                    (kv_export or kv_export_on_finish,
                     "KV export (kv_park / kv_phase=prefill)"),
                    (attach_cache is not None,
                     "KV attach (kv_attach)")):
                if asked:
                    raise self._unsupported(what)
        start = resume_pos if resume_cache is not None else 0
        if blk and -(-(len(prompt) + max_tokens) // blk) * blk \
                > self._max_seq:
            raise ValueError(
                "prompt ({}) + max_tokens ({}) in whole blocks of {} "
                "exceeds max sequence {}".format(
                    len(prompt), max_tokens, blk, self._max_seq))
        if start + len(prompt) + max_tokens > self._max_seq:
            raise ValueError(
                "position ({}) + prompt ({}) + max_tokens ({}) exceeds max "
                "sequence {}".format(
                    start, len(prompt), max_tokens, self._max_seq
                )
            )
        stream = _Stream(prompt, int(max_tokens), eos_id,
                         resume_cache, int(resume_pos), on_finish,
                         deadline=deadline, generation_id=generation_id,
                         prompt_dev=prompt_dev,
                         kv_export=kv_export and resume_cache is None,
                         kv_export_on_finish=(
                             kv_export_on_finish and kv_export
                             and resume_cache is None
                             and generation_id is not None))
        if (attach_cache is not None and resume_cache is None
                and 0 < int(attach_pos) <= len(prompt)):
            # phase-split decode admission: scatter the imported export
            # instead of prefilling; an out-of-range position falls
            # back to the prefill path (token-identical, just slower)
            stream.attach_cache = attach_cache
            stream.attach_pos = int(attach_pos)
        stream.steps, stream.tau = steps, tau
        with span("sched.submit"), self._cond:
            if self._closed:
                raise SchedulerClosed("scheduler is shut down")
            if self._tripped:
                raise SchedulerClosed(
                    "decode loop restart budget exhausted; the scheduler "
                    "is tripped — drain and restart the replica"
                )
            if self._draining:
                raise SchedulerClosed(
                    "scheduler is draining; not accepting new generations"
                )
            if self._shed_ctl is not None:
                retry_after = self._shed_ctl.on_arrival(
                    time.monotonic(), len(self._pending))
                if retry_after is not None:
                    self._codel_sheds += 1
                    raise AdmissionQueueFull(
                        "admission queue sojourn above target for a "
                        "full control interval ({} waiting "
                        "generations); retry later".format(
                            len(self._pending)),
                        retry_after=retry_after,
                    )
            if len(self._pending) >= self._max_pending:
                raise AdmissionQueueFull(
                    "scheduler admission queue is full ({} waiting "
                    "generations); retry later".format(len(self._pending))
                )
            if generation_id is not None:
                # a reused id supersedes any parked predecessor (and
                # its KV export)
                if self._replay.pop(generation_id, None) is not None \
                        and self._kv_discard is not None:
                    self._kv_discard(generation_id)
            self._pending.append(stream)
            self._streams.add(stream)
            self._ensure_running_locked()
            self._cond.notify_all()
        return self._drain(stream, batched)

    def resume(self, generation_id, from_seq=0, wait_s=5.0,
               deadline=None, batched=False):
        """Continue a parked generation: replays its buffered
        ``(token, logprob)`` history from ``from_seq`` (the first
        sequence number the caller has NOT seen), then — for an
        interrupted generation — splices live tokens from a re-admitted
        continuation (re-prefilled ``prompt + history``).  Raises
        :class:`UnknownGeneration` when the id was never issued, was
        already resumed, or aged out of the replay buffer.  Replay
        state is replica-local: resume the SAME endpoint that served
        the original request.

        A disconnected stream is only PARKED when the decode loop next
        reaps its cancelled slot, so a fast reconnect can arrive first;
        while the id still names a live stream, resume waits (up to
        ``wait_s``) for the park instead of turning the race into a
        terminal unknown-generation error.  ``deadline`` is the RESUME
        request's own monotonic bound (None lifts any bound): the
        original request's deadline died with its connection — a
        reconnect carrying a fresh timeout must not be killed by the
        stale one.  ``batched`` as in :meth:`submit`: the replayed
        pairs come as one list, then the live ones as they wait."""
        if self._block_len:
            raise self._unsupported(
                "resume of a generation (resume_generation_id)")
        from_seq = int(from_seq)
        # the park-race wait has its own bound; it must not clobber the
        # ``deadline`` parameter, which is the RECONNECT's own request
        # bound (None = unbounded) stamped onto the re-admitted stream
        wait_deadline = time.monotonic() + float(wait_s)
        discard_export = False
        with self._cond:
            while True:
                if self._closed:
                    raise SchedulerClosed("scheduler is shut down")
                self._sweep_replay_locked(time.monotonic())
                entry = self._replay.pop(generation_id, None)
                if entry is not None:
                    break
                live = any(st.generation_id == generation_id
                           for st in self._streams)
                remaining = wait_deadline - time.monotonic()
                if not live or remaining <= 0:
                    raise UnknownGeneration(
                        "unknown or expired generation id '{}' (replay "
                        "entries live {}s after disconnect; resume is "
                        "same-endpoint only)".format(
                            generation_id, self._replay_ttl_s)
                    )
                self._cond.wait(min(0.05, remaining))
            stream, completed, _ = entry
            if from_seq < 0 or from_seq > len(stream.history):
                # put the entry back: a malformed resume must not
                # destroy the (still valid) replay state
                self._replay[generation_id] = entry
                raise UnknownGeneration(
                    "resume point {} is beyond generation '{}' ({} "
                    "tokens emitted)".format(
                        from_seq, generation_id, len(stream.history))
                )
            replay = list(stream.history[from_seq:])
            if completed:
                # a finished generation's tail stays replayable for its
                # whole TTL (the client may lose more than one tail)
                self._replay[generation_id] = entry
            else:
                if self._tripped:
                    self._replay[generation_id] = entry
                    raise SchedulerClosed(
                        "decode loop restart budget exhausted; the "
                        "scheduler is tripped"
                    )
                if self._draining:
                    # same admission gate as submit(): re-admitting an
                    # interrupted generation is NEW decode work and must
                    # not sneak in mid-drain (completed-tail replays
                    # above stay served — they cost no decode)
                    self._replay[generation_id] = entry
                    raise SchedulerClosed(
                        "scheduler is draining; not accepting new "
                        "generations"
                    )
                # fresh queue: the abandoned one may hold tokens the old
                # consumer never took — those are re-delivered from the
                # history snapshot above, never from the stale queue
                stream.queue = _queue.Queue()
                stream.cancelled = False
                stream.finished = False
                stream.deadline = deadline  # the reconnect's own bound
                self._reset_for_readmission(stream)
                if (self._kv_import is not None and stream.kv_export
                        and stream.resume_cache is None):
                    # same-host attach: the park left the generation's
                    # gathered KV in a server-owned XLA-shm region —
                    # re-admission scatters it back and force-feeds one
                    # token instead of re-prefilling prompt + history.
                    # Import is one-shot (the export drops); any
                    # failure below falls back to the re-prefill path.
                    got = self._kv_import(generation_id)
                    if got is not None:
                        cache, valid = got
                        known = len(stream.prompt) + len(stream.history)
                        if 0 < valid <= known:
                            stream.attach_cache = cache
                            stream.attach_pos = int(valid)
                        # one-shot: the region drops AFTER _cond
                        # releases (unlink is syscall work)
                        discard_export = self._kv_discard is not None
                self._pending.append(stream)
                self._streams.add(stream)
                self._ensure_running_locked()
                self._cond.notify_all()
            # counted only once every validation gate passed: a
            # malformed/rejected resume served nothing from the buffer
            self._replay_hits += 1
        if discard_export:
            self._kv_discard(generation_id)

        def gen():
            live = None if completed else self._drain(stream, batched)
            try:
                if batched:
                    if replay:
                        yield replay
                else:
                    yield from replay
                if live is not None:
                    for item in live:
                        yield item
            finally:
                if live is not None and not stream.finished:
                    # consumer abandoned during the replay prefix: the
                    # live generator's own cancel hook never ran
                    stream.cancelled = True
                    live.close()

        return gen()

    @staticmethod
    def _drain(stream, batched=False):
        held = None  # an event taken behind a batch, for the next turn
        try:
            while True:
                kind, a, b = held or stream.queue.get()
                held = None
                if kind == "tok" and batched:
                    batch = [Emitted(a, b)]
                    while held is None:
                        try:
                            kind, a, b = stream.queue.get_nowait()
                        except _queue.Empty:
                            break
                        if kind == "tok":
                            batch.append(Emitted(a, b))
                        else:
                            held = kind, a, b
                    yield batch
                elif kind == "tok":
                    yield Emitted(a, b)
                elif kind == "err":
                    stream.finished = True
                    raise a
                else:  # "done"
                    stream.finished = True
                    return
        finally:
            if not stream.finished:
                # consumer gone mid-generation (client cancel/disconnect
                # closes the generator): flag the stream so the decode
                # loop retires its slot instead of burning batched steps
                # on tokens nobody will read (resumable streams park in
                # the replay buffer at that point)
                stream.cancelled = True

    def close(self, join_timeout=30):
        """Stop the loop; pending and in-flight requests error out.
        Subsequent submits raise SchedulerClosed.

        Deterministic even when the loop thread is wedged (e.g. inside a
        stuck device dispatch): if the join times out, every stream the
        loop did not terminally deliver gets a SchedulerClosed error
        here, so no consumer is left blocked on its queue forever."""
        with self._cond:
            already_closed = self._closed
            self._closed = True
            self._cond.notify_all()
            thread = self._thread
            supervisor = self._supervisor
        if thread is not None and not already_closed:
            # join once: a second close() (e.g. core.drain's final
            # close after the scheduler already drained) must not spend
            # another join_timeout re-waiting on a wedged thread —
            # the deterministic leftover-fail below still runs
            thread.join(timeout=join_timeout)
        if supervisor is not None and not already_closed:
            supervisor.join(timeout=5)
        # the loop normally fails every live stream on its way out; after
        # a join timeout (or a loop that never started) do it ourselves
        with self._cond:
            leftover = list(self._streams)
            self._streams.clear()
            self._pending.clear()
            parked_ids = list(self._replay)
            self._replay.clear()
            self._cond.notify_all()
        if self._kv_discard is not None:
            for gid in parked_ids:
                self._kv_discard(gid)
        err = SchedulerClosed("scheduler is shut down")
        for stream in leftover:
            stream.queue.put(("err", err, None))

    def drain(self, timeout=30.0):
        """Graceful drain: stop admission immediately, let pending and
        in-flight generations finish within ``timeout`` seconds, then
        close — deterministically failing whatever remains.  Submits
        during and after the drain raise SchedulerClosed."""
        deadline = time.monotonic() + timeout
        with self._cond:
            self._draining = True
            self._cond.notify_all()
            while self._streams:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
        self.close(join_timeout=max(0.1, deadline - time.monotonic()))

    @property
    def healthy(self):
        """False after the decode loop tripped permanently (restart
        budget exhausted) or the scheduler was closed — readiness
        probes report this through ``ServerReady``/``ModelReady`` so
        pools rotate flapping replicas out.  Reads under ``_cond``
        (reentrant — stats() calls this with it held) so a probe never
        sees a half-applied trip."""
        with self._cond:
            return not self._tripped and not self._closed

    def stats(self):
        """Introspection for tests and ops: live stream / pending counts
        and lifecycle flags.  ``live_streams`` counting to zero after
        traffic is the no-leaked-slots invariant chaos tests assert;
        ``restarts`` rising is the flapping signal ops rotate on.  The
        capacity bounds ``max_slots`` / ``max_pending`` ride along so a
        consumer (the fleet router's prober) can turn the counts into a
        utilization signal without extra configuration plumbing."""
        with self._cond:
            fns = self._fns or {}
            pager = self._pager
            if pager is not None:
                alloc, radix = pager
                pages_total = alloc.n_pages
                pages_free = alloc.free_count
                pages_cached = radix.unreferenced if radix is not None else 0
            else:
                # before the first loop start (or after close): the
                # pool is whatever the fns bundle will build
                pages_total = int(fns.get("n_pages", 0) or 0)
                pages_free = pages_total
                pages_cached = 0
            window_total = window_free = 0
            if self._window_class:
                window_total = self._window_class["n_pages"]
                window_free = (self._window_alloc.free_count
                               if self._window_alloc is not None
                               else window_total)
            return {
                "live_streams": len(self._streams),
                "pending": len(self._pending),
                "max_slots": self._max_slots,
                "max_pending": self._max_pending,
                "draining": self._draining,
                "closed": self._closed,
                "healthy": self.healthy,
                "tripped": self._tripped,
                "restarts": self._restarts,
                "quarantined": self._quarantined,
                "replay_entries": len(self._replay),
                "admitted": self._admitted_total,
                "tokens": self._tokens_total,
                "replay_hits": self._replay_hits,
                "codel_sheds": self._codel_sheds,
                "codel_shedding": bool(
                    self._shed_ctl is not None and self._shed_ctl.shedding),
                "prefix_hits": self._prefix_hits,
                "prefix_misses": self._prefix_misses,
                "prefix_evictions": self._prefix_evictions,
                "pages_total": pages_total,
                "pages_free": pages_free,
                "pages_cached": pages_cached,
                # the window class of a two-class pool (0 / 0 without)
                "window_pages_total": window_total,
                "window_pages_free": window_free,
                "context_tokens": self._context_tokens,
                "context_bytes": self._context_bytes,
                "window_skipped_tokens": self._window_skipped_tokens,
                "moe_layer_steps": self._moe_layer_steps,
                "moe_local_pairs": self._moe_local_pairs,
                "moe_experts_hit": self._moe_experts_hit,
                "diffusion_row_passes": self._diffusion_row_passes,
                "diffusion_commit_passes": self._diffusion_commit_passes,
                "diffusion_fused_commits": self._diffusion_fused_commits,
                "diffusion_tokens_unmasked":
                    self._diffusion_tokens_unmasked,
                # a block is committed by a pass of its own or by the
                # next block's first
                "diffusion_blocks_committed":
                    self._diffusion_commit_passes
                    + self._diffusion_fused_commits,
                "control_uploads": self._control_uploads,
                "state_bytes": self._state_bytes,
                "state_writes": self._state_writes,
                "flash_pairs": self._flash_pairs,
                "flash_grid_pairs": self._flash_grid_pairs,
                "loop_seconds": dict(self._loop_seconds),
                "loop_offcpu_seconds": dict(self._loop_offcpu_seconds),
                # a fact of the build, not a rate: which decode
                # attention the step executable holds
                "decode_attention": fns.get("decode_attention"),
            }

    # -- supervisor --------------------------------------------------------

    def _ensure_running_locked(self):
        """Start (or restart) the supervisor; it owns the loop thread.
        Called with ``_cond`` held."""
        if self._supervisor is None or not self._supervisor.is_alive():
            self._supervisor = threading.Thread(
                target=self._supervise, name="decode-supervisor",
                daemon=True,
            )
            self._supervisor.start()

    def _start_loop_locked(self):
        self._epoch += 1
        self._heartbeat = None
        self._loop_error = None
        self._thread = threading.Thread(
            target=self._run, args=(self._epoch,),
            name="decode-scheduler", daemon=True,
        )
        self._thread.start()

    def _beat(self, epoch, now):
        """Stamp (or clear, ``now=None``) this loop's device-op
        heartbeat.  A superseded loop's clear is dropped so a zombie
        cannot erase the live loop's beat mid-step.  Takes ``_cond``
        (reentrant — the loop's except hook calls this with it held):
        the watchdog compares (epoch, stamp) pairs, and a torn
        read-modify-write against a concurrent supervisor demotion
        could resurrect a cleared beat."""
        with self._cond:
            if now is not None:
                self._heartbeat = (epoch, now)
            else:
                hb = self._heartbeat
                if hb is not None and hb[0] == epoch:
                    self._heartbeat = None

    def _hung_locked(self, now):
        hb = self._heartbeat
        return (
            self._step_timeout_s is not None
            and hb is not None
            and hb[0] == self._epoch  # a zombie's stale stamp is inert
            and now - hb[1] > self._step_timeout_s
        )

    def _supervise(self):
        """Own the decode thread: start it, watch for death or a hung
        step, and restart it (re-admitting live streams) under the
        budget — or trip permanently when the budget is spent."""
        poll = 0.05 if self._step_timeout_s is not None else 0.5
        while True:
            with self._cond:
                if self._closed or self._tripped:
                    return
                if self._thread is None:
                    self._start_loop_locked()
                thread = self._thread
            thread.join(timeout=poll)
            death = None
            with self._cond:
                if self._closed:
                    return
                now = time.monotonic()
                self._sweep_replay_locked(now)
                if self._loop_error is not None:
                    # the loop died; its except hook already salvaged
                    # slotted streams back into _pending
                    death = self._loop_error
                    self._loop_error = None
                elif thread.is_alive() and self._hung_locked(now):
                    # wedged device dispatch: demote the thread (epoch
                    # bump — every delivery it attempts after waking is
                    # dropped) and salvage its streams from the registry
                    death = _HungStep(
                        "decode step exceeded step_timeout_s={}s".format(
                            self._step_timeout_s)
                    )
                    self._epoch += 1
                    self._heartbeat = None
                    self._thread = None
                    pending_set = set(self._pending)
                    for st in [s for s in self._streams
                               if s not in pending_set]:
                        if st.cancelled:
                            self._detach_locked(st)
                        else:
                            self._reset_for_readmission(st)
                            self._pending.appendleft(st)
                if death is None:
                    continue
                # restart budget: a sliding window of restart times
                while (self._recent_restarts
                       and now - self._recent_restarts[0]
                       > self._restart_window_s):
                    self._recent_restarts.popleft()
                if len(self._recent_restarts) >= self._max_restarts:
                    self._tripped = True
                    to_fail = list(self._streams)
                    self._streams.clear()
                    self._pending.clear()
                    self._cond.notify_all()
                else:
                    to_fail = None
                    self._recent_restarts.append(now)
                    self._restarts += 1
                    backoff = min(
                        self._restart_backoff_s
                        * (2 ** (len(self._recent_restarts) - 1)),
                        2.0,
                    )
                    # the FULL backoff must elapse (a transient device
                    # fault needs the pause to clear): every submit /
                    # delivery notify_all would otherwise cut the wait
                    # short and burn the whole restart budget in
                    # milliseconds.  Only close() interrupts.
                    backoff_until = now + backoff
                    while not self._closed:
                        remaining = backoff_until - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(remaining)
                    if self._closed:
                        return
                    if self._thread is None:
                        self._start_loop_locked()
            if to_fail is not None:
                err = SchedulerClosed(
                    "decode loop restart budget exhausted ({} restarts "
                    "in {}s) after: {}".format(
                        self._max_restarts, self._restart_window_s, death)
                )
                for st in to_fail:
                    st.queue.put(("err", err, None))
                return

    def _reset_for_readmission(self, stream):
        """Prepare a salvaged/resumed stream for a fresh admission: the
        new loop re-prefills ``prompt + history`` (or forced-feeds both
        over a parked cache), so emission continues exactly where it
        stopped.  Called with ``_cond`` held."""
        stream.pos = 0
        stream.forced.clear()
        stream.enqueued_at = time.monotonic()
        # paging state belonged to the dead loop's pool: the new loop
        # re-reserves pages (and re-matches the radix tree) on
        # re-admission
        stream.table = None
        stream.radix_nodes = None
        stream.span_pages = 0
        stream.table_w = None
        stream.w_done = 0
        # a pending attach-resume dies with the loop that would have
        # scattered it: the salvage re-admission falls back to the
        # re-prefill path (greedy decode makes both token-identical)
        stream.attach_cache = None
        stream.attach_pos = 0
        # a block in progress dies with the loop: the re-admission
        # starts it again from the delivered blocks
        stream.block = {}

    # -- replay buffer -----------------------------------------------------

    def _sweep_replay_locked(self, now):
        expired = [
            gid for gid, (_, _, expires) in self._replay.items()
            if expires <= now
        ]
        for gid in expired:
            self._replay.pop(gid, None)
            if self._kv_discard is not None:
                # the KV export shares the replay entry's lifetime: an
                # id nobody can resume anymore must not pin HBM/shm
                self._kv_discard(gid)

    def _park_locked(self, stream, completed):
        """Retain a resumable generation's history for later resume.
        Called with ``_cond`` held."""
        now = time.monotonic()
        self._sweep_replay_locked(now)
        if completed:
            # a completed park only ever serves history[from_seq:]
            # replays — drop the device state NOW, or up to
            # replay_capacity parked KV-cache copies (resume_cache) and
            # shm-pinning on_finish closures would sit in the buffer
            # for the whole TTL — and any KV export is dead weight (a
            # finished generation never re-decodes)
            stream.resume_cache = None
            stream.on_finish = None
            stream.attach_cache = None
            if (self._kv_discard is not None and stream.kv_export
                    and not stream.kv_export_on_finish):
                # a phase-export (kv_export_on_finish) OUTLIVES the
                # completed park on purpose: the decode-role replica
                # attaches it after this generation's prefill leg
                # finished.  It still dies with the replay entry's TTL
                # sweep (or an explicit drop), so nothing leaks.
                self._kv_discard(stream.generation_id)
        self._replay[stream.generation_id] = (
            stream, completed, now + self._replay_ttl_s
        )
        self._replay.move_to_end(stream.generation_id)
        while len(self._replay) > self._replay_capacity:
            gid, _ = self._replay.popitem(last=False)  # evict oldest
            if self._kv_discard is not None:
                self._kv_discard(gid)

    def _detach_locked(self, stream):
        """Retire a cancelled stream from the live registry; resumable
        ones park in the replay buffer instead of vanishing.  Called
        with ``_cond`` held."""
        self._streams.discard(stream)
        if stream.generation_id is not None and not stream.finished:
            self._park_locked(stream, completed=False)
        self._cond.notify_all()

    # -- decode loop -------------------------------------------------------

    def _fail(self, stream, exc, epoch=None):
        self._deliver(stream, ("err", exc, None), epoch)

    def _deliver(self, stream, event, epoch=None):
        """Deliver a terminal event and retire the stream from the live
        registry (never call while holding ``_cond`` — it takes it).
        With ``epoch``, delivery is dropped when the calling loop has
        been superseded (the new loop owns the stream)."""
        with self._cond:
            if epoch is not None and epoch != self._epoch:
                return
            self._streams.discard(stream)
            if event[0] == "done" and stream.generation_id is not None:
                # completed generations stay resumable for the TTL so a
                # client that lost the tail can replay it
                self._park_locked(stream, completed=True)
            self._cond.notify_all()
            # under the lock: a racing watchdog salvage must either see
            # this terminal delivery or run strictly before it
            stream.queue.put(event)

    def _run(self, epoch):
        slots = [None] * self._max_slots  # slot -> _Stream | None
        try:
            self._loop(slots, epoch)
        except Exception as e:  # noqa: BLE001 — loop death is the
            # supervisor's restart (or trip) signal; swallowing it here
            # would leave every consumer blocked forever on its queue
            with self._cond:
                if self._epoch != epoch:
                    return  # superseded zombie: the new loop owns it all
                self._loop_error = e
                self._beat(epoch, None)
                if self._thread is threading.current_thread():
                    # unregister NOW, under the lock: a submit racing
                    # this cleanup must see no live thread; the
                    # supervisor starts the replacement
                    self._thread = None
                # salvage: slotted streams re-enter the pending queue at
                # the FRONT (they were admitted first) with their state
                # reset for re-prefill of prompt + history
                for st in reversed([s for s in slots if s is not None]):
                    if st not in self._streams:
                        continue  # already terminally delivered
                    if st.cancelled:
                        self._detach_locked(st)
                        continue
                    self._reset_for_readmission(st)
                    self._pending.appendleft(st)
                self._cond.notify_all()
                self._ensure_running_locked()

    def _loop(self, slots, epoch):
        import jax.numpy as jnp

        with self._cond:
            # this loop thread's OWN copy of the totals so far: a
            # demoted zombie that wakes keeps adding to its orphaned
            # copy, never under its successor's feet
            self._loop_seconds = seconds = dict(self._loop_seconds)
            self._loop_offcpu_seconds = offcpu = dict(
                self._loop_offcpu_seconds)
        phase = _LoopClock(seconds, offcpu)
        fns = self._fns
        # block length of a configuration that generates by diffusion
        # over blocks (a step then carries a block a row), else 0
        blk = self._block_len
        page = fns["page_size"]
        ppseq = fns["pages_per_seq"]
        n_pages = fns["n_pages"]
        # chunked/shared prefill runs spans through the dense cached
        # path; on a flash-prefill config that could flip a near-tie
        # greedy argmax vs the one-shot kernel, so both fall back to
        # whole-prompt prefill there (the prefill_bucket determinism
        # policy, applied to spans)
        span_safe = fns["span_safe"]
        chunk = self._prefill_chunk_tokens if span_safe else None
        pages = fns["init_cache"]()
        logits = fns["init_logits"]()
        alloc = PageAllocator(n_pages, page)
        radix = (RadixPrefixCache(page)
                 if self._prefix_cache and span_safe else None)
        # conv layers: their windows ride beside the pool
        # ({"kv", "conv"}); the bytes a row's windows hold, as the array
        # stores them, or 0
        conv = self._conv_state
        kv = pages["kv"] if conv else pages
        state_row_bytes = (int(pages["conv"].nbytes) // self._max_slots
                           if conv else 0)
        # the window class of a two-class pool: its own allocator and
        # ring tables (entry p % ring names logical page p); wc is None
        # for every one-class configuration, whose loop is unchanged
        wc = self._window_class
        if wc:
            window, ring, n_wpages = wc["window"], wc["ring"], wc["n_pages"]
            alloc_w = PageAllocator(n_wpages, page)
            tables_w = np.full((self._max_slots, ring), n_wpages, np.int32)
            n_layers_w = int(pages["window"].shape[0])
            n_layers_all = n_layers_w + int(pages["full"].shape[0])
        else:
            alloc_w = None
            n_layers_all = int(getattr(kv, "shape", (0,))[0])
        # bytes one cached token holds over all attention layers, each
        # layer's in its page class as the pool's array stores it
        # (padding included; 0 for a test double's pool)
        token_bytes = sum(
            int(getattr(pool, "nbytes", 0)) // (count * page)
            for pool, count in (
                ((pages["full"], n_pages), (pages["window"], n_wpages))
                if wc else ((kv, n_pages),)))
        with self._cond:
            # stats/gauges read the live pool through this reference;
            # a supervised restart rebuilds pool, allocator and radix
            # together (the radix cache restarts cold and re-warms)
            self._pager = (alloc, radix)
            self._window_alloc = alloc_w
        # per-slot page tables (sentinel rows are inert); mutated in
        # place as slots turn over — each dispatch packs the then-current
        # content into its picture, which reaches the device only where
        # it differs from the device's own copy
        tables = np.full((self._max_slots, ppseq), n_pages, np.int32)
        ready = [False] * self._max_slots  # prefill complete
        prefilling = {}                    # slot -> _PrefillTask
        inflight = None  # (packed results on the device, snapshot)
        controlled = _ControlledStep.of(
            fns["step"], ppseq, ring if wc else 0, blk)
        held = None  # this loop's (device copy of the control, its value)

        def clear_slot(slot):
            slots[slot] = None
            ready[slot] = False
            tables[slot] = n_pages
            if wc:
                tables_w[slot] = n_wpages

        def free_window_pages(stream):
            """Everything the stream still holds in the window class."""
            if stream.table_w is not None:
                alloc_w.free(
                    int(p) for p in stream.table_w if p != n_wpages)
                stream.table_w = None

        def move_window(slot, stream):
            """Before a step at ``stream.pos``: window pages that now
            lie wholly behind the window go back to the allocator,
            unless the ring will need their entry again (logical page
            p + ring is still inside the reserved span: the entry is
            reused in place, as it was reserved for)."""
            dead = max(0, stream.pos + 1 - window) // page
            for p in range(stream.w_done, dead):
                if p + ring >= stream.span_pages:
                    entry = p % ring
                    if stream.table_w[entry] != n_wpages:
                        alloc_w.free([int(stream.table_w[entry])])
                        stream.table_w[entry] = n_wpages
                        tables_w[slot, entry] = n_wpages
            stream.w_done = max(stream.w_done, dead)

        def superseded():
            """True once a watchdog demotion replaced this loop: a
            thread waking from a hung dispatch must stop mutating
            stream state the successor loop now owns (its own pool,
            tables and tasks die with it and need no cleanup)."""
            with self._cond:
                return self._epoch != epoch

        def release_pages(stream, insert=True):
            """Return a stream's pages to the pool.  The pinned radix
            path unrefs; full pages covered by fed tokens donate back
            as unpinned cached entries (a later resume, restart
            re-admission, or sibling prompt hits them instead of
            re-prefilling — content-addressed, so always safe);
            everything else frees.  ``insert=False`` for poisoned or
            failed streams whose written KV must not be cached."""
            with self._cond:
                if self._epoch != epoch:
                    # superseded (watchdog demotion mid-dispatch): the
                    # stream may already be re-admitted by the NEW
                    # loop with paging state from the NEW pool —
                    # touching stream.table/radix_nodes here would
                    # corrupt it (this loop's own pool dies with it)
                    return
            if wc:
                free_window_pages(stream)
            table = stream.table
            nodes = stream.radix_nodes or []
            if table is None:
                # failed before the span reserved: only the matched
                # pins (if any) need returning
                if nodes:
                    radix.release(nodes)
                stream.radix_nodes = None
                return
            path_len = len(nodes)
            owned = [int(table[d])
                     for d in range(path_len, stream.span_pages)]
            if (insert and radix is not None
                    and stream.resume_cache is None):
                known = (list(int(t) for t in stream.prompt)
                         + [t for t, _ in stream.history])
                insertable = min(stream.pos, len(known)) // page
                donate = max(0, insertable - path_len)
                if donate:
                    _, _, dup_ids = radix.insert_tail(
                        nodes, known, path_len, owned[:donate],
                        pin=False)
                    alloc.free(dup_ids)
                    owned = owned[donate:]
            alloc.free(owned)
            if nodes:
                radix.release(nodes)
            stream.table = None
            stream.radix_nodes = None
            stream.span_pages = 0

        def export_kv(stream):
            """Park a reaped resumable stream's gathered KV through the
            ``kv_export`` hook (the server owns it as an XLA-shm region
            keyed by the generation id).  The valid prefix is exactly
            ``prompt + history`` positions: every dispatched-but-
            unfetched step's write lands beyond it, so the export can
            never contain a token the client was not delivered.  Runs
            BEFORE ``release_pages`` — the gather captures the current
            pool value, so later page reuse cannot corrupt it.  Called
            under the loop's ``_cond`` at both reap sites: the cost is
            an async gather dispatch plus a few shm syscalls (the
            export stores the device reference — no copy), paid only
            on the rare cancel reap.  Export is an optimization: any
            failure silently falls back to the re-prefill resume
            path."""
            if (self._kv_export is None or not stream.kv_export
                    or stream.generation_id is None
                    or stream.resume_cache is not None
                    or stream.table is None):
                return
            valid = len(stream.prompt) + len(stream.history)
            if valid <= 0:
                return
            try:
                parked = fns["gather"](pages, stream.table)
                self._kv_export(stream.generation_id, parked, valid)
            except Exception:  # noqa: BLE001 — optimization only
                pass

        def complete_admission(slot, stream, full):
            """Post-admit bookkeeping: donate the prompt's full pages
            to the radix tree NOW (pinned — siblings admitted next
            iteration already share them), publish the page table, and
            count the admission."""
            if superseded():
                return  # zombie: the stream belongs to the new loop
            if (radix is not None and full is not None
                    and stream.resume_cache is None):
                path_len = len(stream.radix_nodes)
                donate = stream.pos // page - path_len
                if donate > 0:
                    owned = [int(stream.table[d])
                             for d in range(path_len, path_len + donate)]
                    appended, dups, dup_ids = radix.insert_tail(
                        stream.radix_nodes, full, path_len, owned,
                        pin=True)
                    for d, existing in dups:
                        # a concurrent sibling already donated this
                        # page's content: the tree copy wins (equal
                        # bytes — content-addressed) and ours frees
                        stream.table[d] = existing
                    alloc.free(dup_ids)
                    stream.radix_nodes.extend(appended)
            tables[slot] = stream.table
            if wc:
                tables_w[slot] = stream.table_w
            ready[slot] = True
            self._admitted_total += 1
            if conv:
                self._state_writes += 1
            if self._queue_hist is not None:
                self._queue_hist.observe(
                    time.monotonic() - stream.enqueued_at)

        def start_admission(slot, stream):
            """Reserve the stream's page span and run (or begin) its
            prefill.  The slot is already reserved in ``slots``; on a
            shed or per-request fault it is cleared here."""
            nonlocal pages, logits
            t = self._step_timeout_s
            try:
                if superseded():
                    # a previous admission's hung dispatch demoted this
                    # loop mid-iteration: the remaining admissions are
                    # the NEW loop's to make
                    return
                # admission-failure chaos hook
                faults.fire("scheduler.admit", self.fault_scope)
                # new incarnation: step snapshots taken against a
                # previous admission of this stream object become inert
                stream.incarnation += 1
                if stream.attach_cache is not None:
                    # park-attach resume (tentpole 3): the generation's
                    # exported KV pages scatter straight back into a
                    # fresh page span and ONE token (the last of the
                    # valid prefix, rewritten in place) force-feeds to
                    # regenerate the logits — no re-prefill of
                    # prompt + history.  Token-identical to the
                    # re-prefill path by greedy determinism
                    # (test-pinned in tests/test_shm_data_plane.py).
                    known = [int(t_) for t_ in stream.prompt] + [
                        t_ for t_, _ in stream.history]
                    start = stream.attach_pos - 1
                    span_end = len(stream.prompt) + stream.max_tokens
                    span_pages = pages_for(span_end, page)
                    stream.radix_nodes = []
                    owned = alloc.alloc(span_pages)
                    if owned is None and radix is not None:
                        freed = radix.evict(span_pages - alloc.free_count)
                        self._prefix_evictions += len(freed)
                        alloc.free(freed)
                        owned = alloc.alloc(span_pages)
                    if owned is None:
                        self._fail(stream, AdmissionQueueFull(
                            "kv page pool exhausted: attach-resume "
                            "needs {} pages but only {} are free; "
                            "retry later".format(
                                span_pages, alloc.free_count)), epoch)
                        clear_slot(slot)
                        return
                    table = np.full((ppseq,), n_pages, np.int32)
                    table[:span_pages] = owned
                    stream.table = table
                    stream.span_pages = span_pages
                    t = self._step_timeout_s
                    self._beat(epoch,
                               time.monotonic() + 9 * t if t else None)
                    slot_logits = jnp.zeros(
                        (1, logits.shape[1]), logits.dtype)
                    stream.forced.extend(known[start:])
                    stream.pos = start
                    attach_cache = stream.attach_cache
                    stream.attach_cache = None  # consumed
                    pages, logits = fns["admit"](
                        pages, logits, jnp.asarray(attach_cache),
                        slot_logits, table, slot)
                    complete_admission(slot, stream, None)
                    return
                replayed = [t_ for t_, _ in stream.history]
                start = (stream.resume_pos
                         if stream.resume_cache is not None else 0)
                full = (
                    np.concatenate(
                        [stream.prompt, np.asarray(replayed, np.int32)])
                    if replayed else stream.prompt
                )
                prefill_len = start + len(full)
                # the whole potential span reserves up front, so decode
                # can never run out of pages mid-generation: exhaustion
                # is a typed admission-time shed, not an OOM
                span_end = start + len(stream.prompt) + stream.max_tokens
                if blk:
                    # reservation by block: generation runs in whole
                    # blocks, whatever is delivered of the last
                    span_end = -(-span_end // blk) * blk
                span_pages = pages_for(span_end, page)
                matched_nodes = []
                shared_pages = 0
                if radix is not None and stream.resume_cache is None:
                    nodes, _ids = radix.match(full)
                    # cap so the prompt's LAST token always re-runs:
                    # its logits seed the first decode step
                    shared_pages = min(
                        len(nodes), (prefill_len - 1) // page)
                    matched_nodes = nodes[:shared_pages]
                    # recorded on the stream BEFORE anything can fail:
                    # the exception/shed paths unpin via
                    # release_pages(stream), which reads this field
                    stream.radix_nodes = list(matched_nodes)
                    if matched_nodes:
                        # pin BEFORE any eviction can run for this
                        # admission's own allocation
                        radix.acquire(matched_nodes)
                shared_len = shared_pages * page
                needed = span_pages - shared_pages
                owned = alloc.alloc(needed)
                if owned is None and radix is not None:
                    freed = radix.evict(needed - alloc.free_count)
                    self._prefix_evictions += len(freed)
                    alloc.free(freed)
                    owned = alloc.alloc(needed)
                if owned is None:
                    release_pages(stream, insert=False)  # unpin only
                    self._fail(stream, AdmissionQueueFull(
                        "kv page pool exhausted: admission needs {} "
                        "pages but only {} are free and every cached "
                        "page is pinned by a live stream; retry "
                        "later".format(needed, alloc.free_count)), epoch)
                    clear_slot(slot)
                    return
                # counted only once the reservation SUCCEEDED: a shed
                # admission served nothing and prefilled nothing, so it
                # must not skew the hit-rate perfanalyzer window-diffs
                if stream.resume_cache is None:
                    if radix is not None:
                        self._prefix_hits += shared_len
                    self._prefix_misses += prefill_len - shared_len
                dest_w = None
                if wc:
                    # the window class: the prompt's last window and
                    # what decode will add, at most one ring; the
                    # prompt's pages behind the window are never held
                    first_w = max(0, prefill_len + 1 - window) // page
                    need_w = min(span_pages, first_w + ring) - first_w
                    held_w = alloc_w.alloc(need_w)
                    if held_w is None:
                        alloc.free(owned)
                        self._fail(stream, AdmissionQueueFull(
                            "kv page pool exhausted: admission needs {} "
                            "window-class pages but only {} are free; "
                            "retry later".format(
                                need_w, alloc_w.free_count)), epoch)
                        clear_slot(slot)
                        return
                    stream.table_w = np.full((ring,), n_wpages, np.int32)
                    dest_w = np.full((ppseq,), n_wpages, np.int32)
                    for n, pid in enumerate(held_w):
                        stream.table_w[(first_w + n) % ring] = pid
                        dest_w[first_w + n] = pid
                    stream.w_done = first_w
                table = np.full((ppseq,), n_pages, np.int32)
                for d, node in enumerate(matched_nodes):
                    table[d] = node.page
                table[shared_pages:span_pages] = owned
                stream.table = table
                if stream.radix_nodes is None:
                    stream.radix_nodes = []  # radix off / resume path
                stream.span_pages = span_pages
                # prefill dispatches are watchdogged like steps, with
                # the compile headroom admissions get (future-dated
                # stamp = a 10x deadline: a novel bucket may
                # legitimately compile)
                self._beat(epoch, time.monotonic() + 9 * t if t else None)
                if stream.resume_cache is not None:
                    # parked-cache restore: the parked contiguous row
                    # scatters into the reserved pages (only READ —
                    # the region's copy stays valid for the next
                    # resume) and the prompt (+ history, after a
                    # restart) replays as forced tokens
                    slot_logits = jnp.zeros(
                        (1, logits.shape[1]), logits.dtype)
                    stream.forced.extend(int(t_) for t_ in stream.prompt)
                    stream.forced.extend(int(t_) for t_ in replayed)
                    stream.pos = start
                    pages, logits = fns["admit"](
                        pages, logits, jnp.asarray(stream.resume_cache),
                        slot_logits, table, slot)
                    complete_admission(slot, stream, None)
                    return
                suffix = np.asarray(full[shared_len:], np.int32)
                suffix_len = len(suffix)
                if shared_pages:
                    # restore the shared prefix into the single-row
                    # cache, then prefill only the unique suffix on
                    # top of it — the shared-system-prompt admission
                    # pays for its suffix alone
                    prefix_table = np.full((ppseq,), n_pages, np.int32)
                    prefix_table[:shared_pages] = table[:shared_pages]
                    slot_cache = fns["gather"](pages, prefix_table)
                    dest = table.copy()
                    # shared pages live in the pool already: never
                    # rewrite them from this admission's scatter
                    dest[:shared_pages] = n_pages
                else:
                    slot_cache = None
                    dest = table
                if chunk is not None and suffix_len > chunk:
                    pad_len = min(-(-suffix_len // chunk) * chunk,
                                  self._max_seq - shared_len)
                    padded = np.zeros((pad_len,), np.int32)
                    padded[:suffix_len] = suffix
                    if slot_cache is None:
                        slot_cache = fns["init_slot_cache"]()
                    prefilling[slot] = _PrefillTask(
                        stream, slot, slot_cache, padded, shared_len,
                        suffix_len - 1, chunk, dest, full)
                    return
                if shared_pages:
                    bucket = 8
                    while bucket < suffix_len:
                        bucket <<= 1
                    bucket = min(bucket, self._max_seq - shared_len)
                    padded = np.zeros((bucket,), np.int32)
                    padded[:suffix_len] = suffix
                    with span("sched.prefill", prompt_len=suffix_len):
                        slot_logits, slot_cache = fns["prefill_span"](
                            self._params, slot_cache,
                            jnp.asarray(padded)[None, :], shared_len,
                            suffix_len - 1)
                    if superseded():
                        return  # demoted mid-dispatch: mutate nothing
                else:
                    # cold one-shot admission: the pre-paging bucketed
                    # prefill, byte-for-byte (prefill_bucket keeps the
                    # kernel choice, padding rows stay masked)
                    bucket = fns["prefill_bucket"](suffix_len)
                    if self._flash_pairs_of is not None:
                        pairs, grid = self._flash_pairs_of(bucket)
                        self._flash_pairs += pairs
                        self._flash_grid_pairs += grid
                    if (stream.prompt_dev is not None and not replayed
                            and stream.resume_cache is None):
                        # zero-copy data plane: the prompt is already a
                        # device-resident XLA-shm segment view — pad it
                        # on device (zeros + scatter of the view) so
                        # the ids never stage through the host
                        tokens_in = jnp.zeros(
                            (bucket,), jnp.int32
                        ).at[:suffix_len].set(
                            stream.prompt_dev.astype(jnp.int32)
                        )[None, :]
                    else:
                        padded = np.zeros((bucket,), np.int32)
                        padded[:suffix_len] = suffix
                        tokens_in = jnp.asarray(padded)[None, :]
                    with span("sched.prefill", prompt_len=suffix_len):
                        slot_cache = fns["init_slot_cache"]()
                        slot_logits, slot_cache = fns["prefill"](
                            self._params, slot_cache, tokens_in,
                            suffix_len)
                    if superseded():
                        return  # demoted mid-dispatch: mutate nothing
                stream.pos = prefill_len
                if wc:
                    dest = {"full": dest, "window": dest_w}
                pages, logits = fns["admit"](
                    pages, logits, slot_cache, slot_logits, dest, slot)
                complete_admission(slot, stream, full)
            except Exception as e:  # noqa: BLE001 — per-request fault
                release_pages(stream, insert=False)
                self._fail(stream, e, epoch)
                clear_slot(slot)
            finally:
                self._beat(epoch, None)

        def run_prefill_chunk():
            """One chunk of the oldest in-progress chunked prefill —
            a single bounded dispatch interleaved with the decode
            step, so co-batched streams keep emitting."""
            nonlocal pages, logits
            if superseded():
                return
            slot, task = next(iter(prefilling.items()))
            stream = task.stream
            n = min(task.chunk, task.total - task.done)
            tok = jnp.asarray(
                task.padded[task.done:task.done + n])[None, :]
            rel = task.logits_at - task.done
            rel = rel if 0 <= rel < n else 0
            t = self._step_timeout_s
            self._beat(epoch, time.monotonic() + 9 * t if t else None)
            try:
                with span("sched.prefill", prompt_len=n):
                    chunk_logits, task.slot_cache = fns["prefill_span"](
                        self._params, task.slot_cache, tok,
                        task.start + task.done, rel)
                if superseded():
                    return  # demoted mid-dispatch: mutate nothing
                task.done += n
                if task.done < task.total:
                    return
                del prefilling[slot]
                stream.pos = task.start + task.logits_at + 1
                pages, logits = fns["admit"](
                    pages, logits, task.slot_cache, chunk_logits,
                    task.dest, slot)
                complete_admission(slot, stream, task.full)
            except Exception as e:  # noqa: BLE001 — per-request fault
                prefilling.pop(slot, None)
                release_pages(stream, insert=False)
                self._fail(stream, e, epoch)
                clear_slot(slot)
            finally:
                self._beat(epoch, None)

        def timed_admission(fn, *args):
            """One piece of admission work, entry to return, shed or
            not: host time that holds the loop, and with it every
            stream, so it has a histogram of its own (queue wait less
            this is the wait for the loop to come round)."""
            began = time.monotonic()
            fn(*args)
            if self._admit_hist is not None:
                self._admit_hist.observe(time.monotonic() - began)

        def emit(st, tok, lp):
            """One token (of a block configuration: one finished
            block) onto its stream's queue (``_cond`` held), with the
            ``time.monotonic()`` it went there.  A stream's first token
            on its first admission is the server-side time to first
            token; resumes and re-admissions after a restart
            (``incarnation`` > 1) restarted the stamp and do not
            observe."""
            if (st.emitted == 0 and st.incarnation == 1
                    and self._first_token_hist is not None):
                self._first_token_hist.observe(
                    time.monotonic() - st.enqueued_at)
            if blk:
                # a finished block: ``tok`` is its (tokens, logprobs,
                # positions, unmask passes), already cut to what is
                # still to be delivered
                st.history.extend(zip(tok[0], tok[1]))
                st.emitted += len(tok[0])
                self._tokens_total += len(tok[0])
            else:
                st.history.append((tok, lp))
                st.emitted += 1
                self._tokens_total += 1
            st.queue.put(("tok", (tok, lp), time.monotonic()))

        def block_pass(st, row, logcs):
            """One fetched pass of a live row's block (``_cond`` held):
            ``row`` / ``logcs`` are the step's results for the row
            (``llama.paged_block_step``).  Counts the pass and the
            commit it made or carried, records what it unmasked, and
            when no position of the block is left masked sends the
            block.  Returns ``(poisoned, finished)``."""
            start, commit, n_pass, fused = (int(n) for n in row[2 * blk:])
            self._diffusion_row_passes += 1
            # the keys the pass attended, every attention layer; a
            # commit that rode along attended the ``start`` keys up to
            # its own block's end
            self._context_tokens += (
                start + blk + fused * start) * n_layers_all
            self._context_bytes += (
                start + blk + fused * start) * token_bytes
            if fused:
                self._diffusion_fused_commits += 1
            elif commit:
                # the pass did nothing else
                self._diffusion_commit_passes += 1
                return False, False
            for j in np.flatnonzero(row[blk:2 * blk]):
                lp = float(logcs[j])
                if not np.isfinite(lp):
                    return True, False
                st.block[start + int(j)] = (int(row[j]), lp, n_pass)
                self._diffusion_tokens_unmasked += 1
            # positions below st.pos were given (the prompt's rest)
            if len(st.block) < start + blk - max(start, st.pos):
                return False, False
            at = sorted(st.block)[:st.max_tokens - st.emitted]
            done = [st.block[p] for p in at]
            st.block = {}
            st.pos = start + blk
            emit(st, ([t for t, _, _ in done], [lp for _, lp, _ in done],
                      at, [n for _, _, n in done]), None)
            return False, (st.emitted >= st.max_tokens or (
                st.eos_id is not None
                and any(t == st.eos_id for t, _, _ in done)))

        def finish(stream, slot):
            if stream.on_finish is not None:
                # gather+park is a device dispatch too: under the
                # watchdog, with the same compile headroom admissions
                # get (a future-dated stamp = a 10x deadline)
                t = self._step_timeout_s
                self._beat(epoch,
                           time.monotonic() + 9 * t if t else None)
                try:
                    parked = fns["gather"](pages, stream.table)
                    if superseded():
                        return  # never park a stale copy over the
                        # successor loop's own park
                    stream.on_finish(parked)
                except Exception as e:  # noqa: BLE001 — park is
                    # per-stream
                    self._fail(stream, e, epoch)
                    release_pages(stream)
                    clear_slot(slot)
                    return
                finally:
                    self._beat(epoch, None)
            if stream.kv_export_on_finish:
                # disaggregated prefill leg: the finished generation's
                # KV (prompt + the one emitted token) exports BEFORE
                # its pages free — the decode-role replica attaches
                # this region instead of re-prefilling
                export_kv(stream)
            release_pages(stream)
            self._deliver(stream, ("done", None, None), epoch)
            clear_slot(slot)

        while True:
            with phase("sweep"):
                expired = []
                with self._cond:
                    if self._epoch != epoch:
                        return  # superseded by a watchdog restart
                    while (
                        not self._closed
                        and not self._draining
                        and not self._pending
                        and inflight is None
                        and not any(s is not None for s in slots)
                    ):
                        with phase("idle"):
                            self._cond.wait()
                        if self._epoch != epoch:
                            return
                    if self._closed:
                        pending = list(self._pending)
                        self._pending.clear()
                        break
                    if (
                        self._draining
                        and not self._pending
                        and inflight is None
                        and not any(s is not None for s in slots)
                    ):
                        # drain complete: every accepted generation finished;
                        # exit cleanly so drain() sees a closed scheduler
                        self._closed = True
                        pending = []
                        break
                    # reap cancelled streams first: their consumers are gone,
                    # so the slot (and its pages) free for waiting work (no
                    # park of the KV — resumable streams keep only their
                    # token history; their full pages donate to the radix
                    # cache, so the resume's re-prefill is mostly a hit)
                    for i, st in enumerate(slots):
                        if st is not None and st.cancelled:
                            prefilling.pop(i, None)
                            if ready[i]:
                                # park-export before the pages free: the
                                # resumable stream's attach-resume rides it
                                export_kv(st)
                            release_pages(st)
                            self._detach_locked(st)
                            clear_slot(i)
                    # deadline sweep: a pending request past its deadline
                    # fails BEFORE prefill (no slot or compute is ever spent
                    # on it); an in-flight one retires mid-generation, its
                    # slot and pages freeing for waiting work this iteration
                    now = time.monotonic()
                    if self._shed_ctl is not None:
                        # adaptive-shed sojourn signal: the head stream's
                        # wait is the FIFO maximum, so "head under target"
                        # means the whole queue is.  Noted inside the
                        # already-held _cond region — the controller costs
                        # the loop zero new lock acquisitions.
                        self._shed_ctl.note_sojourn(
                            (now - self._pending[0].enqueued_at)
                            if self._pending else 0.0, now)
                    if self._pending:
                        keep = deque()
                        for st in self._pending:
                            (expired if st.expired(now) else keep).append(st)
                        self._pending = keep
                    for i, st in enumerate(slots):
                        if st is not None and st.expired(now):
                            expired.append(st)
                            prefilling.pop(i, None)
                            release_pages(st)
                            clear_slot(i)
                    self._cond.notify_all()
                    admissions = []
                    free = [i for i, s in enumerate(slots) if s is None]
                    while self._pending and free:
                        st = self._pending.popleft()
                        if st.cancelled:
                            self._detach_locked(st)
                            continue  # abandoned while still queued
                        slot = free.pop(0)
                        # reserve NOW, under the lock: the cancel-reap and
                        # the watchdog salvage must see prefilling streams
                        # as slotted
                        slots[slot] = st
                        admissions.append((slot, st))
                # deadline failures deliver OUTSIDE the lock (delivery
                # re-takes it to retire the stream from the live registry)
                for st in expired:
                    self._fail(st, DeadlineExceeded(
                        "request deadline exceeded after {} emitted "
                        "tokens".format(st.emitted)), epoch)
            # device work runs OUTSIDE the lock: submitters must be able
            # to enqueue while the chip computes
            if admissions or prefilling:
                with phase("admit"):
                    for slot, stream in admissions:
                        timed_admission(start_admission, slot, stream)
                    if prefilling:
                        # exactly one bounded chunk per iteration: long
                        # prompts trickle in while decode keeps stepping
                        timed_admission(run_prefill_chunk)

            current = None
            active_ids = [i for i, s in enumerate(slots)
                          if s is not None and ready[i]]
            if active_ids:
                with phase("dispatch"):
                    # sentinel position max_seq on inert rows: their cache
                    # writes drop instead of corrupting a parked slot
                    active = np.zeros((self._max_slots,), bool)
                    snapshot = []
                    context = skipped = 0
                    if blk:
                        # a block a row: the device carries each row's
                        # block, its start and its pass number; the host
                        # says who is live and each row's settings (the
                        # keys attended are counted when the pass comes
                        # back and says where its block starts)
                        steps = np.ones((self._max_slots,), np.int32)
                        taus = np.ones((self._max_slots,), np.float32)
                        for i in active_ids:
                            st = slots[i]
                            active[i] = True
                            steps[i], taus[i] = st.steps, st.tau
                            snapshot.append((i, st, False, st.incarnation))
                        vectors = (steps, taus, active)
                    else:
                        positions = np.full(
                            (self._max_slots,), self._max_seq, np.int32)
                        forced_tok = np.zeros((self._max_slots,), np.int32)
                        forced_mask = np.zeros((self._max_slots,), bool)
                        for i in active_ids:
                            st = slots[i]
                            positions[i] = st.pos
                            active[i] = True
                            was_forced = bool(st.forced)
                            if was_forced:
                                forced_tok[i] = st.forced.popleft()
                                forced_mask[i] = True
                            snapshot.append(
                                (i, st, was_forced, st.incarnation))
                            context += st.pos + 1
                            if wc:
                                move_window(i, st)
                                skipped += max(0, st.pos + 1 - window)
                            st.pos += 1
                        vectors = (positions, active, forced_tok,
                                   forced_mask)
                    # a fresh array a step: the device may alias what it
                    # was sent while earlier steps are still in flight
                    picture = controlled.picture(
                        tables, tables_w if wc else None, *vectors)
                    # key positions this step's attention layers cover,
                    # and those its window layers need not read
                    self._context_tokens += context * n_layers_all
                    self._context_bytes += context * token_bytes
                    if wc:
                        self._window_skipped_tokens += skipped * n_layers_w
                    if conv:
                        # every live row reads its windows, whole
                        self._state_bytes += (
                            len(active_ids) * state_row_bytes)
                    # chaos hook: "scheduler.step" raise = loop death (the
                    # supervised-restart path), sleep = slow step, nan =
                    # poison one slot's logits row (the quarantine path),
                    # hang = stall INSIDE the heartbeat window below so the
                    # watchdog provably observes it.  A raise here may have
                    # left the donated cache consumed — exactly what the
                    # restart rebuilds.
                    action = faults.fire("scheduler.step", self.fault_scope)
                    if action is not None and action[0] == "nan" and not blk:
                        row = min(max(0, action[1]), self._max_slots - 1)
                        logits = logits.at[row].set(float("nan"))
                    step_start = time.monotonic()
                    self._beat(epoch, step_start)
                    if action is not None and action[0] == "hang":
                        time.sleep(action[1])
                    packed_dev, logits, pages, held, sent = controlled(
                        self._params, pages, logits, picture, held)
                    self._control_uploads += sent
                    self._beat(epoch, None)
                    if self._step_hist is not None:
                        # lock-free observe: the loop must never acquire a
                        # lock per step just to be observable
                        self._step_hist.observe(
                            time.monotonic() - step_start)
                    current = (packed_dev, snapshot)

            if inflight is not None:
                packed_dev, snapshot = inflight
                with phase("fetch"):
                    # host-transfer chaos; a raise is loop death (restart)
                    faults.fire("scheduler.fetch", self.fault_scope)
                    self._beat(epoch, time.monotonic())
                    toks, lps, *moe = controlled.unpack(
                        np.asarray(packed_dev))
                    if moe:
                        # a routed configuration's three integers of its
                        # routed layers, fetched with the step's tokens
                        layer_steps, pairs, hit = (int(n) for n in moe[0])
                        self._moe_layer_steps += layer_steps
                        self._moe_local_pairs += pairs
                        self._moe_experts_hit += hit
                    self._beat(epoch, None)
                with phase("deliver"):
                    quarantined = []
                    finished = []
                    with self._cond:
                        if self._epoch != epoch:
                            return  # superseded mid-fetch: deliver nothing
                        for i, st, was_forced, inc in snapshot:
                            if slots[i] is not st or st.incarnation != inc:
                                # slot retired (and possibly re-admitted —
                                # even by the SAME stream, resumed after a
                                # disconnect) after this step was
                                # dispatched: its token is the one-deep
                                # pipeline's wasted extra
                                continue
                            if st.cancelled:
                                # consumer gone: free the slot (and its
                                # pages — full ones donate to the radix
                                # cache) AND retire the stream (parking
                                # resumables, with their KV exported for
                                # attach-resume)
                                export_kv(st)
                                release_pages(st)
                                self._detach_locked(st)
                                clear_slot(i)
                                continue
                            if was_forced:
                                continue  # resumed-prompt feed, no emission
                            if blk:
                                # a pass of the row's block: 0..B
                                # positions unmasked, a block sent when
                                # its last position is
                                poisoned, done = block_pass(
                                    st, toks[i], lps[i])
                                if poisoned:
                                    quarantined.append((i, st))
                                    release_pages(st, insert=False)
                                    clear_slot(i)
                                elif done:
                                    finished.append((st, i))
                                continue
                            tok = int(toks[i])
                            lp = float(lps[i])
                            if not np.isfinite(lp):
                                # poisoned output: THIS slot's logits went
                                # non-finite.  The batched step's math is
                                # row-independent, so co-batched slots are
                                # untouched — retire only the offender.
                                quarantined.append((i, st))
                                # poisoned KV must never enter the radix
                                # cache: free without donating
                                release_pages(st, insert=False)
                                clear_slot(i)
                                continue
                            if st.emitted < st.max_tokens:
                                emit(st, tok, lp)
                            if st.emitted >= st.max_tokens or (
                                st.eos_id is not None and tok == st.eos_id
                            ):
                                finished.append((st, i))
                    for i, st in quarantined:
                        with self._cond:
                            self._quarantined += 1
                        self._fail(st, SlotQuarantined(
                            "generation produced non-finite logits after {} "
                            "emitted tokens; its slot was quarantined (co-"
                            "batched generations are unaffected)".format(
                                st.emitted)), epoch)
                    for st, i in finished:
                        finish(st, i)
            inflight = current

        # closed: fail whatever is still queued or running
        err = SchedulerClosed("scheduler is shut down")
        if inflight is not None:
            for i, st, _, _ in inflight[1]:
                if slots[i] is st:
                    slots[i] = None
                    self._fail(st, err, epoch)
        for st in slots:
            if st is not None:
                self._fail(st, err, epoch)
        for st in pending:
            self._fail(st, err, epoch)
