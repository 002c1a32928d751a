"""Core serving runtime: model registry, inference execution, shared-memory
registries, statistics.

Protocol-facing frontends live in ``tpuserver.http_frontend`` /
``tpuserver.grpc_frontend``; this module is transport-agnostic and works on
numpy/jax arrays.
"""

import base64
import mmap
import os
import threading
import time

import numpy as np

from tpuserver import faults
from tpuserver import scheduler as _scheduler
from tpuserver._clock import wall_clock_ms
from tpuserver.metrics import MetricsRegistry
from tpuserver.errors import (  # noqa: F401 — re-exported: the public
    # names every frontend/client/test imports from tpuserver.core
    DeadlineExceeded,
    KvExportConflict,
    KvExportNotFound,
    Overloaded,
    ServerError,
    ShmRegionInUse,
    ShuttingDown,
    SlotQuarantined,
    UnknownGeneration,
)
from tritonclient.utils import (
    deserialize_bytes_tensor,
    serialize_byte_tensor,
    serialized_byte_size,
    triton_to_np_dtype,
)

SERVER_NAME = "tpu-triton-server"
SERVER_VERSION = "0.1.0"
SERVER_EXTENSIONS = [
    "classification",
    "sequence",
    "model_repository",
    "model_repository(unload_dependents)",
    "schedule_policy",
    "model_configuration",
    "system_shared_memory",
    "cuda_shared_memory",
    "xla_shared_memory",
    "binary_tensor_data",
    "parameters",
    "statistics",
    "trace",
    "logging",
]


class TensorSpec:
    """Declared input/output tensor: name, wire datatype, dims (-1 dynamic)."""

    def __init__(self, name, datatype, shape):
        self.name = name
        self.datatype = datatype
        self.shape = list(shape)

    def as_metadata(self):
        return {
            "name": self.name,
            "datatype": self.datatype,
            "shape": list(self.shape),
        }


class RequestedOutput:
    """Server-side view of one requested output and its delivery options."""

    def __init__(self, name, binary_data=True, class_count=0,
                 shm_region=None, shm_byte_size=0, shm_offset=0):
        self.name = name
        self.binary_data = binary_data
        self.class_count = class_count
        self.shm_region = shm_region
        self.shm_byte_size = shm_byte_size
        self.shm_offset = shm_offset


class InferRequest:
    """Transport-agnostic inference request."""

    def __init__(self, model_name, model_version="", request_id="",
                 inputs=None, requested_outputs=None, parameters=None):
        self.model_name = model_name
        self.model_version = model_version
        self.id = request_id
        self.inputs = inputs or {}  # name -> np.ndarray (BYTES as np.object_)
        self.requested_outputs = requested_outputs  # list[RequestedOutput]|None
        self.parameters = parameters or {}
        # shm regions the frontend resolved inputs from: a decoupled
        # model pins them for the stream's lifetime, so unregistering
        # the region backing a live prompt view is a typed 409
        self.shm_input_regions = ()
        # monotonic deadline: stamped by the gRPC frontend (context
        # deadline) and/or resolved from the 'timeout' parameter in
        # InferenceServer._resolve_deadline
        self.deadline = None
        # the client reads responses that carry several tokens of one
        # generation (MULTI_TOKEN_PARAM, taken off the wire by the gRPC
        # frontend): a streaming model may then send what waits together
        self.multi_token = False

    @property
    def sequence_id(self):
        return self.parameters.get("sequence_id", 0)

    @property
    def sequence_start(self):
        return bool(self.parameters.get("sequence_start", False))

    @property
    def sequence_end(self):
        return bool(self.parameters.get("sequence_end", False))


class InferResponse:
    """Transport-agnostic inference response."""

    def __init__(self, model_name, model_version, request_id, outputs,
                 parameters=None):
        self.model_name = model_name
        self.model_version = model_version
        self.id = request_id
        # list of (TensorSpec-like dict name/datatype/shape, np.ndarray|None,
        #          delivery dict) — array None when delivered via shm
        self.outputs = outputs
        self.parameters = parameters or {}
        # time.monotonic() at which the decode loop queued each token
        # (of a block model: each block) this response carries, a list,
        # None for a replayed one; None where no loop did: the
        # frontend's count of the wait from there to the wire
        self.emitted_at = None
        # the frontend may send this response together with the next
        # mergeable one of the same request that waits behind it
        # (merge_responses): each output's first axis is a token, and
        # the parameters are those of the first token
        self.mergeable = False


#: Reserved key a decoupled model may include in a yielded output dict
#: to attach per-response parameters (e.g. the generation id and token
#: sequence number resumable streams carry on the wire); popped before
#: the dict is interpreted as output tensors.
RESPONSE_PARAMS_KEY = "__response_parameters__"

#: Reserved key of the same kind: the ``time.monotonic()`` stamps at
#: which a decode loop queued the response's tokens (``scheduler.Emitted``),
#: popped into ``InferResponse.emitted_at``; never sent.
EMITTED_AT_KEY = "__emitted_at__"

#: Reserved key of the same kind: True makes the response
#: ``InferResponse.mergeable``; never sent.
MERGEABLE_KEY = "__mergeable__"

#: Request parameter by which a streaming client declares that it reads
#: responses carrying several tokens of one generation (``TOKEN`` /
#: ``LOGPROB`` of shape ``[k]``, ``seq`` the first token's).
#: ``tritonclient.grpc``'s ``generate_stream`` sends it; the gRPC
#: frontend takes it off the request into ``InferRequest.multi_token``.
MULTI_TOKEN_PARAM = "multi_token_responses"

#: Response parameter of such a response that carries more than one
#: token: how many.
TOKEN_COUNT_PARAM = "token_count"


def merge_responses(responses):
    """One response carrying, in order, what ``responses`` carry: the
    mergeable responses of one request, each output concatenated along
    its first axis, the first response's parameters (its ``seq``), and
    every token's own stamp."""
    if len(responses) == 1:
        return responses[0]
    first = responses[0]
    outputs = []
    for n, (spec, _, delivery) in enumerate(first.outputs):
        array = np.concatenate([r.outputs[n][1] for r in responses])
        outputs.append((dict(spec, shape=list(array.shape)), array,
                        delivery))
    merged = InferResponse(first.model_name, first.model_version, first.id,
                           outputs, dict(first.parameters))
    merged.parameters[TOKEN_COUNT_PARAM] = len(outputs[0][1])
    merged.emitted_at = [t for r in responses for t in r.emitted_at]
    merged.mergeable = True
    return merged


def _instance_kind(model):
    """``instance_group`` kind of ``model``: where its compute runs, as
    observed.  Python-backend and ensemble models run on the host; a
    jitted model runs on jax's default backend WHATEVER that turned out
    to be — a server that came up on the CPU says ``KIND_CPU``."""
    if model.backend != "jax":
        return "KIND_CPU"
    import jax

    return {"tpu": "KIND_TPU", "gpu": "KIND_GPU"}.get(
        jax.default_backend(), "KIND_CPU")


class Model:
    """Base model: subclasses define specs and ``execute``.

    ``execute(inputs, request)`` returns ``dict name -> np.ndarray``.
    Decoupled models instead implement ``execute_stream`` yielding such dicts
    (possibly zero or many — the decoupled contract).
    Sequence models implement ``execute_sequence(inputs, state, request)``
    returning ``(outputs, new_state)``.
    """

    name = "model"
    platform = "jax"
    backend = "jax"
    max_batch_size = 0
    inputs = ()
    outputs = ()
    decoupled = False
    sequence = False
    ensemble_steps = None  # list of dicts for ensemble models
    labels = None  # name -> list[str] classification labels
    version = "1"
    # server-side dynamic batching (role of the reference server's
    # dynamic_batching model-config block): concurrent single requests
    # are coalesced into one batched ``execute`` call.  On TPU one
    # [N, ...] dispatch keeps the MXU fed and amortizes the
    # host<->device round trip N ways where N serialized [1, ...]
    # dispatches each pay it in full.
    dynamic_batching = False
    max_queue_delay_us = 2000
    # allowed padded batch sizes (ascending); None = powers of two up to
    # max_batch_size.  Fewer buckets = fewer compiled executables —
    # each distinct batch shape is a separate XLA compile.
    batch_buckets = None
    # parallel executor count (role of the reference server's
    # instance_group count): >1 lets batch executions overlap, hiding
    # the host<->device sync of one batch behind the compute of the
    # next.
    instance_count = 1

    def config_dict(self):
        cfg = {
            "name": self.name,
            "platform": self.platform,
            "backend": self.backend,
            "max_batch_size": self.max_batch_size,
            "input": [
                {
                    "name": t.name,
                    "data_type": "TYPE_" + t.datatype,
                    "dims": list(t.shape),
                }
                for t in self.inputs
            ],
            "output": [
                {
                    "name": t.name,
                    "data_type": "TYPE_" + t.datatype,
                    "dims": list(t.shape),
                }
                for t in self.outputs
            ],
            "instance_group": [{
                "name": self.name + "_0",
                "kind": _instance_kind(self),
                "count": self.instance_count,
            }],
            "version_policy": {"latest": {"num_versions": 1}},
        }
        if self.decoupled:
            cfg["model_transaction_policy"] = {"decoupled": True}
        if self.dynamic_batching and self.max_batch_size > 1:
            cfg["dynamic_batching"] = {
                "preferred_batch_size": [self.max_batch_size],
                "max_queue_delay_microseconds": self.max_queue_delay_us,
            }
        if self.sequence:
            cfg["sequence_batching"] = {
                "max_sequence_idle_microseconds": 60000000,
                "control_input": [
                    {"name": "START",
                     "control": [{"kind": "CONTROL_SEQUENCE_START",
                                  "int32_false_true": [0, 1]}]},
                    {"name": "END",
                     "control": [{"kind": "CONTROL_SEQUENCE_END",
                                  "int32_false_true": [0, 1]}]},
                ],
            }
        if self.ensemble_steps is not None:
            cfg["platform"] = "ensemble"
            cfg["ensemble_scheduling"] = {"step": self.ensemble_steps}
        return cfg

    def metadata_dict(self):
        return {
            "name": self.name,
            "versions": [self.version],
            "platform": self.platform,
            "inputs": [t.as_metadata() for t in self.inputs],
            "outputs": [t.as_metadata() for t in self.outputs],
        }

    def execute(self, inputs, request):
        raise NotImplementedError

    def execute_stream(self, inputs, request):
        raise NotImplementedError

    def execute_sequence(self, inputs, state, request):
        raise NotImplementedError

    def warmup(self):
        """Trigger compilation with representative shapes (optional)."""


class JaxModel(Model):
    """A model whose compute is a jitted JAX callable.

    ``fn(**inputs) -> dict`` runs under ``jax.jit`` with static shapes on
    jax's default device; host arrays are pushed with ``device_put`` and
    results fetched once.  Direct ``jax.Array`` inputs (the in-process
    XLA-shm fast path) skip the host push entirely.  Trivial/control
    models whose compute is smaller than a dispatch are plain numpy
    ``Model`` subclasses instead (``models/simple.py``).
    """

    def __init__(self):
        self._jitted = None
        self._lock = threading.Lock()

    def jax_fn(self, **kwargs):
        raise NotImplementedError

    def prepare(self):
        """One-time eager setup (e.g. parameter initialization), run
        OUTSIDE any jit trace.  Lazily creating params inside the traced
        ``jax_fn`` would store tracers of that trace in model state
        (jitted helpers like jax.random.normal inline into an active
        trace), corrupting every later re-trace."""

    def _get_jitted(self):
        if self._jitted is None:
            with self._lock:
                if self._jitted is None:
                    import jax

                    self._jitted = jax.jit(self.jax_fn)
        return self._jitted

    def execute(self, inputs, request):
        import jax

        fn = self._get_jitted()
        self.prepare()
        dev_inputs = {}
        for name, arr in inputs.items():
            if isinstance(arr, jax.Array):
                dev_inputs[name] = arr  # zero-copy: stays in HBM
            else:
                dev_inputs[name] = jax.device_put(arr)
        out = fn(**dev_inputs)
        # Outputs stay as device arrays: the response builder converts
        # (= synchronizes) only when a tensor actually leaves in-band,
        # so XLA-shm-delivered outputs never block on the device, and
        # the zero-sync path is what lets dispatches pipeline.
        return dict(out)


class _SystemShmRegion:
    def __init__(self, name, key, offset, byte_size):
        self.name = name
        self.key = key
        self.offset = offset
        self.byte_size = byte_size
        path = "/dev/shm" + key if key.startswith("/") else "/dev/shm/" + key
        self._fd = os.open(path, os.O_RDWR)
        self._map = mmap.mmap(self._fd, offset + byte_size)

    def read(self, offset, nbytes):
        start = self.offset + offset
        return bytes(self._map[start : start + nbytes])

    def write(self, offset, data):
        start = self.offset + offset
        self._map[start : start + len(data)] = data

    def close(self):
        try:
            self._map.close()
        finally:
            os.close(self._fd)


class _XlaShmRegion:
    """Server-side view of a registered XLA/TPU shared-memory region.

    The raw handle (see tritonclient.utils.xla_shared_memory) names both a
    host staging window (POSIX shm) and, when client and server share a
    process, an in-process buffer registry slot holding live ``jax.Array``s —
    the zero-host-copy fast path.
    """

    def __init__(self, name, raw_handle, device_ordinal, byte_size):
        from tritonclient.utils import xla_shared_memory as xshm

        self.name = name
        self.device_ordinal = device_ordinal
        self.byte_size = byte_size
        self.handle = xshm.attach_from_raw_handle(raw_handle)

    def read(self, offset, nbytes):
        return self.handle.read_bytes(offset, nbytes)

    def write(self, offset, data):
        self.handle.write_bytes(offset, data)

    def get_device_array(self, offset, datatype, shape):
        """Device-resident ``jax.Array`` parked at ``offset``, or None.

        Only live in-process segments qualify (the zero-copy fast path).
        Cross-process attaches hold data in the host staging window; for
        those, returning None lets the caller read host bytes — a jitted
        model will device_put once itself, and numpy models skip the
        device round-trip entirely (eager device_put here would cost two
        transfers per request)."""
        seg = self.handle.get_jax_segment(offset)
        if seg is None:
            return None
        if list(seg.shape) != list(shape):
            seg = seg.reshape(shape)
        return seg

    def put_device_array(self, offset, array):
        return self.handle.put_jax(offset, array)

    def close(self):
        self.handle.detach()


class _BatchSlot:
    """One queued request inside the dynamic batcher."""

    __slots__ = ("inputs", "rows", "event", "outputs", "error",
                 "enqueue_ns", "queue_ns")

    def __init__(self, inputs, rows):
        self.inputs = inputs
        self.rows = rows
        self.event = threading.Event()
        self.outputs = None
        self.error = None
        # KServe-style queue accounting: time from enqueue to the moment
        # a worker starts executing the batch this slot landed in
        self.enqueue_ns = time.monotonic_ns()
        self.queue_ns = 0


class _DynamicBatcher:
    """Coalesces concurrent requests for one model into batched calls.

    Role of the reference server's dynamic batcher (model_config
    ``dynamic_batching``; observable to perf_analyzer as super-linear
    throughput under concurrency).  A worker thread drains a queue:
    the first waiting request opens a window of
    ``model.max_queue_delay_us``; every compatible request (same input
    names, dtypes and trailing dims) that arrives inside it is stacked
    along the batch axis, executed as ONE device call, and the outputs
    are split back per request.  Requests left over (incompatible
    signature or window overflow) seed the next batch, so nothing
    starves.
    """

    def __init__(self, model):
        self._model = model
        self._cond = threading.Condition()
        self._queue = []   # of _BatchSlot  # guarded-by: _cond
        self._stop = False  # guarded-by: _cond
        self._threads = [
            threading.Thread(
                target=self._run,
                name="batcher-{}-{}".format(model.name, i),
                daemon=True,
            )
            for i in range(max(1, model.instance_count))
        ]
        for t in self._threads:
            t.start()

    @staticmethod
    def _signature(inputs):
        return tuple(
            sorted(
                (name, arr.dtype.str, arr.shape[1:])
                for name, arr in inputs.items()
            )
        )

    def submit(self, inputs, rows):
        """Queue one request's inputs; blocks until its batch executes.

        Returns ``(outputs, queue_ns)`` — the request's slice of the
        batched outputs plus the nanoseconds this request waited in the
        batching window before execution started (the KServe ``queue``
        stat bucket; raises the batch's error if execution failed)."""
        slot = _BatchSlot(inputs, rows)
        with self._cond:
            if self._stop:
                raise ServerError(
                    "model '{}' is unloading".format(self._model.name)
                )
            self._queue.append(slot)
            self._cond.notify_all()
        slot.event.wait()
        if slot.error is not None:
            raise slot.error
        return slot.outputs, slot.queue_ns

    def stop(self):
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5)
        # snapshot under the lock: a worker that outlived the join may
        # still rebind the queue in _take_batch_locked; slots it has
        # taken will complete normally, only still-queued slots get
        # errored
        with self._cond:
            pending, self._queue = self._queue, []
        for slot in pending:
            slot.error = ServerError(
                "model '{}' is unloading".format(self._model.name)
            )
            slot.event.set()

    def _take_batch_locked(self):
        """Collect one compatible batch.  Called with ``_cond`` held
        (the ``_locked`` suffix is the convention tpulint R1 keys on)."""
        max_rows = self._model.max_batch_size
        sig = self._signature(self._queue[0].inputs)
        batch, rest, rows = [], [], 0
        for slot in self._queue:
            if (
                rows + slot.rows <= max_rows
                and self._signature(slot.inputs) == sig
            ):
                batch.append(slot)
                rows += slot.rows
            else:
                rest.append(slot)
        if not batch:
            # oversized single request: run it alone, the model's own
            # shape validation decides its fate
            batch, rest = [rest[0]], rest[1:]
            rows = batch[0].rows
        self._queue = rest
        return batch, rows

    def _run(self):
        delay_s = self._model.max_queue_delay_us / 1e6
        while True:
            with self._cond:
                while not self._queue and not self._stop:
                    self._cond.wait()
                if self._stop:
                    return
                # batching window: wait for companions until the delay
                # elapses or a full preferred batch is queued
                deadline = time.monotonic() + delay_s
                while (
                    sum(s.rows for s in self._queue)
                    < self._model.max_batch_size
                    and not self._stop
                ):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
                if self._stop:
                    return
                if not self._queue:
                    # a sibling instance thread drained the queue while
                    # this one sat in its batching window
                    continue
                batch, rows = self._take_batch_locked()
            self._execute(batch, rows)

    def _bucket(self, rows, max_rows):
        """Smallest allowed padded batch >= rows: every jit model
        compiles one executable per distinct batch shape, so padding
        the batch axis to a few fixed buckets bounds the compile set
        (model.batch_buckets, default powers of two up to max_batch)
        instead of one compile per concurrency level."""
        buckets = self._model.batch_buckets
        if buckets:
            for b in buckets:
                if b >= rows:
                    return b
            return max(buckets[-1], rows)
        b = 1
        while b < rows:
            b <<= 1
        return min(b, max(max_rows, rows))

    def _stack(self, batch, rows, padded):
        """Build the batched input dict.

        Host (numpy) parts are stacked host-side into one bucket-shaped
        array — the model's single device_put moves the whole batch in
        one transfer, and the compiled-shape set stays exactly the
        bucket set.  Device-resident parts (the XLA-shm fast path) are
        concatenated on device instead, so they never round-trip through
        the host; the padding rows replicate row 0.
        """
        stacked = {}
        for name in batch[0].inputs:
            raw_parts = [s.inputs[name] for s in batch]
            if all(isinstance(p, np.ndarray) for p in raw_parts):
                parts = raw_parts
                if padded > rows:
                    parts = parts + [
                        np.repeat(parts[0][:1], padded - rows, axis=0)
                    ]
                stacked[name] = (
                    np.concatenate(parts, axis=0)
                    if len(parts) > 1
                    else parts[0]
                )
            else:
                import jax
                import jax.numpy as jnp

                parts = [
                    p if isinstance(p, jax.Array) else jax.device_put(p)
                    for p in raw_parts
                ]
                x = (
                    jnp.concatenate(parts, axis=0)
                    if len(parts) > 1
                    else parts[0]
                )
                if padded > rows:
                    x = jnp.concatenate(
                        [x, jnp.repeat(x[:1], padded - rows, axis=0)],
                        axis=0,
                    )
                stacked[name] = x
        return stacked

    def _execute(self, batch, rows):
        t_start = time.monotonic_ns()
        for slot in batch:
            slot.queue_ns = max(0, t_start - slot.enqueue_ns)
        try:
            padded = self._bucket(rows, self._model.max_batch_size)
            stacked = self._stack(batch, rows, padded)
            outputs = self._model.execute(stacked, None)
            if len(batch) > 1:
                # materialize device outputs ONCE for the whole batch:
                # splitting into per-slot device slices would make each
                # response pay its own device sync for the same bytes
                outputs = {
                    k: v if isinstance(v, np.ndarray) else np.asarray(v)
                    for k, v in outputs.items()
                }
            # A max_batch_size>0 model's declared outputs always carry
            # the batch dim (Triton config semantics), so split them by
            # declaration — including ones the model returned un-padded
            # (shape[0] == rows).  Undeclared extras have no spec to
            # consult; they fall back to the padded-shape heuristic so
            # a batch-shaped extra is still split per request (never
            # replicated whole, which would leak other requests' rows).
            declared = {t.name for t in self._model.outputs}
            for name, arr in outputs.items():
                if name not in declared:
                    continue
                ndim = getattr(arr, "ndim", 0)
                if ndim < 1 or arr.shape[0] not in (rows, padded):
                    # a misdeclared un-batched output (e.g. [1000] class
                    # scores for a 3-row batch) must fail loudly — the
                    # declaration-driven split would otherwise slice it
                    # into wrong per-request rows
                    raise ValueError(
                        "declared output '{}' of model '{}' must carry "
                        "the batch dim (shape[0] in ({}, {})), got shape "
                        "{}".format(
                            name, self._model.name, rows, padded,
                            tuple(getattr(arr, "shape", ())),
                        )
                    )
            offset = 0
            for slot in batch:
                slot.outputs = {}
                for name, arr in outputs.items():
                    ndim = getattr(arr, "ndim", 0)
                    batched = ndim >= 1 and (
                        name in declared or arr.shape[0] == padded
                    )
                    if batched and arr.shape[0] >= rows:
                        if len(batch) == 1 and arr.shape[0] == slot.rows:
                            slot.outputs[name] = arr  # no split needed
                        else:
                            slot.outputs[name] = arr[
                                offset : offset + slot.rows
                            ]
                    else:  # non-batched output: replicate
                        slot.outputs[name] = arr
                offset += slot.rows
        except Exception as e:  # noqa: BLE001 — failure fans out per slot
            # each waiting frontend thread raises its own slot.error;
            # handing every slot the same instance would race the
            # interpreter's __traceback__ mutation on concurrent raises.
            # ValueError keeps the 400 the frontends would have mapped it
            # to on the unbatched path; everything else is a server 500.
            code = getattr(
                e, "code", 400 if isinstance(e, ValueError) else 500
            )
            for slot in batch:
                slot.error = ServerError(
                    "batched execution failed for model '{}': {}".format(
                        self._model.name, e
                    ),
                    code=code,
                )
        finally:
            for slot in batch:
                slot.event.set()


class _ModelStats:
    def __init__(self):
        self.lock = threading.Lock()
        self.inference_count = 0     # guarded-by: lock
        self.execution_count = 0     # guarded-by: lock
        # epoch ms, the KServe statistics wire contract — a REPORTING
        # field, stamped through the sanctioned _clock.wall_clock_ms()
        # boundary.  Nothing may do liveness/recency math on it (wall
        # clocks jump; tpulint R3 bans wall-clock reads everywhere
        # else, so a monotonic source must be added if such math ever
        # appears).
        self.last_inference_ms = 0   # guarded-by: lock
        self.success_count = 0       # guarded-by: lock
        self.success_ns = 0          # guarded-by: lock
        self.fail_count = 0          # guarded-by: lock
        self.fail_ns = 0             # guarded-by: lock
        self.queue_ns = 0            # guarded-by: lock
        self.compute_input_ns = 0    # guarded-by: lock
        self.compute_infer_ns = 0    # guarded-by: lock
        self.compute_output_ns = 0   # guarded-by: lock

    def record(self, batch, queue_ns, ci_ns, cf_ns, co_ns, ok=True):
        with self.lock:
            if ok:
                self.inference_count += batch
                self.execution_count += 1
                self.last_inference_ms = wall_clock_ms()
                self.success_count += 1
                self.success_ns += queue_ns + ci_ns + cf_ns + co_ns
                self.queue_ns += queue_ns
                self.compute_input_ns += ci_ns
                self.compute_infer_ns += cf_ns
                self.compute_output_ns += co_ns
            else:
                self.fail_count += 1
                self.fail_ns += queue_ns + ci_ns + cf_ns + co_ns

    def as_dict(self, name, version):
        with self.lock:
            def sd(count, ns):
                return {"count": count, "ns": ns}

            return {
                "name": name,
                "version": version,
                "last_inference": self.last_inference_ms,
                "inference_count": self.inference_count,
                "execution_count": self.execution_count,
                "inference_stats": {
                    "success": sd(self.success_count, self.success_ns),
                    "fail": sd(self.fail_count, self.fail_ns),
                    "queue": sd(self.success_count, self.queue_ns),
                    "compute_input": sd(self.success_count,
                                        self.compute_input_ns),
                    "compute_infer": sd(self.success_count,
                                        self.compute_infer_ns),
                    "compute_output": sd(self.success_count,
                                         self.compute_output_ns),
                    "cache_hit": sd(0, 0),
                    "cache_miss": sd(0, 0),
                },
                "batch_stats": [],
            }


class InferenceServer:
    """The serving core: models, shared memory, statistics, settings.

    Lifecycle: ``starting`` (constructed with ``ready=False``, e.g.
    while warmup compiles run) -> ``ready`` -> ``draining`` (via
    :meth:`drain`/:meth:`begin_drain`) -> ``stopped`` (via
    :meth:`close`).  :meth:`server_ready` reports True only in
    ``ready`` with every model's health check passing, so load
    balancers see drain and watchdog trips, not a constant.

    ``max_inflight`` is the server-wide overload valve: when that many
    requests are executing, further ones are shed with a typed
    :class:`Overloaded` (HTTP 429 + Retry-After) instead of queueing
    without bound behind a saturated device.
    """

    def __init__(self, models=None, max_inflight=None, ready=True,
                 fault_scope=None, role=None, spawn_nonce=None):
        # identifies this replica at shared fault-injection points, so
        # multi-server chaos harnesses can break ONE in-process replica
        # (tpuserver.faults scopes)
        self.fault_scope = fault_scope
        # spawn identity nonce (fleet supervisor adoption): echoed in
        # health_snapshot so a RESTARTED supervisor can prove the
        # process on a recorded port is the exact child it spawned
        # before claiming it (fleetmanifest adoption contract)
        self.spawn_nonce = spawn_nonce
        # disaggregated-serving role ("prefill" | "decode" | None =
        # fused): advertised in health_snapshot so a fleet router can
        # partition its candidate pools by phase without configuration
        self.role = role
        self._models = {}  # name -> Model
        self._ready = {}  # name -> bool
        self._stats = {}  # name -> _ModelStats
        self._lock = threading.Lock()
        # lifecycle state machine; reads go through server_state() so
        # probes never see a torn transition
        self._state = "ready" if ready else "starting"  # guarded-by: _inflight_cond
        self._max_inflight = max_inflight  # guarded-by: _inflight_cond
        self._inflight = 0  # guarded-by: _inflight_cond
        self._inflight_cond = threading.Condition()
        self._system_shm = {}
        self._cuda_shm = {}  # parity only; registration succeeds, no CUDA io
        self._xla_shm = {}
        # region name -> reference count of in-flight generations /
        # token rings holding the region (guarded by _shm_lock):
        # unregister of a pinned region is a typed 409 conflict, never
        # a crash or silent corruption under the zero-copy data plane
        self._shm_pins = {}
        self._shm_lock = threading.Lock()
        # generation id -> (region name, parked position, shape, wire
        # dtype): the server-owned XLA-shm KV exports a parked
        # generation leaves behind so a same-host resume re-scatters
        # instead of re-prefilling  # guarded-by: _shm_lock
        self._kv_exports = {}
        # generation ids whose export descriptor was already handed out:
        # the disaggregated transfer contract is one-shot (exactly one
        # decode replica re-scatters a prefill leg), so a second fetch
        # is a typed 409, not a silent double-attach  # guarded-by: _shm_lock
        self._kv_export_claims = set()
        self._batchers = {}  # name -> _DynamicBatcher (lazily created;
        # double-checked locking — deliberately unannotated, see
        # docs/static_analysis.md R1)
        self._closed = False  # guarded-by: _lock
        # attached frontends; last detach closes  # guarded-by: _lock
        self._frontends = 0
        self._sequence_state = {}  # (model, seq_id) -> (state, touched)
        self._last_sequence_sweep = 0.0
        self._trace_settings = {
            "trace_file": [""],
            "trace_level": ["OFF"],
            "trace_rate": ["1000"],
            "trace_count": ["-1"],
            "log_frequency": ["0"],
        }
        self._log_settings = {
            "log_file": "",
            "log_info": True,
            "log_warning": True,
            "log_error": True,
            "log_verbose_level": 0,
            "log_format": "default",
        }
        # the replica's telemetry plane (docs/observability.md):
        # owned per-verb instruments plus a scrape-time collector over
        # every model's scheduler counters — the scheduler stays the
        # single account of its own events, the registry is a view.
        # Verb children are pre-bound so the per-request hot path
        # costs two lock-free adds, never a family-lock lookup.
        self.metrics = MetricsRegistry()
        requests_family = self.metrics.counter(
            "tpu_requests_total", labelnames=("verb",))
        seconds_family = self.metrics.histogram(
            "tpu_request_seconds", labelnames=("verb",))
        self._metric_errors = self.metrics.counter(
            "tpu_request_errors_total", labelnames=("verb", "code"))
        self._m_infer_count = requests_family.labels(verb="infer")
        self._m_infer_hist = seconds_family.labels(verb="infer")
        self._m_stream_count = requests_family.labels(verb="stream_infer")
        self._m_stream_hist = seconds_family.labels(verb="stream_infer")
        # (verb, code) -> bound counter child; plain-dict cache so the
        # error path never re-pays the family lock
        self._metric_error_children = {}
        # shared-memory data-plane traffic: bytes materialized from /
        # written into registered regions (device-resident zero-copy
        # transfers count their logical tensor size — the bytes that
        # did NOT cross the wire)
        self._m_shm_read = self.metrics.counter(
            "tpu_shm_bytes_read_total").labels()
        self._m_shm_written = self.metrics.counter(
            "tpu_shm_bytes_written_total").labels()
        # a streamed token's wait from the decode loop's queue to the
        # transport, and the loop's emissions and the responses that
        # carried them (count_token_handoff): model -> (seconds, tokens,
        # emissions, responses) children, bound on a model's first
        # stamped response
        self._handoff_families = tuple(
            self.metrics.counter(name, labelnames=("model",)) for name in (
                "tpu_frontend_token_handoff_seconds_total",
                "tpu_frontend_token_handoffs_total",
                "tpu_frontend_stream_emissions_total",
                "tpu_frontend_stream_responses_total"))
        self._handoff_children = {}
        self.metrics.register_collector(self._collect_metrics)
        self.metrics.register_collector(self._collect_shm_ring)
        for m in models or []:
            self.register_model(m)

    # -- model repository --------------------------------------------------

    def register_model(self, model, ready=True):
        with self._lock:
            self._models[model.name] = model
            self._ready[model.name] = ready
            self._stats.setdefault(model.name, _ModelStats())
        attach = getattr(model, "attach_server", None)
        if attach is not None:
            attach(self)

    def _get_model(self, name, version=""):
        model = self._models.get(name)
        if model is None:
            raise ServerError(
                "Request for unknown model: '{}' is not found".format(name),
                code=404,
            )
        if version not in ("", model.version):
            raise ServerError(
                "Request for unknown model version: '{}' version {}".format(
                    name, version
                ),
                code=404,
            )
        if not self._ready.get(name, False):
            raise ServerError(
                "Model '{}' is not ready".format(name), code=400
            )
        return model

    def requires_stream_order(self, name, version=""):
        """Whether stream requests to this model must execute in arrival
        order: decoupled response bursts are contractual, and sequence
        state depends on step order.

        Continuous-batching decoupled models (``concurrent_decoupled``,
        e.g. the llama scheduler with ``max_slots > 1``) opt OUT of
        per-stream serialization: their whole point is that many
        generations run interleaved on the chip, each response carrying
        its request id so clients demultiplex."""
        model = self._get_model(name, version)
        if model.sequence:
            return True
        if model.decoupled:
            return not getattr(model, "concurrent_decoupled", False)
        return False

    def is_concurrent_decoupled(self, name, version=""):
        """Whether this model runs decoupled requests interleaved (the
        continuous-batching scheduler).  Such requests self-limit via
        the model's slot count, so stream frontends must not cap them
        with their own in-flight bound — a long-lived generation would
        otherwise starve the scheduler of work it has slots for."""
        model = self._models.get(name)
        return bool(
            model is not None
            and model.decoupled
            and getattr(model, "concurrent_decoupled", False)
        )

    def model_ready(self, name, version=""):
        model = self._models.get(name)
        return (
            model is not None
            and version in ("", model.version)
            and self._ready.get(name, False)
            and self.server_state() == "ready"
            and self._model_healthy(model)
        )

    @staticmethod
    def _model_healthy(model):
        """A model may expose ``healthy`` (property or callable) — e.g.
        the continuous-batching scheduler's watchdog; absent means
        healthy."""
        probe = getattr(model, "healthy", None)
        if probe is None:
            return True
        return bool(probe() if callable(probe) else probe)

    # -- lifecycle / readiness ---------------------------------------------

    def server_state(self):
        """``starting`` | ``ready`` | ``draining`` | ``stopped``."""
        with self._inflight_cond:
            return self._state

    def server_ready(self):
        """Real readiness for load balancers: True only when serving
        (not starting/draining/stopped) and every registered model's
        health probe passes (a tripped scheduler watchdog reports
        here)."""
        if self.server_state() != "ready":
            return False
        with self._lock:  # snapshot: register_model mutates under _lock
            models = list(self._models.items())
        for name, model in models:
            if self._ready.get(name, False) and not self._model_healthy(
                model
            ):
                return False
        return True

    def health_snapshot(self):
        """Cheap machine-readable health/load snapshot — the routing
        signal a fleet router's prober polls (`/v2/health/stats`).

        Deliberately NOT the per-model inference-statistics verb: this
        touches only the lifecycle state, the in-flight counter, and
        each model's scheduler counters (one lock hold apiece), so a
        sub-second probe cadence across a fleet costs nothing.  Shape::

            {"state": "ready", "ready": true, "inflight": 3,
             "max_inflight": 64, "pid": 4242, "role": null,
             "models": {"llama_generate": {<DecodeScheduler.stats()>}}}

        ``role`` is the disaggregated-serving phase this replica is
        dedicated to (``"prefill"`` / ``"decode"``, None = fused) — the
        signal a phase-aware router partitions its candidate pools by.

        ``pid`` identifies the serving *process*: a fleet supervisor
        restarting replicas at a stable address can tell a healed
        process from a survivor without tracking anything else.

        ``spawn_nonce`` (when the spawner passed one) closes the
        adoption loop: pid + start-time token prove "a process", the
        echoed nonce proves "MY process" — a foreign server squatting
        the recorded port can never be claimed by a restarted
        supervisor.

        ``models`` maps each registered model to its scheduler stats
        dict (``None`` for models with no scheduler, or before first
        use) — ``tripped``/``restarts``/``replay_entries`` and the
        ``live_streams``/``pending`` vs ``max_slots``/``max_pending``
        utilization are the routing and shed signals."""
        with self._inflight_cond:
            state = self._state
            inflight = self._inflight
            max_inflight = self._max_inflight
        with self._lock:
            items = list(self._models.items())
        models = {}
        for name, model in items:
            stats_fn = getattr(model, "scheduler_stats", None)
            models[name] = stats_fn() if callable(stats_fn) else None
        snap = {
            "state": state,
            "ready": self.server_ready(),
            "inflight": inflight,
            "max_inflight": max_inflight,
            "pid": os.getpid(),
            "role": self.role,
            "models": models,
        }
        if self.spawn_nonce is not None:
            snap["spawn_nonce"] = self.spawn_nonce
        return snap

    # -- telemetry ---------------------------------------------------------

    def _count_error(self, verb, code):
        key = (verb, str(code))
        child = self._metric_error_children.get(key)
        if child is None:
            child = self._metric_errors.labels(verb=verb, code=key[1])
            self._metric_error_children[key] = child
        child.inc()

    def count_token_handoff(self, resp):
        """A frontend hands ``resp`` to its transport: add each of its
        tokens' wait since the decode loop queued it, by the token's own
        stamp, to ``tpu_frontend_token_handoff_seconds_total`` and one a
        token to ``tpu_frontend_token_handoffs_total`` (a replayed token
        carries no stamp and is not counted there); and its emissions,
        replayed ones included, to ``tpu_frontend_stream_emissions_total``
        (a block model's emission is a block) and one to
        ``tpu_frontend_stream_responses_total``.  A response no loop
        stamped (an error, a model without a scheduler) is not counted.
        Called before the transport write, so the count lands before
        the client can hold what it counts."""
        stamps = resp.emitted_at
        if stamps is None:
            return
        now = time.monotonic()
        waits = [now - t for t in stamps if t is not None]
        children = self._handoff_children.get(resp.model_name)
        if children is None:
            # labels() hands every caller the same children: a race
            # here binds them twice, harmlessly
            children = self._handoff_children[resp.model_name] = tuple(
                family.labels(model=resp.model_name)
                for family in self._handoff_families)
        if waits:
            children[0].inc(sum(waits))
            children[1].inc(len(waits))
        children[2].inc(len(stamps))
        children[3].inc()

    def _collect_metrics(self):
        """Scrape-time collector: the in-flight gauge plus every
        scheduler-backed model's counters, read straight from
        ``scheduler_stats()`` — one source of truth, no double
        accounting (test-pinned in tests/test_metrics.py)."""
        with self._inflight_cond:
            inflight = self._inflight
        families = [("tpu_inflight_requests", [({}, inflight)])]
        families.append((
            "tpu_shm_regions",
            [({"kind": "system"}, len(self._system_shm)),
             ({"kind": "cuda"}, len(self._cuda_shm)),
             ({"kind": "xla"}, len(self._xla_shm))],
        ))
        with self._lock:
            items = list(self._models.items())
        per_family = {
            "tpu_scheduler_admissions_total": "admitted",
            "tpu_scheduler_tokens_total": "tokens",
            "tpu_scheduler_restarts_total": "restarts",
            "tpu_scheduler_quarantined_total": "quarantined",
            "tpu_scheduler_replay_hits_total": "replay_hits",
            "tpu_scheduler_live_streams": "live_streams",
            "tpu_scheduler_pending": "pending",
            # adaptive queue shedding (tail-latency defense): sheds by
            # the sojourn controller + whether it is shedding NOW
            # (bool coerces to the 0/1 gauge)
            "tpu_scheduler_codel_sheds_total": "codel_sheds",
            "tpu_scheduler_codel_shedding": "codel_shedding",
            # paged KV + radix prefix cache (PR 11): the counters
            # perfanalyzer's hit-rate column window-diffs, and the
            # page-utilization gauges
            "tpu_prefix_cache_hits_total": "prefix_hits",
            "tpu_prefix_cache_misses_total": "prefix_misses",
            "tpu_prefix_cache_evictions_total": "prefix_evictions",
            "tpu_kv_pages_total": "pages_total",
            "tpu_kv_pages_free": "pages_free",
            "tpu_kv_pages_cached": "pages_cached",
            # the window class of a two-class pool, what the steps'
            # attention covered and skipped, and the routed layers'
            # counts (all 0 for a model with neither)
            "tpu_kv_window_pages_total": "window_pages_total",
            "tpu_kv_window_pages_free": "window_pages_free",
            "tpu_scheduler_context_tokens_total": "context_tokens",
            "tpu_scheduler_context_bytes_total": "context_bytes",
            "tpu_scheduler_window_skipped_tokens_total":
                "window_skipped_tokens",
            "tpu_moe_layer_steps_total": "moe_layer_steps",
            "tpu_moe_local_pairs_total": "moe_local_pairs",
            "tpu_moe_experts_hit_total": "moe_experts_hit",
            # the block steps' counts (0 for a one-token model)
            "tpu_diffusion_row_passes_total": "diffusion_row_passes",
            "tpu_diffusion_commit_passes_total":
                "diffusion_commit_passes",
            "tpu_diffusion_fused_commits_total":
                "diffusion_fused_commits",
            "tpu_diffusion_tokens_unmasked_total":
                "diffusion_tokens_unmasked",
            "tpu_diffusion_blocks_committed_total":
                "diffusion_blocks_committed",
            "tpu_scheduler_control_uploads_total": "control_uploads",
            # the conv layers' windows (0 for a model without them)
            "tpu_scheduler_state_bytes_total": "state_bytes",
            "tpu_scheduler_state_writes_total": "state_writes",
            # the flash prefill kernel's block pairs (0 on dense prefill)
            "tpu_scheduler_flash_pairs_total": "flash_pairs",
            "tpu_scheduler_flash_grid_pairs_total": "flash_grid_pairs",
        }
        samples = {name: [] for name in per_family}
        # the decode loop's seconds by phase, wall and off the CPU: the
        # families with a second label, float like every *_seconds
        by_phase = {"tpu_scheduler_loop_seconds_total": "loop_seconds",
                    "tpu_scheduler_loop_offcpu_seconds_total":
                        "loop_offcpu_seconds"}
        samples.update((name, []) for name in by_phase)
        for model_name, model in items:
            stats_fn = getattr(model, "scheduler_stats", None)
            stats = stats_fn() if callable(stats_fn) else None
            if not isinstance(stats, dict):
                continue
            for fam_name, key in per_family.items():
                samples[fam_name].append(
                    ({"model": model_name}, int(stats.get(key) or 0)))
            for fam_name, key in by_phase.items():
                samples[fam_name].extend(
                    ({"model": model_name, "phase": phase}, float(seconds))
                    for phase, seconds in (stats.get(key) or {}).items())
        families.extend(
            (name, rows) for name, rows in samples.items() if rows)
        return families

    @staticmethod
    def _collect_shm_ring():
        """Scrape-time view of the process-wide seqlock torn-read
        counter (tpuserver.shm_ring) — readers are client-side code
        with no server handle, so the module counter is the single
        account and this is its exposition."""
        from tpuserver import shm_ring

        return [("tpu_shm_ring_torn_total", [({}, shm_ring.torn_total())])]

    def metrics_text(self):
        """The replica's full ``/metrics`` exposition: the ``nv_*``
        compatibility gauges (what the reference server publishes on
        :8002 and perf_analyzer ``--collect-metrics`` scrapes,
        metrics_manager.h:44-91) followed by the ``tpu_*`` registry.
        One snapshot for both transports: the HTTP frontend serves it
        at ``GET /metrics`` and the gRPC frontend via the
        ``ServerMetrics`` unary."""
        lines = []
        rss_bytes = None
        try:
            # current RSS (ru_maxrss is the PEAK, and its unit is
            # platform-dependent; /proc is authoritative on Linux)
            with open("/proc/self/statm") as f:
                rss_bytes = int(f.read().split()[1]) * os.sysconf(
                    "SC_PAGE_SIZE")
        except Exception:
            try:
                import resource
                import sys

                peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                # Linux reports KB, macOS bytes; label it as the peak
                # it is rather than mislabeling it current
                rss_bytes = peak * (1 if sys.platform == "darwin" else 1024)
            except Exception:
                pass
        if rss_bytes is not None:
            lines.append(
                "# HELP nv_cpu_memory_used_bytes Server RSS.\n"
                "# TYPE nv_cpu_memory_used_bytes gauge\n"
                "nv_cpu_memory_used_bytes {}".format(rss_bytes))
        try:
            import jax

            devices = [
                d for d in jax.devices() if d.platform != "cpu"
            ]
            for i, dev in enumerate(devices):
                stats = {}
                try:
                    stats = dev.memory_stats() or {}
                except Exception:
                    pass
                used = stats.get("bytes_in_use", 0)
                total = stats.get("bytes_limit", 0)
                label = '{{tpu="{}"}}'.format(i)
                lines.append(
                    "nv_gpu_memory_used_bytes{} {}".format(label, used))
                lines.append(
                    "nv_gpu_memory_total_bytes{} {}".format(label, total))
                if total:
                    # a memory fraction, NOT compute duty-cycle — keep it
                    # out of nv_gpu_utilization (whose nv_* semantics,
                    # and perf_analyzer's averaging, mean busy-percent)
                    lines.append(
                        "nv_gpu_memory_utilization{} {}".format(
                            label, used / total))
        except Exception:
            pass
        for stat in self.model_statistics()["model_stats"]:
            label = '{{model="{}"}}'.format(stat["name"])
            lines.append(
                "nv_inference_count{} {}".format(
                    label, stat["inference_count"]))
            lines.append(
                "nv_inference_exec_count{} {}".format(
                    label, stat["execution_count"]))
        return ("\n".join(lines) + "\n" if lines else "") \
            + self.metrics.render()

    def mark_ready(self):
        """Flip a ``starting`` server to ``ready`` (after warmup), or
        cancel an in-progress ``begin_drain()`` (an ops undrain: the
        replica rejoins the fleet and readiness probes flip back).  A
        ``stopped`` server stays stopped — its workers are gone; only
        ``attach_frontend`` re-opens one."""
        with self._inflight_cond:
            if self._state in ("starting", "draining"):
                self._state = "ready"
                # wake a drain() waiting on inflight==0 so it observes
                # the cancellation instead of closing a serving server
                self._inflight_cond.notify_all()

    def set_max_inflight(self, max_inflight):
        """Adjust the server-wide in-flight cap at runtime (None lifts
        it); an ops valve, also what overload tests flip."""
        with self._inflight_cond:
            self._max_inflight = max_inflight
            self._inflight_cond.notify_all()

    def _enter_inflight(self):
        with self._inflight_cond:
            if self._state != "ready":
                reason = {
                    "starting": "starting and not yet ready",
                    "draining": "draining",
                }.get(self._state, "shut down")
                raise ShuttingDown(
                    "server is {}; not accepting new requests".format(
                        reason
                    )
                )
            if (
                self._max_inflight is not None
                and self._inflight >= self._max_inflight
            ):
                raise Overloaded(
                    "server is at its in-flight request cap ({}); "
                    "retry later".format(self._max_inflight)
                )
            self._inflight += 1

    def _exit_inflight(self):
        with self._inflight_cond:
            self._inflight -= 1
            # the only waiter is drain()'s inflight==0 loop, and it can
            # only be waiting after begin_drain() flipped the state (a
            # flip this exit cannot miss: both run under the cond) — a
            # ready-state exit pays no wakeup syscall on the hot path
            if self._state != "ready":
                self._inflight_cond.notify_all()

    def inflight_count(self):
        with self._inflight_cond:
            return self._inflight

    def begin_drain(self):
        """Stop admission and flip readiness; in-flight work continues.
        The first half of :meth:`drain`, split out so probes can observe
        the draining state."""
        with self._inflight_cond:
            if self._state != "stopped":
                self._state = "draining"

    def drain(self, timeout=30.0):
        """Graceful shutdown: stop admission (new requests get a typed
        503), let in-flight requests — including scheduler-backed
        generations — finish within ``timeout`` seconds, then close,
        deterministically failing whatever remains.

        A concurrent :meth:`mark_ready` (undrain) aborts the drain:
        once the server is admitting again, running ``close()`` would
        hard-kill the just-admitted requests.  Undrain is only safe
        BEFORE the wait completes — cancel early or not at all."""
        self.begin_drain()
        deadline = time.monotonic() + timeout
        # model-owned schedulers drain first: their in-flight
        # generations are the long-lived work the deadline budgets for.
        # Per-model guard: one failing drainer must not abort the whole
        # graceful shutdown (the server would be stuck 'draining' with
        # close() never reached)
        for model in list(self._models.values()):
            drainer = getattr(model, "drain", None)
            if callable(drainer):
                try:
                    drainer(max(0.0, deadline - time.monotonic()))
                except Exception:  # noqa: BLE001 — close() must run
                    pass
        with self._inflight_cond:
            while self._inflight > 0 and self._state == "draining":
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._inflight_cond.wait(remaining)
            if self._state == "ready":
                return  # undrained mid-wait: the server is serving again
        self.close()

    def load_model(self, name):
        if name not in self._models:
            raise ServerError(
                "failed to load '{}', no such model".format(name), code=400
            )
        self._ready[name] = True

    def unload_model(self, name, unload_dependents=False):
        if name not in self._models:
            raise ServerError(
                "failed to unload '{}', no such model".format(name), code=400
            )
        self._ready[name] = False
        if unload_dependents:
            model = self._models[name]
            for step in model.ensemble_steps or []:
                if step["model_name"] in self._models:
                    self._ready[step["model_name"]] = False

    def repository_index(self, ready_only=False):
        out = []
        for name, model in sorted(self._models.items()):
            ready = self._ready.get(name, False)
            if ready_only and not ready:
                continue
            out.append(
                {
                    "name": name,
                    "version": model.version,
                    "state": "READY" if ready else "UNAVAILABLE",
                    "reason": "",
                }
            )
        return out

    # -- metadata ----------------------------------------------------------

    def server_metadata(self):
        return {
            "name": SERVER_NAME,
            "version": SERVER_VERSION,
            "extensions": list(SERVER_EXTENSIONS),
        }

    def model_metadata(self, name, version=""):
        return self._get_model(name, version).metadata_dict()

    def model_config(self, name, version=""):
        return self._get_model(name, version).config_dict()

    def model_statistics(self, name="", version=""):
        out = []
        for mname, model in sorted(self._models.items()):
            if name and mname != name:
                continue
            out.append(self._stats[mname].as_dict(mname, model.version))
        if name and not out:
            raise ServerError(
                "Request for unknown model: '{}' is not found".format(name),
                code=404,
            )
        return {"model_stats": out}

    # -- settings ----------------------------------------------------------

    def get_trace_settings(self, model_name=None):
        return {"settings": dict(self._trace_settings)}

    def update_trace_settings(self, model_name=None, settings=None):
        for key, val in (settings or {}).items():
            if val is None:
                continue
            self._trace_settings[key] = (
                [str(v) for v in val] if isinstance(val, list) else [str(val)]
            )
        return self.get_trace_settings(model_name)

    def get_log_settings(self):
        return dict(self._log_settings)

    def update_log_settings(self, settings):
        for key, val in (settings or {}).items():
            if key not in self._log_settings:
                raise ServerError("unknown log setting '{}'".format(key))
            self._log_settings[key] = val
        return self.get_log_settings()

    # -- shared memory -----------------------------------------------------

    def register_system_shm(self, name, key, offset, byte_size):
        if name in self._system_shm:
            raise ServerError(
                "shared memory region '{}' already in manager".format(name)
            )
        try:
            region = _SystemShmRegion(name, key, offset, byte_size)
        except OSError as e:
            raise ServerError(
                "unable to open shared memory region '{}': {}".format(name, e)
            )
        with self._shm_lock:  # publish atomically vs pin/unregister
            if name in self._system_shm:
                region.close()
                raise ServerError(
                    "shared memory region '{}' already in "
                    "manager".format(name)
                )
            self._system_shm[name] = region

    def unregister_system_shm(self, name=""):
        # pin check and registry pop are ONE atomic step under
        # _shm_lock: a pin taken concurrently (a generation starting)
        # either lands before the pop — and the unregister conflicts —
        # or after — and finds the region gone, a typed 400.  The
        # close itself (syscalls) runs outside the lock.
        with self._shm_lock:
            if name:
                self._check_unpinned_locked(name)
                regions = [self._system_shm.pop(name, None)]
            else:
                for rname in self._system_shm:
                    self._check_unpinned_locked(rname)
                regions = list(self._system_shm.values())
                self._system_shm.clear()
        for region in regions:
            if region is not None:
                region.close()

    def system_shm_status(self, name=""):
        regions = {}
        for rname, r in self._system_shm.items():
            if name and rname != name:
                continue
            regions[rname] = {
                "name": rname,
                "key": r.key,
                "offset": r.offset,
                "byte_size": r.byte_size,
            }
        return regions

    def register_cuda_shm(self, name, raw_handle, device_id, byte_size):
        raise ServerError(
            "failed to register CUDA shared memory region '{}': no CUDA "
            "devices on a TPU host (use xla shared memory)".format(name)
        )

    def unregister_cuda_shm(self, name=""):
        self._cuda_shm.clear()

    def cuda_shm_status(self, name=""):
        return {}

    def register_xla_shm(self, name, raw_handle, device_ordinal, byte_size):
        if name in self._xla_shm:
            raise ServerError(
                "shared memory region '{}' already in manager".format(name)
            )
        try:
            region = _XlaShmRegion(
                name, raw_handle, device_ordinal, byte_size
            )
        except Exception as e:
            raise ServerError(
                "unable to attach xla shared memory region '{}': {}".format(
                    name, e
                )
            )
        with self._shm_lock:  # publish atomically vs pin/unregister
            if name in self._xla_shm:
                region.close()
                raise ServerError(
                    "shared memory region '{}' already in "
                    "manager".format(name)
                )
            self._xla_shm[name] = region

    def unregister_xla_shm(self, name=""):
        # same atomicity as unregister_system_shm: check + pop under
        # one _shm_lock hold, close/unlink outside it
        with self._shm_lock:
            if name:
                self._check_unpinned_locked(name)
                dropped = [(name, self._xla_shm.pop(name, None))]
            else:
                for rname in self._xla_shm:
                    self._check_unpinned_locked(rname)
                dropped = list(self._xla_shm.items())
                self._xla_shm.clear()
            for rname, _ in dropped:
                self._drop_export_entry_locked(rname)
        for _, region in dropped:
            if region is not None:
                region.close()
                self._destroy_owned(region)

    def xla_shm_status(self, name=""):
        regions = {}
        for rname, r in self._xla_shm.items():
            if name and rname != name:
                continue
            regions[rname] = {
                "name": rname,
                "device_ordinal": r.device_ordinal,
                "byte_size": r.byte_size,
            }
        return regions

    # -- region pinning (the in-flight-reference contract) -----------------

    def pin_shm_region(self, name):
        """Mark ``name`` as referenced by an in-flight generation or a
        registered token ring.  While pinned, unregister is a typed
        409 :class:`ShmRegionInUse` — never a crash mid-stream or a
        silent write into freed memory.  Raises the usual 400 when the
        region is not registered at all.  Pins nest (one per
        referencing stream); pair every pin with :meth:`unpin_shm_region`."""
        with self._shm_lock:
            self._shm_region(name)  # existence check, typed 400
            self._shm_pins[name] = self._shm_pins.get(name, 0) + 1

    def unpin_shm_region(self, name):
        with self._shm_lock:
            count = self._shm_pins.get(name, 0) - 1
            if count > 0:
                self._shm_pins[name] = count
            else:
                self._shm_pins.pop(name, None)

    def _check_unpinned_locked(self, name):
        """Raise the typed 409 for a pinned region.  Called with
        ``_shm_lock`` held (the unregister paths take it around the
        check AND the registry pop, so a concurrent pin can never land
        between the two)."""
        pins = self._shm_pins.get(name, 0)
        if pins > 0:
            raise ShmRegionInUse(
                "cannot unregister shared memory region '{}': {} "
                "in-flight generation(s) or token ring(s) still "
                "reference it; retry after they finish".format(name, pins)
            )

    # -- server-owned KV exports (park-attach resume) ----------------------

    @staticmethod
    def _kv_export_region_name(generation_id):
        return "kvexport/{}".format(generation_id)

    def export_kv_region(self, generation_id, cache, position):
        """Park a finished-with-for-now generation's gathered KV pages
        (a device-resident ``jax.Array``) as a server-owned XLA-shm
        region keyed by the generation id.  A same-host resume (or a
        restarted frontend over the same core) attaches the region and
        re-scatters it instead of re-prefilling ``prompt + history`` —
        token-identical by construction (greedy decode is
        deterministic; pinned in tests/test_shm_data_plane.py)."""
        from tritonclient.utils import xla_shared_memory as xshm

        name = self._kv_export_region_name(generation_id)
        byte_size = int(cache.size) * cache.dtype.itemsize
        self.drop_kv_region(generation_id)  # a reused id supersedes
        owner = xshm.create_shared_memory_region(name, byte_size)
        try:
            region = _XlaShmRegion(
                name, xshm.get_raw_handle(owner), 0, byte_size)
        except Exception:
            xshm.destroy_shared_memory_region(owner)
            raise
        region._owner_handle = owner
        region.put_device_array(0, cache)
        with self._shm_lock:
            self._xla_shm[name] = region
            self._kv_exports[generation_id] = (
                name, int(position), tuple(cache.shape), str(cache.dtype))
            self._kv_export_claims.discard(generation_id)

    def import_kv_region(self, generation_id):
        """``(device cache, parked position)`` of a prior export, or
        None when the generation never exported, the region was
        unregistered, or the device segment is no longer live (e.g. a
        cross-process attach) — the caller then falls back to the
        re-prefill path, gracefully."""
        with self._shm_lock:
            entry = self._kv_exports.get(generation_id)
            if entry is None:
                return None
            name, position, _, _ = entry
            region = self._xla_shm.get(name)
        if region is None:
            with self._shm_lock:
                self._kv_exports.pop(generation_id, None)
            return None
        cache = region.handle.get_jax_segment(0)
        if cache is None:
            return None
        return cache, position

    def drop_kv_region(self, generation_id):
        """Release a generation's KV export (resume consumed it, or its
        replay entry aged out): region unregistered, host window
        unlinked.  Idempotent."""
        with self._shm_lock:
            entry = self._kv_exports.pop(generation_id, None)
            self._kv_export_claims.discard(generation_id)
            region = self._xla_shm.pop(entry[0], None) if entry else None
        if region is not None:
            region.close()
            self._destroy_owned(region)

    def kv_export_descriptor(self, generation_id):
        """Wire descriptor of a live KV export — the transfer handle a
        decode-role replica attaches to re-scatter a prefill leg's
        pages instead of re-prefilling (docs/resilience.md
        "Disaggregated prefill/decode").

        The contract is **one-shot**: the first fetch claims the export
        (the disagg orchestrator hands it to exactly one decode
        replica), a second fetch for the same generation raises the
        typed 409 ``KvExportConflict``, and a fetch for a generation
        with no live export (never exported, dropped, or TTL-expired
        with its replay entry) raises the typed 404 ``KvExportNotFound``
        — the caller falls back to the fused re-prefill path instead of
        crashing later inside the ``paged_gather`` scatter.

        Fetching forces the device-resident pages into the region's
        host staging window (one device→host sync, outside the shm
        lock) so a cross-process attach reads real bytes.  Returns a
        JSON-able dict::

            {"generation_id", "name", "raw_handle", "position",
             "shape", "dtype", "byte_size", "device_ordinal"}
        """
        from tritonclient.utils import xla_shared_memory as xshm

        with self._shm_lock:
            entry = self._kv_exports.get(generation_id)
            region = self._xla_shm.get(entry[0]) if entry else None
            if entry is None or region is None:
                if entry is not None:
                    # region unregistered under the record: forget it
                    self._kv_exports.pop(generation_id, None)
                    self._kv_export_claims.discard(generation_id)
                raise KvExportNotFound(
                    "no live KV export for generation '{}' (never "
                    "exported, dropped, or expired); fall back to "
                    "prefill".format(generation_id))
            if generation_id in self._kv_export_claims:
                raise KvExportConflict(
                    "KV export for generation '{}' already claimed: the "
                    "transfer contract is one-shot".format(generation_id))
            self._kv_export_claims.add(generation_id)
            name, position, shape, dtype = entry
        try:
            # device->host sync + handle serialization outside the lock
            # (syscall/DMA work never holds _shm_lock)
            owner = getattr(region, "_owner_handle", None)
            handle = owner if owner is not None else region.handle
            region.read(0, region.byte_size)
            raw = xshm.get_raw_handle(handle)
        except Exception:
            with self._shm_lock:  # leave the export fetchable again
                self._kv_export_claims.discard(generation_id)
            raise
        return {
            "generation_id": generation_id,
            "name": name,
            "raw_handle": raw.decode("ascii"),
            "position": int(position),
            "shape": list(shape),
            "dtype": dtype,
            "byte_size": int(region.byte_size),
            "device_ordinal": int(region.device_ordinal),
        }

    def import_kv_descriptor(self, descriptor):
        """Attach a KV export published by another replica from its wire
        descriptor: ``(device cache, parked position)`` ready for the
        scheduler's attach-admission path.  In-process the device
        segment aliases zero-copy; cross-process the host staging
        window is read once and device_put.  A malformed or unreachable
        descriptor raises the typed 404 ``KvExportNotFound`` — at
        admission time, never a late crash inside the scatter."""
        import jax.numpy as jnp
        from tritonclient.utils import xla_shared_memory as xshm

        try:
            raw = descriptor["raw_handle"]
            shape = tuple(int(d) for d in descriptor["shape"])
            try:
                dtype = np.dtype(descriptor["dtype"])
            except TypeError:
                # extension dtypes (bfloat16 — the default KV wire
                # dtype) resolve only once ml_dtypes registers them
                import ml_dtypes  # noqa: F401

                dtype = np.dtype(descriptor["dtype"])
            position = int(descriptor["position"])
            byte_size = int(descriptor.get("byte_size")
                            or int(np.prod(shape)) * dtype.itemsize)
        except (KeyError, TypeError, ValueError) as e:
            raise KvExportNotFound(
                "malformed kv-export descriptor: {}".format(e))
        try:
            handle = xshm.attach_from_raw_handle(raw)
        except Exception as e:
            raise KvExportNotFound(
                "kv export unreachable (region gone?): {}".format(e))
        try:
            cache = handle.get_jax_segment(0)
            if cache is not None:  # in-process: zero-copy alias
                if tuple(cache.shape) != shape:
                    cache = cache.reshape(shape)
                return cache, position
            host = np.frombuffer(
                handle.read_bytes(0, byte_size), dtype=dtype).reshape(shape)
            return jnp.asarray(host), position
        except KvExportNotFound:
            raise
        except Exception as e:
            raise KvExportNotFound(
                "kv export attach failed for region '{}': {}".format(
                    descriptor.get("name", "?"), e))
        finally:
            handle.detach()

    def _drop_export_entry_locked(self, region_name):
        """Forget the export record pointing at ``region_name`` (the
        region itself is being unregistered by the caller).  Called
        with ``_shm_lock`` held."""
        for gid, entry in list(self._kv_exports.items()):
            if entry[0] == region_name:
                self._kv_exports.pop(gid, None)
                self._kv_export_claims.discard(gid)

    @staticmethod
    def _destroy_owned(region):
        """Unlink the owner handle of a server-created region (client
        regions are owned by the client; their unregister only
        detaches)."""
        owner = getattr(region, "_owner_handle", None)
        if owner is not None:
            from tritonclient.utils import xla_shared_memory as xshm

            try:
                xshm.destroy_shared_memory_region(owner)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    def _shm_region(self, name):
        region = self._system_shm.get(name) or self._xla_shm.get(name)
        if region is None:
            raise ServerError(
                "Unable to find shared memory region: '{}'".format(name)
            )
        return region

    def xla_shm_region(self, name):
        """Public lookup of a registered XLA region (for models that park
        device state in shm, e.g. llama KV caches); raises ServerError when
        unknown."""
        region = self._xla_shm.get(name)
        if region is None:
            raise ServerError(
                "Unable to find xla shared memory region: '{}'".format(name)
            )
        return region

    @staticmethod
    def _check_shm_bounds(region, byte_size, offset, direction):
        """Typed 400 for a shared-memory reference outside its
        registered region — at request time, instead of an opaque
        buffer/mmap error deep inside the shm read/write."""
        try:
            byte_size = int(byte_size)
            offset = int(offset)
        except (TypeError, ValueError):
            raise ServerError(
                "shared-memory {} reference for region '{}' must carry "
                "integer byte_size/offset (got byte_size={!r}, "
                "offset={!r})".format(
                    direction, region.name, byte_size, offset
                ),
                code=400,
            )
        if byte_size < 0 or offset < 0:
            raise ServerError(
                "shared-memory {} reference for region '{}' must be "
                "non-negative (got byte_size={}, offset={})".format(
                    direction, region.name, byte_size, offset
                ),
                code=400,
            )
        if offset + byte_size > region.byte_size:
            raise ServerError(
                "shared-memory {} reference out of bounds for region "
                "'{}': offset {} + byte_size {} exceeds the registered "
                "size {}".format(
                    direction, region.name, offset, byte_size,
                    region.byte_size,
                ),
                code=400,
            )
        return byte_size, offset

    def read_shm_input(self, region_name, byte_size, offset, datatype, shape):
        """Materialize an input tensor from a registered shm region.

        For XLA regions holding live device buffers this returns the
        ``jax.Array`` itself — no host copy."""
        # shm-read-failure chaos hook (scoped: multi-replica harnesses
        # can fail one replica's shm plane)
        faults.fire("core.shm_read", self.fault_scope)
        region = self._shm_region(region_name)
        byte_size, offset = self._check_shm_bounds(
            region, byte_size, offset, "input"
        )
        if isinstance(region, _XlaShmRegion):
            arr = region.get_device_array(offset, datatype, shape)
            if arr is not None:
                # the zero-copy fast path: count the logical tensor
                # size (the bytes that did NOT need to cross the host)
                self._m_shm_read.inc(
                    int(arr.size) * arr.dtype.itemsize)
                return arr
        self._m_shm_read.inc(byte_size)
        raw = region.read(offset, byte_size)
        if datatype == "BYTES":
            return deserialize_bytes_tensor(raw).reshape(
                [s for s in shape]
            )
        np_dtype = triton_to_np_dtype(datatype)
        return np.frombuffer(raw, dtype=np_dtype).reshape(shape)

    def write_shm_output(self, region_name, offset, array, datatype):
        """Write an output tensor into a registered shm region.

        jax.Array outputs written to an in-process XLA region stay on device."""
        region = self._shm_region(region_name)
        if isinstance(region, _XlaShmRegion) and not isinstance(
            array, np.ndarray
        ):
            # the device-resident path is bounds-checked too (.nbytes
            # is metadata on jax arrays — no transfer): a ring slot or
            # output reference past the registered size must be the
            # same typed 400 the host path raises, not a later silent
            # overrun when the segment syncs to the host window
            nbytes = int(array.size) * array.dtype.itemsize
            _, offset = self._check_shm_bounds(region, nbytes, offset,
                                               "output")
            if region.put_device_array(offset, array):
                self._m_shm_written.inc(nbytes)
                return
        if datatype == "BYTES":
            serialized = serialize_byte_tensor(np.asarray(array, dtype=object))
            data = serialized.item() if serialized.size > 0 else b""
        else:
            data = np.ascontiguousarray(np.asarray(array)).tobytes()
        _, offset = self._check_shm_bounds(region, len(data), offset,
                                           "output")
        region.write(offset, data)
        self._m_shm_written.inc(len(data))

    #: bytes per token-ring slot: one int32 TOKEN + one fp32 LOGPROB,
    #: little-endian, packed back to back — the whole per-step event
    #: payload once the tensors travel through shared memory
    SHM_RING_SLOT_BYTES = 8

    def write_shm_ring_slot(self, region_name, offset, token, logprob):
        """Write one generation step into its token-ring slot (the
        shm-delivery twin of the TOKEN/LOGPROB decoupled response):
        int32 token + fp32 logprob packed little-endian, ONE
        bounds-checked region write per step — the same
        :meth:`write_shm_output` plumbing (lookup, bounds, write,
        byte accounting) without paying it twice on the per-token hot
        path.  A ring descriptor pointing past the region is a typed
        400 on THAT step, never an overrun."""
        import struct

        data = struct.pack("<if", int(token), float(logprob))
        region = self._shm_region(region_name)
        _, offset = self._check_shm_bounds(region, len(data), offset,
                                           "output")
        region.write(offset, data)
        self._m_shm_written.inc(len(data))

    def write_shm_ring_seq_word(self, region_name, offset, word):
        """Stamp one 4-byte seqlock word for a ring slot (requests
        opting in via ``shm_ring_seq_base`` — see tpuserver.shm_ring).
        Same bounds-checked plumbing as the slot write: a seq-word
        array pointing past the region is a typed 400 on that step."""
        from tpuserver import shm_ring

        data = shm_ring.pack_word(word)
        region = self._shm_region(region_name)
        _, offset = self._check_shm_bounds(region, len(data), offset,
                                           "output")
        region.write(offset, data)
        self._m_shm_written.inc(len(data))

    # -- inference ---------------------------------------------------------

    @staticmethod
    def _resolve_deadline(request):
        """One canonical monotonic deadline per request: the ``timeout``
        request parameter (microseconds, Triton semantics) combined with
        any transport deadline the frontend stamped on
        ``request.deadline`` (the gRPC context deadline) — the sooner
        wins.  Stored back on the request so downstream consumers (the
        decode scheduler) see the same bound."""
        deadline = getattr(request, "deadline", None)
        t = request.parameters.get("timeout")
        if t:
            try:
                param_deadline = time.monotonic() + int(t) / 1e6
            except (TypeError, ValueError):
                raise ServerError(
                    "request parameter 'timeout' must be an integer "
                    "microsecond count (got {!r})".format(t)
                )
            deadline = (
                param_deadline
                if deadline is None
                else min(deadline, param_deadline)
            )
        request.deadline = deadline
        return deadline

    @staticmethod
    def _check_deadline(deadline):
        if deadline is not None and time.monotonic() >= deadline:
            raise DeadlineExceeded(
                "request deadline expired before execution"
            )

    def infer(self, request):
        """Execute one inference request; returns InferResponse.

        Decoupled models are rejected here (use ``infer_stream``), matching
        server behavior for non-streaming endpoints.
        """
        t0 = time.monotonic()
        self._m_infer_count.inc()
        try:
            deadline = self._resolve_deadline(request)
            self._check_deadline(deadline)
            self._enter_inflight()
            try:
                model = self._get_model(
                    request.model_name, request.model_version
                )
                if model.decoupled:
                    raise ServerError(
                        "model '{}' is a decoupled model: it can only be "
                        "served over the streaming endpoint".format(
                            model.name)
                    )
                return self._execute(model, request)
            finally:
                self._exit_inflight()
        except ServerError as e:
            # typed failures count by wire code: 429 = shed, 504 =
            # deadline, 503 = draining/shutdown — the shed/deadline/
            # error breakdown /metrics carries per verb
            self._count_error("infer", getattr(e, "code", 500))
            raise
        finally:
            self._m_infer_hist.observe(time.monotonic() - t0)

    def infer_stream(self, request):
        """Execute a (possibly decoupled) request; yields InferResponse(s).

        With the ``triton_enable_empty_final_response`` request parameter a
        trailing empty response marked ``triton_final_response`` is emitted
        so clients can detect completion of data-dependent-length streams.
        """
        t0 = time.monotonic()
        self._m_stream_count.inc()
        try:
            deadline = self._resolve_deadline(request)
            self._check_deadline(deadline)
            self._enter_inflight()
            try:
                yield from self._infer_stream_inner(request)
            finally:
                self._exit_inflight()
        except ServerError as e:
            self._count_error("stream_infer", getattr(e, "code", 500))
            raise
        finally:
            # streamed verbs measure submit-to-terminal-event: the
            # duration covers the whole generation, not just dispatch
            self._m_stream_hist.observe(time.monotonic() - t0)

    def _infer_stream_inner(self, request):
        want_final = bool(
            request.parameters.get("triton_enable_empty_final_response")
        )
        model = self._get_model(request.model_name, request.model_version)
        if not model.decoupled:
            resp = self._execute(model, request)
            if want_final:
                resp.parameters["triton_final_response"] = True
            yield resp
            return
        t0 = time.monotonic_ns()
        inputs = dict(request.inputs)
        t1 = time.monotonic_ns()
        count = 0
        try:
            for out in model.execute_stream(inputs, request):
                # per-response deadline enforcement covers EVERY
                # decoupled model (the scheduler path also self-expires;
                # the single-stream path relies on this check): a token
                # produced past the deadline belongs to a request whose
                # client has stopped waiting
                self._check_deadline(request.deadline)
                count += 1
                extra_params = emitted_at = None
                mergeable = False
                if (RESPONSE_PARAMS_KEY in out or EMITTED_AT_KEY in out
                        or MERGEABLE_KEY in out):
                    out = dict(out)
                    extra_params = out.pop(RESPONSE_PARAMS_KEY, None)
                    emitted_at = out.pop(EMITTED_AT_KEY, None)
                    mergeable = out.pop(MERGEABLE_KEY, False)
                resp = self._make_response(model, request, out,
                                           mark_final=False)
                resp.emitted_at = emitted_at
                resp.mergeable = mergeable
                if extra_params:
                    resp.parameters.update(extra_params)
                if want_final:
                    resp.parameters["triton_final_response"] = False
                yield resp
        except Exception as e:
            self._stats[model.name].record(0, 0, 0, 0, 0, ok=False)
            if isinstance(e, ServerError):
                # the scheduler raises the canonical tpuserver.errors
                # types directly (deadline 504, quarantined slot 422,
                # unknown resume id 404 — one definition, R4-enforced).
                # Class/code/retry_after pass through untouched, but a
                # multi-model server needs attribution: scheduler
                # messages carry no model name, so logs/clients could
                # not tell whose stream failed
                prefix = "model '{}': ".format(model.name)
                if (e.args and isinstance(e.args[0], str)
                        and not e.args[0].startswith(prefix)):
                    e.args = (prefix + e.args[0],) + e.args[1:]
                raise
            # the two scheduler-lifecycle signals that stay scheduler-
            # local types map to their typed wire forms here:
            # admission-full -> 429 (+Retry-After), closed/draining ->
            # 503 — instead of the generic 500 wrap
            if isinstance(e, _scheduler.AdmissionQueueFull):
                # the adaptive shed controller computes Retry-After
                # from its current control interval — the pace the
                # queue is actually draining; the fixed-cliff shed
                # keeps the 1s default
                raise Overloaded(
                    "model '{}': {}".format(model.name, e),
                    retry_after=getattr(e, "retry_after", None) or 1)
            if isinstance(e, _scheduler.SchedulerClosed):
                raise ShuttingDown("model '{}': {}".format(model.name, e))
            raise ServerError(
                "inference failed for model '{}': {}".format(model.name, e),
                code=500,
            )
        t2 = time.monotonic_ns()
        self._stats[model.name].record(
            self._batch_of(model, inputs), 0, t1 - t0, t2 - t1, 0
        )
        if want_final:
            yield InferResponse(
                model.name, model.version, request.id, [],
                parameters={"triton_final_response": True},
            )

    def _batch_of(self, model, inputs):
        if model.max_batch_size > 0 and inputs:
            first = next(iter(inputs.values()))
            # .shape/.ndim are metadata on numpy and jax arrays alike;
            # np.asarray here would force a device→host transfer when the
            # input is a device-resident jax.Array from an XLA shm region.
            shape = getattr(first, "shape", None)
            if shape is None:
                shape = np.asarray(first).shape
            return int(shape[0]) if len(shape) > 0 else 1
        return 1

    def _execute(self, model, request):
        stats = self._stats[model.name]
        t_queue0 = time.monotonic_ns()
        # compute_input: materialize shm-resident inputs already done by
        # frontend; here validate presence.
        t_ci0 = time.monotonic_ns()
        inputs = dict(request.inputs)
        declared = {t.name: t for t in model.inputs}
        for t in model.inputs:
            if t.name not in inputs:
                raise ServerError(
                    "expected {} inputs but got {} inputs for model '{}': "
                    "missing '{}'".format(
                        len(model.inputs), len(inputs), model.name, t.name
                    )
                )
        for name in inputs:
            if declared and name not in declared:
                raise ServerError(
                    "unexpected inference input '{}' for model '{}'".format(
                        name, model.name
                    )
                )
        t_cf0 = time.monotonic_ns()
        batch_queue_ns = 0
        try:
            if model.ensemble_steps is not None:
                outputs = self._execute_ensemble(model, inputs, request)
            elif model.sequence:
                outputs = self._execute_sequence(model, inputs, request)
            elif self._batchable(model, inputs, request):
                # the batcher reports how long this request sat in its
                # batching window: that wait lands in the KServe `queue`
                # bucket, so the profiler's server-side breakdown can
                # tell queueing from actual device compute
                outputs, batch_queue_ns = self._batcher_of(model).submit(
                    inputs, int(next(iter(inputs.values())).shape[0])
                )
            else:
                outputs = model.execute(inputs, request)
        except ServerError:
            stats.record(0, 0, 0, 0, 0, ok=False)
            raise
        except Exception as e:
            stats.record(0, 0, 0, 0, 0, ok=False)
            # malformed tensors surface as ValueError from the model's
            # numpy/jax ops: a client error (400), matching the batched
            # path and the frontends' own ValueError mapping
            raise ServerError(
                "inference failed for model '{}': {}".format(model.name, e),
                code=400 if isinstance(e, ValueError) else 500,
            )
        t_co0 = time.monotonic_ns()
        # the deadline is a contract, not advice: a result produced past
        # it is reported as 504 (the client has stopped waiting) and
        # counted as a failure in the model stats
        if request.deadline is not None and time.monotonic() >= (
            request.deadline
        ):
            stats.record(0, 0, 0, 0, 0, ok=False)
            raise DeadlineExceeded(
                "request deadline expired during execution"
            )
        resp = self._make_response(model, request, outputs)
        t_end = time.monotonic_ns()
        stats.record(
            self._batch_of(model, inputs),
            (t_ci0 - t_queue0) + batch_queue_ns,
            t_cf0 - t_ci0,
            max(0, (t_co0 - t_cf0) - batch_queue_ns),
            t_end - t_co0,
        )
        return resp

    def _batchable(self, model, inputs, request):
        """Route through the dynamic batcher? Requires the model to opt
        in, host (numpy) inputs with a leading batch dim, one consistent
        row count, and no per-request parameters (batched execution sees
        no request object)."""
        if not (model.dynamic_batching and model.max_batch_size > 1):
            return False
        # lifecycle-only parameters (deadline/priority plumbing) don't
        # make a request un-batchable — the deadline is enforced in
        # infer(), not inside batched execution
        extra_params = set(request.parameters) - {"timeout", "priority"}
        if extra_params or not inputs:
            return False
        on_device = isinstance(model, JaxModel)
        rows = None
        for arr in inputs.values():
            ok = isinstance(arr, np.ndarray)
            if not ok and on_device:
                # device-resident inputs (XLA-shm fast path) batch too —
                # the batcher stacks them on device, no host copy
                import jax

                ok = isinstance(arr, jax.Array)
            if not ok or getattr(arr, "ndim", 0) < 1:
                return False
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                return False
        return True

    def _batcher_of(self, model):
        batcher = self._batchers.get(model.name)
        if batcher is None:
            with self._lock:
                if self._closed:
                    # a request racing close() must not lazily resurrect
                    # a batcher whose stop() already ran
                    raise ServerError("server is shutting down", code=503)
                batcher = self._batchers.get(model.name)
                if batcher is None:
                    batcher = _DynamicBatcher(model)
                    self._batchers[model.name] = batcher
        return batcher

    def attach_frontend(self):
        """Frontends register on start(); the last detach closes the
        core's background workers, so frontend shutdown paths reach
        batcher stop()/unload errors instead of leaking threads."""
        with self._lock:
            self._frontends += 1
            self._closed = False  # re-attach after close re-opens
        with self._inflight_cond:
            if self._state == "stopped":
                self._state = "ready"

    def detach_frontend(self):
        to_stop = []
        with self._lock:
            self._frontends = max(0, self._frontends - 1)
            if self._frontends == 0:
                # decide AND mark closed under the same lock hold: a
                # concurrent attach_frontend can only run before (it
                # bumps the count, no close) or after (it re-opens and
                # batchers lazily recreate) — never see a close land
                # under a live attach
                self._closed = True
                to_stop, self._batchers = list(
                    self._batchers.values()), {}
        for b in to_stop:
            b.stop()

    def close(self):
        """Stop background workers (dynamic batchers, and any model-owned
        schedulers via the model's own ``close``).  Safe to call twice;
        after close, batched/scheduled inference is rejected rather than
        lazily recreating workers."""
        with self._inflight_cond:
            self._state = "stopped"
            self._inflight_cond.notify_all()
        with self._lock:
            self._closed = True
            batchers, self._batchers = list(self._batchers.values()), {}
        for b in batchers:
            b.stop()
        for model in list(self._models.values()):
            closer = getattr(model, "close", None)
            if callable(closer):
                closer()
        # server-owned KV exports die with the server: their host
        # windows unlink so healed replicas never inherit stale
        # /dev/shm files (the chaos --shm zero-leak invariant)
        with self._shm_lock:
            export_ids = list(self._kv_exports)
        for gid in export_ids:
            self.drop_kv_region(gid)

    def _execute_sequence(self, model, inputs, request):
        if request.sequence_id == 0:
            raise ServerError(
                "inference request to model '{}' must specify a non-zero "
                "sequence id".format(model.name)
            )
        self._expire_idle_sequences(model)
        key = (model.name, request.sequence_id)
        if request.sequence_start:
            state = None
        else:
            if key not in self._sequence_state:
                raise ServerError(
                    "inference request for sequence {} to model '{}' must "
                    "specify the START flag on the first request of the "
                    "sequence".format(request.sequence_id, model.name)
                )
            state = self._sequence_state[key][0]
        outputs, new_state = model.execute_sequence(inputs, state, request)
        if request.sequence_end:
            self._sequence_state.pop(key, None)
        else:
            self._sequence_state[key] = (new_state, time.monotonic())
        return outputs

    def _expire_idle_sequences(self, model):
        """Drop sequences idle beyond their model's
        ``max_sequence_idle_us`` so abandoned sequences (no END request)
        cannot grow state unboundedly — role of the reference sequence
        batcher's max_sequence_idle_microseconds expiry.  One sweep
        covers EVERY model's sequences (each judged by its own idle
        window), so a model that stops receiving traffic still gets its
        abandoned state reclaimed by any other model's requests.  Swept
        at most once per triggering model's half-window (min 50 ms) so
        the scan stays off the per-request hot path, over an atomic
        snapshot so concurrent frontend threads can insert/pop freely."""
        idle_us = getattr(model, "max_sequence_idle_us", 60_000_000)
        now = time.monotonic()
        sweep_gap = max(idle_us / 1e6 / 2.0, 0.05)
        if now - self._last_sequence_sweep < sweep_gap:
            return
        self._last_sequence_sweep = now
        idle_cache = {}
        expired = []
        for key, (_, touched) in list(self._sequence_state.items()):
            name = key[0]
            if name not in idle_cache:
                owner = self._models.get(name)
                idle_cache[name] = getattr(
                    owner, "max_sequence_idle_us", 60_000_000
                ) if owner is not None else 0
            if touched < now - idle_cache[name] / 1e6:
                expired.append(key)
        for key in expired:
            self._sequence_state.pop(key, None)

    def _execute_ensemble(self, model, inputs, request):
        tensors = dict(inputs)
        for step in model.ensemble_steps:
            sub = self._get_model(step["model_name"])
            sub_inputs = {
                model_in: tensors[ens_name]
                for model_in, ens_name in step["input_map"].items()
            }
            sub_req = InferRequest(
                sub.name, "", request.id, sub_inputs, None, request.parameters
            )
            sub_out = sub.execute(sub_inputs, sub_req)
            for model_out, ens_name in step["output_map"].items():
                tensors[ens_name] = sub_out[model_out]
        return {
            t.name: tensors[t.name] for t in model.outputs
        }

    def _classify(self, array, class_count, labels):
        """Top-k classification strings 'value:index[:label]' per batch row."""
        arr = np.asarray(array)
        squeeze = arr.ndim == 1
        mat = arr.reshape(1, -1) if squeeze else arr.reshape(arr.shape[0], -1)
        k = min(class_count, mat.shape[-1])
        idx = np.argsort(-mat, axis=-1)[:, :k]
        rows = []
        for r in range(mat.shape[0]):
            row = []
            for i in idx[r]:
                entry = "{:f}:{}".format(float(mat[r, i]), int(i))
                if labels is not None and int(i) < len(labels):
                    entry += ":" + labels[int(i)]
                row.append(entry.encode("utf-8"))
            rows.append(row)
        out = np.array(rows, dtype=np.object_)
        if squeeze:
            out = out.reshape(-1)
        return out

    #: delivery options of a default (no requested_outputs) response:
    #: one shared immutable dict instead of a per-output allocation on
    #: the hot path — consumers only read it
    _DEFAULT_DELIVERY = {"binary_data": True, "shm_region": None,
                         "shm_byte_size": 0, "shm_offset": 0}

    def _make_response(self, model, request, outputs, mark_final=True):
        declared = {t.name: t for t in model.outputs}
        requested = request.requested_outputs
        if not requested:
            # the overwhelmingly common shape (every output, wire
            # delivery, no classification): skip the RequestedOutput
            # and per-output delivery-dict allocations entirely —
            # measured at several percent of the simple-model
            # per-request hot path (ISSUE 11 headline recapture)
            resp_outputs = []
            for name, array in outputs.items():
                spec = declared.get(name)
                datatype = spec.datatype if spec is not None else None
                if not datatype:
                    datatype = _np_to_wire(array)
                np_arr = np.asarray(array) if not hasattr(
                    array, "addressable_shards"
                ) else array
                resp_outputs.append((
                    {"name": name, "datatype": datatype,
                     "shape": list(np_arr.shape)},
                    np.asarray(np_arr),
                    self._DEFAULT_DELIVERY,
                ))
            return InferResponse(
                model.name, model.version, request.id, resp_outputs
            )
        wanted = []
        for ro in requested:
            if ro.name not in outputs:
                raise ServerError(
                    "unexpected inference output '{}' for model "
                    "'{}'".format(ro.name, model.name)
                )
            wanted.append(ro)

        resp_outputs = []
        for ro in wanted:
            array = outputs[ro.name]
            spec = declared.get(ro.name)
            if ro.class_count > 0:
                labels = (model.labels or {}).get(ro.name)
                array = self._classify(array, ro.class_count, labels)
                datatype = "BYTES"
            else:
                datatype = spec.datatype if spec is not None else None
                if datatype is None or datatype == "":
                    datatype = _np_to_wire(array)
            np_arr = np.asarray(array) if not hasattr(
                array, "addressable_shards"
            ) else array
            shape = list(np_arr.shape)
            delivery = {
                "binary_data": ro.binary_data,
                "shm_region": ro.shm_region,
                "shm_byte_size": ro.shm_byte_size,
                "shm_offset": ro.shm_offset,
            }
            if ro.shm_region is not None:
                # .nbytes is metadata on both numpy and jax arrays; avoid
                # np.asarray here — it would force a device→host transfer
                # for outputs that stay device-resident in an XLA region.
                expected = (
                    serialized_byte_size(np.asarray(np_arr, dtype=object))
                    if datatype == "BYTES"
                    else int(np_arr.nbytes)
                )
                if expected > ro.shm_byte_size:
                    raise ServerError(
                        "shared memory size specified with the request for "
                        "output '{}' ({} bytes) should be at least {} "
                        "bytes".format(ro.name, ro.shm_byte_size, expected)
                    )
                self.write_shm_output(
                    ro.shm_region, ro.shm_offset, np_arr, datatype
                )
                resp_outputs.append(
                    (
                        {"name": ro.name, "datatype": datatype,
                         "shape": shape},
                        None,
                        delivery,
                    )
                )
            else:
                resp_outputs.append(
                    (
                        {"name": ro.name, "datatype": datatype,
                         "shape": shape},
                        np.asarray(np_arr),
                        delivery,
                    )
                )
        return InferResponse(
            model.name, model.version, request.id, resp_outputs
        )


def install_sigterm_drain(server, drain_timeout=30.0):
    """Install a SIGTERM handler that gracefully drains ``server``:
    admission stops and readiness flips immediately (so load balancers
    route away), in-flight generations finish within ``drain_timeout``
    seconds, and the rest fail deterministically.  The drain runs on a
    worker thread — signal handlers must return promptly.  Returns the
    previous handler (pass it back to ``signal.signal`` to restore).
    Main-thread only, as all Python signal installation is."""
    import signal

    def _handler(signum, frame):
        threading.Thread(
            target=server.drain,
            args=(drain_timeout,),
            name="sigterm-drain",
            daemon=True,
        ).start()

    return signal.signal(signal.SIGTERM, _handler)


def _np_to_wire(array):
    from tritonclient.utils import np_to_triton_dtype

    dt = np_to_triton_dtype(np.asarray(array).dtype)
    return dt or "FP32"
