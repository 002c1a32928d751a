"""The scheduler loop and the request's life, measured from inside.

Always-on counters (``tpu_scheduler_loop_seconds_total``, the admit and
first-token histograms) and profiler annotations (``sched.*`` spans on
the profiler's clock, executables named after the functions they wrap,
``jax.named_scope`` inside them): docs/observability.md "Tracing".
CPU-sim, tiny config, seconds each.
"""

import glob
import os
import time

import numpy as np
import pytest

from tpuserver.metrics import MetricsRegistry, parse_prometheus_text
from tpuserver.models import llama
from tpuserver.scheduler import (
    LOOP_PHASES,
    AdmissionQueueFull,
    DecodeScheduler,
)

pytestmark = pytest.mark.metrics

CFG = llama.tiny()
MAX_SEQ = 64
PPSEQ = MAX_SEQ // 16
LABELS = {"model": "m"}


@pytest.fixture(scope="module")
def params():
    import jax

    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def fns(params):
    return llama.make_scheduler_fns(CFG, MAX_SEQ, 2)


@pytest.fixture(scope="module")
def fns_small(params):
    """4 rows over a pool that holds ONE full-length sequence."""
    return llama.make_scheduler_fns(CFG, MAX_SEQ, 4, kv_pages=PPSEQ)


def _prompt(i, n=8):
    return ((np.arange(n) * 7 + 3 * i + 1) % 250).astype(np.int32)


def _collect(sched, prompt, n, **kwargs):
    return [t for t, _ in sched.submit(prompt, n, **kwargs)]


def _count(registry, family):
    fam = parse_prometheus_text(registry.render())[family]
    return next(value for name, _, value in fam["samples"]
                if name == family + "_count")


class _TimedLoop(DecodeScheduler):
    """The loop thread's wall time, entry of ``_loop`` to its return."""

    loop_wall_s = None

    def _loop(self, slots, epoch):
        began = time.monotonic()
        try:
            return super()._loop(slots, epoch)
        finally:
            self.loop_wall_s = time.monotonic() - began


def test_loop_phases_tile_the_loop_threads_time(fns, params):
    """After N generations the six phase floats sum to the loop thread's
    wall time (2 %: nothing is left between the phases), the host waited
    for the device (``fetch``) and slept between bursts (``idle``)."""
    sched = _TimedLoop(fns, params, 2, MAX_SEQ)
    try:
        for i in range(6):
            assert len(_collect(sched, _prompt(i), 8)) == 8
            time.sleep(0.02)   # the loop goes idle between generations
    finally:
        sched.close()
    seconds = sched.stats()["loop_seconds"]
    assert tuple(seconds) == LOOP_PHASES
    assert sched.loop_wall_s > 0
    assert abs(sum(seconds.values()) - sched.loop_wall_s) <= (
        0.02 * sched.loop_wall_s), (seconds, sched.loop_wall_s)
    assert all(s > 0 for s in seconds.values()), seconds


def test_offcpu_seconds_lie_within_each_phases_wall(fns, params):
    """Of every phase's wall seconds, the off-CPU part is >= 0 and never
    more than the whole; the loop's sleeps (``idle``) are off the CPU."""
    sched = DecodeScheduler(fns, params, 2, MAX_SEQ)
    try:
        for i in range(4):
            assert len(_collect(sched, _prompt(i), 8)) == 8
            time.sleep(0.02)
    finally:
        sched.close()
    stats = sched.stats()
    wall, offcpu = stats["loop_seconds"], stats["loop_offcpu_seconds"]
    assert tuple(offcpu) == LOOP_PHASES
    for phase in LOOP_PHASES:
        assert 0.0 <= offcpu[phase] <= wall[phase], (phase, offcpu, wall)
    assert offcpu["idle"] > 0.5 * wall["idle"] > 0


def _dispatch_seconds(fns, params, fault_delay=None):
    """(steps, dispatch wall, dispatch off-CPU) of one 12-token
    generation, with every step slowed by ``fault_delay`` seconds."""
    from tpuserver import faults

    registry = MetricsRegistry()
    sched = DecodeScheduler(fns, params, 2, MAX_SEQ, metrics=registry,
                            metric_labels=LABELS, fault_scope="offcpu")
    if fault_delay is not None:
        faults.install("scheduler.step", mode="slow", delay=fault_delay,
                       scope="offcpu")
    try:
        assert len(_collect(sched, _prompt(3), 12)) == 12
    finally:
        faults.clear()
        sched.close()
    stats = sched.stats()
    return (_count(registry, "tpu_scheduler_step_seconds"),
            stats["loop_seconds"]["dispatch"],
            stats["loop_offcpu_seconds"]["dispatch"])


def test_a_slow_step_is_dispatch_time_off_the_cpu(fns, params):
    """``scheduler.step`` in mode ``slow`` sleeps inside ``dispatch`` on
    every step: the phase's off-CPU seconds rise by about the sleep
    times the steps, its CPU seconds (wall less off-CPU) do not."""
    delay = 0.05
    _dispatch_seconds(fns, params)   # compiles the step: no side pays it
    steps0, wall0, off0 = _dispatch_seconds(fns, params)
    steps, wall, off = _dispatch_seconds(fns, params, delay)
    assert steps == steps0 >= 12
    slept = steps * delay
    assert 0.95 * slept <= off - off0 <= 1.5 * slept, (off, off0, slept)
    assert (wall - off) - (wall0 - off0) < 0.25 * slept, (
        wall, off, wall0, off0)


def test_a_coarse_cpu_clock_still_gives_wall_less_cpu(monkeypatch):
    """A CPU clock that moves in 10 ms ticks (as on a host whose kernel
    samples thread CPU time) charges a 1 ms phase a whole tick now and
    then: the excess is carried to the phase's next charges, so its
    off-CPU float never falls, stays within its wall time, and ends at
    wall less CPU (a clamp at each charge would read 95 ms here)."""
    from tpuserver import scheduler

    class Clocks:
        wall = cpu = 0.0

        def monotonic(self):
            return self.wall

        def thread_time(self):
            return self.cpu

    clocks = Clocks()
    monkeypatch.setattr(scheduler, "time", clocks)
    wall = dict.fromkeys(LOOP_PHASES, 0.0)
    offcpu = dict.fromkeys(LOOP_PHASES, 0.0)
    clock = scheduler._LoopClock(wall, offcpu)
    seen = []
    for i in range(100):
        with clock("dispatch"):
            clocks.wall += 0.001
            if i % 20 == 0:
                clocks.cpu += 0.010
        seen.append(offcpu["dispatch"])
        assert 0.0 <= offcpu["dispatch"] <= wall["dispatch"]
    assert seen == sorted(seen)
    assert offcpu["dispatch"] == pytest.approx(0.100 - 0.050)


def test_first_token_observes_fresh_streams_only(fns, params):
    """One first-token observation per fresh generation; a resume (a new
    admission of the same stream object) does not observe again."""
    registry = MetricsRegistry()
    sched = DecodeScheduler(fns, params, 2, MAX_SEQ, metrics=registry,
                            metric_labels=LABELS)
    family = "tpu_scheduler_first_token_seconds"
    try:
        for i in range(3):
            _collect(sched, _prompt(i), 4)
        assert _count(registry, family) == 3
        stream = sched.submit(_prompt(9), 12, generation_id="g-resume")
        got = [next(stream) for _ in range(3)]
        stream.close()   # the consumer walks away after 3 tokens
        deadline = time.monotonic() + 5
        while ("g-resume" not in sched._replay
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert _count(registry, family) == 4
        resumed = list(sched.resume("g-resume", from_seq=len(got)))
        assert len(got) + len(resumed) == 12
        assert _count(registry, family) == 4
    finally:
        sched.close()


def test_admit_histogram_counts_admissions_and_sheds(fns_small, params):
    """With chunked prefill off every call of ``start_admission`` is one
    observation, whether it admitted or shed."""
    registry = MetricsRegistry()
    sched = DecodeScheduler(fns_small, params, 4, MAX_SEQ, metrics=registry,
                            metric_labels=LABELS, prefill_chunk_tokens=None)
    try:
        big = sched.submit(np.array([3, 1, 4, 1, 5], np.int32), 40)
        next(big)        # 3 of the pool's 4 pages pinned by a live stream
        with pytest.raises(AdmissionQueueFull, match="page pool"):
            list(sched.submit(np.array([9, 8, 7], np.int32), 20))
        big.close()
        _collect(sched, _prompt(1), 4)
        admitted, sheds = sched.stats()["admitted"], 1
        assert admitted == 2
        assert _count(registry, "tpu_scheduler_admit_seconds") == (
            admitted + sheds)
    finally:
        sched.close()


@pytest.fixture(scope="module")
def profiled(fns, params, tmp_path_factory):
    """The same generations without and under a profiler session (Python
    tracer off, as ``benchmark/run.py --trace 1`` sets it): the tokens
    of both, and the profile."""
    import jax

    sched = DecodeScheduler(fns, params, 2, MAX_SEQ)
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    try:
        plain = [_collect(sched, _prompt(i), 8) for i in range(3)]
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            traced = [_collect(sched, _prompt(i), 8) for i in range(3)]
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.close()
    path, = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return plain, traced, jax.profiler.ProfileData.from_file(path)


def test_served_tokens_do_not_change_under_a_profiler_session(profiled):
    plain, traced, _ = profiled
    assert traced == plain


def test_profile_holds_the_loops_spans_on_one_thread(profiled):
    """``sched.dispatch`` / ``fetch`` / ``deliver`` (and the admission's
    spans) come from ONE thread's line, the submit span from another."""
    _, _, data = profiled
    lines = [{e.name for e in line.events}
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    loop = [names for names in lines if "sched.dispatch" in names]
    assert len(loop) == 1
    assert {"sched.sweep", "sched.admit", "sched.dispatch", "sched.fetch",
            "sched.deliver"} <= loop[0]
    assert any(n.startswith("sched.prefill") for n in loop[0])
    assert "sched.submit" not in loop[0]
    assert any("sched.submit" in names for names in lines)


def test_profile_names_every_executable_after_its_function(profiled):
    """No executable of the served model is ``jit__unknown``: the
    operations' ``hlo_module`` and jax's dispatch spans carry the names
    of the functions the jitted entries wrap."""
    _, _, data = profiled
    modules, dispatches = set(), set()
    for plane in data.planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("PjitFunction("):
                    dispatches.add(event.name)
                modules.update(v for k, v in event.stats
                               if k == "hlo_module")
    assert {"jit_paged_scheduler_step", "jit_prefill_to_length",
            "jit_paged_admit"} <= modules
    assert {"PjitFunction(paged_scheduler_step)",
            "PjitFunction(prefill_to_length)"} <= dispatches
    assert not [m for m in modules | dispatches if "unknown" in m]


def _scopes(lowered):
    """The scope paths of a lowered program's operations."""
    import re

    return set(re.findall(r'jit\([a-z_]+\)/([A-Za-z_.]+)/',
                          lowered.as_text(debug_info=True)))


def test_step_and_prefill_carry_the_named_scopes(fns, params):
    """Inside the jitted step and prefill every operation a reader of a
    trace asks about sits under its scope."""
    import jax.numpy as jnp

    slots = 2
    step = fns["step"].lower(
        params, fns["init_cache"](), fns["init_logits"](),
        np.zeros((slots, PPSEQ), np.int32), np.zeros((slots,), np.int32),
        np.ones((slots,), bool), np.zeros((slots,), np.int32),
        np.zeros((slots,), bool))
    assert step.as_text().startswith("module @jit_paged_scheduler_step")
    assert {"sample", "embed", "attn.qkv", "attn.kv_write",
            "attn.page_gather", "attn.kernel", "attn.out", "ffn",
            "head"} <= _scopes(step)
    prefill = fns["prefill"].lower(
        params, fns["init_slot_cache"](), jnp.zeros((1, 8), jnp.int32), 8)
    assert prefill.as_text().startswith("module @jit_prefill_to_length")
    assert {"embed", "attn.qkv", "attn.kv_write", "attn.kernel",
            "attn.out", "ffn", "head"} <= _scopes(prefill)


def test_kernel_eligible_step_has_no_page_gather(params):
    """Where the geometry lets the decode kernel read the pool in place
    (every real preset; here ``tiny`` with the kernel stated, 256-token
    rows), the step holds ``attn.kernel`` and NO ``attn.page_gather``,
    and the bundle and ``stats()`` name the path that was built."""
    import dataclasses

    max_seq, slots = 256, 2
    cfg = dataclasses.replace(CFG, decode_impl="pallas")
    kernel_fns = llama.make_scheduler_fns(cfg, max_seq, slots)
    assert kernel_fns["decode_attention"] == "paged_kernel"
    step = kernel_fns["step"].lower(
        params, kernel_fns["init_cache"](), kernel_fns["init_logits"](),
        np.zeros((slots, max_seq // 16), np.int32),
        np.zeros((slots,), np.int32), np.ones((slots,), bool),
        np.zeros((slots,), np.int32), np.zeros((slots,), bool))
    scopes = _scopes(step)
    assert {"attn.kv_write", "attn.kernel"} <= scopes
    assert "attn.page_gather" not in scopes
    sched = DecodeScheduler(kernel_fns, params, slots, max_seq)
    try:
        assert sched.stats()["decode_attention"] == "paged_kernel"
    finally:
        sched.close()


def test_stats_name_the_fallback_decode_attention(fns, params):
    """At MAX_SEQ = 64 no kernel block fits: the gather, then dense."""
    assert fns["decode_attention"] == "gather_dense"
    sched = DecodeScheduler(fns, params, 2, MAX_SEQ)
    try:
        assert sched.stats()["decode_attention"] == "gather_dense"
    finally:
        sched.close()


# -- a streamed token's wait from the loop to the wire ----------------------

HANDOFF_FAMILIES = ("tpu_frontend_token_handoff_seconds_total",
                    "tpu_frontend_token_handoffs_total")


@pytest.fixture(scope="module")
def served_llama():
    """A generation model behind both frontends of one core."""
    from tpuserver.core import InferenceServer
    from tpuserver.grpc_frontend import GrpcFrontend
    from tpuserver.http_frontend import HttpFrontend
    from tpuserver.models.llama_serving import LlamaGenerateModel

    core = InferenceServer([LlamaGenerateModel(
        cfg=llama.tiny(vocab=512), max_seq=MAX_SEQ, max_slots=2)])
    frontends = {"grpc": GrpcFrontend(core, port=0).start(),
                 "http": HttpFrontend(core, port=0).start()}
    try:
        yield core, {kind: "127.0.0.1:{}".format(fe.port)
                     for kind, fe in frontends.items()}
    finally:
        for fe in frontends.values():
            fe.stop()
        core.close()


def _handoffs(core):
    """(seconds, count) of the model's handed-off tokens so far."""
    families = parse_prometheus_text(core.metrics_text())
    return tuple(
        next((value for _, labels, value in families[name]["samples"]
              if labels == {"model": "llama_generate"}), 0.0)
        for name in HANDOFF_FAMILIES)


def _stream_tokens(kind, url, n):
    """The tokens of one ``n``-token generation streamed over ``kind``
    (gRPC ``ModelStreamInfer`` or HTTP ``/generate_stream``)."""
    prompt = _prompt(5)
    if kind == "grpc":
        import tritonclient.grpc as grpcclient

        p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)], "INT32")
        p_in.set_data_from_numpy(prompt)
        m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        m_in.set_data_from_numpy(np.array([n], np.int32))
        client = grpcclient.InferenceServerClient(url)
        try:
            return [int(r.as_numpy("TOKEN")[0]) for r in
                    client.generate_stream("llama_generate", [p_in, m_in])]
        finally:
            client.close()
    import tritonclient.http as httpclient

    client = httpclient.InferenceServerClient(url)
    try:
        return [int(out["data"][0])
                for event in client.generate_stream(
                    "llama_generate",
                    {"PROMPT_IDS": prompt,
                     "MAX_TOKENS": np.array([n], np.int32)})
                for out in event.get("outputs", [])
                if out["name"] == "TOKEN"]
    finally:
        client.close()


@pytest.mark.parametrize("kind", ["grpc", "http"])
def test_every_streamed_token_counts_one_handoff(served_llama, kind):
    """One hand-off a token streamed, over ``ModelStreamInfer`` and over
    ``/generate_stream``; their summed wait is > 0 and below the
    stream's own wall time."""
    core, urls = served_llama
    _stream_tokens(kind, urls[kind], 2)     # compiles: not measured
    seconds0, count0 = _handoffs(core)
    began = time.monotonic()
    tokens = _stream_tokens(kind, urls[kind], 10)
    wall = time.monotonic() - began
    seconds, count = _handoffs(core)
    assert len(tokens) == 10
    assert count - count0 == len(tokens)
    assert 0.0 < seconds - seconds0 < wall


def test_handoff_is_not_counted_off_the_wire(served_llama):
    """A stream read inside the process never reaches a transport: the
    core stamps its responses and counts none of them."""
    from tpuserver.core import InferRequest

    core, _ = served_llama
    before = _handoffs(core)
    responses = list(core.infer_stream(InferRequest(
        "llama_generate", inputs={
            "PROMPT_IDS": _prompt(6),
            "MAX_TOKENS": np.array([4], np.int32)})))
    assert len(responses) == 4
    assert all(r.emitted_at is not None for r in responses)
    assert _handoffs(core) == before


def test_new_families_render_under_their_catalog_names(served_llama):
    """The off-CPU family (a sample a phase) and the two hand-off
    families render as the counters ``CATALOG`` declares."""
    from tpuserver.metrics import CATALOG

    core, urls = served_llama
    _stream_tokens("grpc", urls["grpc"], 3)
    families = parse_prometheus_text(core.metrics_text())
    offcpu = "tpu_scheduler_loop_offcpu_seconds_total"
    for name in (offcpu,) + HANDOFF_FAMILIES:
        assert CATALOG[name][0] == "counter"
        assert families[name]["type"] == "counter"
        assert families[name]["help"] == CATALOG[name][1]
    assert {labels["phase"] for _, labels, _ in families[offcpu]["samples"]
            if labels["model"] == "llama_generate"} == set(LOOP_PHASES)
    for name in HANDOFF_FAMILIES:
        assert [labels for _, labels, _ in families[name]["samples"]] == [
            {"model": "llama_generate"}]
