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
