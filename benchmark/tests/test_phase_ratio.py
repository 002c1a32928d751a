import types

import pytest

import counters
import manifest
from readers import phase_ratio

TEXT = """# HELP tpu_scheduler_loop_seconds_total seconds by phase
tpu_scheduler_loop_seconds_total{model="m",phase="idle"} %(idle)s
tpu_scheduler_loop_seconds_total{model="m",phase="sweep"} %(sweep)s
tpu_scheduler_loop_seconds_total{model="m",phase="admit"} %(admit)s
tpu_scheduler_loop_seconds_total{model="m",phase="dispatch"} %(dispatch)s
tpu_scheduler_loop_seconds_total{model="m",phase="fetch"} %(fetch)s
tpu_scheduler_loop_seconds_total{model="m",phase="deliver"} %(deliver)s
tpu_scheduler_loop_seconds_total{model="other",phase="sweep"} 99
tpu_scheduler_step_seconds_count{model="m"} %(steps)d
"""
T0 = dict(idle=5.0, sweep=1.0, admit=1.0, dispatch=2.0, fetch=10.0,
          deliver=1.0, steps=100)
T1 = dict(idle=5.5, sweep=1.25, admit=1.5, dispatch=3.0, fetch=17.0,
          deliver=2.25, steps=300)


def ctx_of(t0, t1):
    return types.SimpleNamespace(
        traffic={"model": "m"},
        counters_t0={"metrics": counters.parse_exposition(TEXT % t0)},
        counters_t1={"metrics": counters.parse_exposition(TEXT % t1)})


@pytest.mark.parametrize("metric, expected", [
    # host phases 0.25 + 0.5 + 1.0 + 1.25 = 3.0 s over 200 steps
    ("sched_host_ms_per_step", 15.0),
    # ... of 3.0 + 7.0 s of fetch; idle is left out
    ("sched_host_busy_share", 30.0),
])
def test_the_metric_files_sum_window_deltas_over_phases(metric, expected):
    spec, read = manifest.reader_of(metric)
    assert read is phase_ratio.read
    assert read(ctx_of(T0, T1), spec) == pytest.approx(expected)


def test_an_absent_sample_reads_none():
    spec, read = manifest.reader_of("sched_host_ms_per_step")
    # the parent commit: no loop seconds at all
    old = "tpu_scheduler_step_seconds_count{model=\"m\"} %d\n"
    ctx = types.SimpleNamespace(
        traffic={"model": "m"},
        counters_t0={"metrics": counters.parse_exposition(old % 1)},
        counters_t1={"metrics": counters.parse_exposition(old % 9)})
    assert read(ctx, spec) is None
    # one phase of the sum missing, or the plain sample
    ctx = ctx_of(T0, T1)
    term = dict(spec["numerator"], phases=["sweep", "no_such_phase"])
    assert read(ctx, dict(spec, numerator=term)) is None
    assert read(ctx, dict(spec, denominator={"metric": "tpu_no_such"})) is None


def test_a_zero_denominator_reads_none():
    ctx = ctx_of(T0, dict(T1, steps=T0["steps"]))   # a window without a step
    spec, read = manifest.reader_of("sched_host_ms_per_step")
    assert read(ctx, spec) is None
    spec, read = manifest.reader_of("sched_host_busy_share")
    assert read(ctx_of(T0, T0), spec) is None       # ... or without time
