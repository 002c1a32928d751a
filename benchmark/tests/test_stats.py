import types

import stats


def rec(t_send, times, t_free=None):
    return types.SimpleNamespace(t_send=t_send, token_times=times,
                                 t_free=t_send if t_free is None else t_free)


def test_percentile_interpolates_and_is_none_without_samples():
    assert stats.percentile([], 50) is None
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 99) == 99
    assert stats.percentile([5], 90) == 5


def test_rate_counts_every_token_in_the_window_cut_streams_included():
    steady = [rec(0.0, [0.1 * k for k in range(1, 200)])]   # runs past t1
    assert stats.token_rate(steady, 5.0, 10.0) == 10.0
    stalled = [rec(0.0, [t for t in steady[0].token_times
                         if not 6.0 <= t < 8.0])]
    assert stats.token_rate(stalled, 5.0, 10.0) == 6.0      # the stall shows


def test_gaps_and_ttft_are_attributed_to_the_window_they_end_in():
    r = [rec(4.0, [4.9, 5.1, 5.2, 9.9, 10.1])]
    gaps = stats.gaps_ms(r, 5.0, 10.0)
    assert [round(g) for g in gaps] == [200, 100, 4700]
    assert stats.ttfts_ms(r, 5.0, 10.0) == []               # first token before
    assert [round(x) for x in stats.ttfts_ms([rec(5.0, [5.25])], 5, 10)] == [250]
    assert [round(x, 3) for x in stats.late_ms([rec(6.0, [], 5.998)], 5, 10)] == [2.0]


def test_histogram_line():
    assert stats.histogram([1, 12, 12, 99], (0, 10, 50)) == "0:1 10:2 50:1"


def test_the_window_waits_for_a_process_that_compiled_and_for_no_other():
    import run

    log = run.CompileLog.__new__(run.CompileLog)    # no listener registered
    log.events, log.compiled, log._read = [], [], set()
    read, compiled = ("/jax/compilation_cache/cache_retrieval_time_sec",
                      "/jax/core/compile/backend_compile_duration")
    log._on(read, 3.0)
    log._on(compiled, 3.1)      # the read's own closing event: no compile
    log._on(compiled, 0.2)      # a short compile
    log._on("/jax/core/compile/jaxpr_trace_duration", 9.0)
    assert len(log.events) == 3 and log.compiled[0][1] == 0.2
    assert log.settled_at(15.0) == 0.0      # all from the cache, or short
    log._on(compiled, 6.0)
    t_long = log.compiled[-1][0]
    log._on(compiled, 0.1)
    assert log.settled_at(15.0) == t_long + 15.0    # past the last LONG one
    assert log.settled_at(0.0) == t_long            # a cell that asks for none


def test_counter_ratio_reads_window_deltas_of_the_traffics_model():
    import types

    import counters
    from readers import counter_ratio

    text = """# HELP tpu_scheduler_tokens_total tokens
tpu_scheduler_tokens_total{model="m"} %d
tpu_scheduler_step_seconds_count{model="m"} %d
tpu_scheduler_tokens_total{model="other"} 7
"""
    def snap(tokens, steps):
        return {"metrics": counters.parse_exposition(text % (tokens, steps))}
    ctx = types.SimpleNamespace(traffic={"model": "m"},
                                counters_t0=snap(100, 10),
                                counters_t1=snap(900, 60))
    rows = {"numerator": {"metric": "tpu_scheduler_tokens_total"},
            "denominator": {"metric": "tpu_scheduler_step_seconds_count"},
            "scale": 1.0}
    assert counter_ratio.read(ctx, rows) == 16.0
    # a counter the program does not have, or a window with no events:
    # nothing to read, never 0
    absent = dict(rows, numerator={"metric": "tpu_no_such_total"})
    assert counter_ratio.read(ctx, absent) is None
    ctx.counters_t1 = ctx.counters_t0
    assert counter_ratio.read(ctx, rows) is None
