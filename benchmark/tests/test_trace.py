"""The trace reducer against a small recorded trace: 0.45 s of
``mistral7b.long_prompt_c8`` on one TPU v5 lite (this PR's first chip
call), cut from the profiler's file by ``trace.extract``."""

import os

import pytest

import roofline
import trace
from models import llama_generate

from conftest import BENCH

SYNC_AT = 100.0     # the perf_counter reading the sync span stands for


@pytest.fixture(scope="module")
def t():
    raw = trace.load_raw(os.path.join(BENCH, "tests", "data",
                                      "trace_small.json.gz"))
    return trace.TraceData(raw, SYNC_AT, llama_generate.TRACE_LABELS)


def test_nameless_executables_are_told_apart_by_their_kernel(t):
    assert {r.module for r in t.runs_of("decode_step")} == {"_unknown"}
    assert {r.module for r in t.runs_of("prefill")} == {"_unknown"}
    assert len(t.runs_of("decode_step")) == 8
    assert len(t.runs_of("prefill")) == 1
    assert len(t.runs_of("paged_admit")) == 1


def test_busy_idle_and_window(t):
    assert abs(t.window_s - 0.44995) < 1e-4
    assert 0.9998 < t.busy_s / t.window_s <= 1.0
    lo, hi = t.interval()
    assert abs((hi - lo) - t.window_s) < 1e-9
    assert SYNC_AT + 0.9 < lo < SYNC_AT + 1.1   # the cut starts 1 s in
    assert all(d < 1e-4 for _, d in t.idle_gaps())


def test_per_executable_and_per_kernel_time(t):
    prefill, = t.runs_of("prefill")
    assert abs(prefill.dur - 0.11619) < 1e-4
    assert llama_generate.prompt_tokens([prefill]) == [2048]
    assert abs(prefill.op_seconds("flash_attention") - 0.013648) < 1e-5
    steps = t.runs_of("decode_step")
    assert all(0.040 < r.dur < 0.0425 for r in steps)
    assert abs(sum(r.op_seconds("decode_attention") for r in steps)
               - 0.10902) < 1e-4
    assert steps[0].op_dims("decode_attention") == [16, 32, 128]


def test_breakdown_lists_at_most_ten_of_each(t):
    b = trace.breakdown(t)
    assert b["device_ops"][0][0] == "decode_step/decode_attention_bf16_16_32_128"
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(s, float) and s > 0 for _, s in b["device_ops"])
    assert all(" " not in name and "," not in name for name, _ in b["device_ops"])


def test_operation_text_is_reduced_to_name_and_type():
    text = ("%slice_bitcast_fusion.38.remat = bf16[2560,16,8,128]{3,2,1,0:T(8,128)"
            "(2,1)} fusion(bf16[20,2,2560,16,8,128]{5,4,3,2,1,0} %p)")
    assert trace.op_base(text) == "slice_bitcast_fusion"
    assert trace.op_shape(text) == "bf16[2560,16,8,128]"
    assert trace.shape_dims("bf16[32,2048,128]") == [32, 2048, 128]
    assert trace.op_shape("%copy-start.3 = (bf16[64]{0}, u32[]) copy-start(x)") == "bf16[64]"


def test_a_trace_with_no_device_plane_reads_nothing():
    empty = trace.TraceData({"devices": {}, "host": []}, SYNC_AT,
                            llama_generate.TRACE_LABELS)
    assert empty.busy_s == 0.0 and empty.interval() is None
    assert empty.runs_of("decode_step") == [] and empty.idle_gaps() == []


def test_a_scope_is_found_through_the_repositorys_builder(t):
    """``devicework`` knows no model family: the scope, its executable and
    the work the algorithm needs come from ``models/<builder>.py`` of the
    repository entry."""
    import types

    import devicework
    import manifest
    from kinds import generate

    config = manifest.load_json(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3-l20.json"))
    lo, hi = t.interval()
    rec = generate.Record(0, 0, False, 1024, 64,
                          token_times=[lo + 0.01 * k for k in range(8)])
    ctx = types.SimpleNamespace(
        config=config, trace_data=t,
        builders={"llama_generate": llama_generate},
        kind=types.SimpleNamespace(records=lambda ctx: [rec]))
    assert devicework.labels(ctx) == llama_generate.TRACE_LABELS
    assert devicework.owner(ctx, "resnet") is None
    assert devicework.runs_of(ctx, "resnet") == []
    assert devicework.work(ctx, "resnet") is None
    prefill, = devicework.runs_of(ctx, "prefill")
    assert devicework.prompt_tokens(ctx, "prefill", [prefill]) == [2048]
    sizes = llama_generate.sizes_of(config, config["repository"][0])
    flops, nbytes, seconds = devicework.work(ctx, "prefill")
    assert (flops, nbytes) == roofline.prefill_work(sizes, 2048)
    assert abs(seconds - 0.11619) < 1e-4
    flops, nbytes, seconds = devicework.work(ctx, "flash_prefill")
    assert (flops, nbytes) == roofline.flash_prefill_work(sizes, 2048)
    assert abs(seconds - 0.013648) < 1e-5
    flops, nbytes, seconds = devicework.work(ctx, "decode_step")
    contexts = [1025 + k for k in range(8)]
    step_flops, step_bytes = roofline.decode_step_work(sizes, contexts)
    assert flops == step_flops
    assert nbytes == step_bytes + 7 * 2 * roofline.matmul_params(sizes)
    attn = devicework.work(ctx, "decode_attention")
    assert attn[:2] == roofline.decode_attention_work(sizes, contexts)
    assert abs(attn[2] - 0.10902) < 1e-4
