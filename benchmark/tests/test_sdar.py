"""The SDAR family's cell on the CPU at its dry_run sizes, its
configuration against the catalog, its schedule, its work counts and what
``correct`` compares for a block stream."""

import dataclasses
import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH
from test_run import KEYS, run_cell

import reference_sdar
import roofline_sdar
import traffic
from kinds import generate_blocks
from models import sdar_generate

CELL = "sdar.block_diffusion_c32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MIX = json.load(open(os.path.join(BENCH, "traffic",
                                  "block-diffusion-c32.json")))
COUNTERS = ("diffusion_rows_per_step", "diffusion_tokens_per_row_pass",
            "diffusion_commit_pass_share", "moe_pairs_per_layer_step")


def config_and_sizes():
    config = json.load(open(os.path.join(
        BENCH, "configs", "sdar-30b-a3b-chat-l6.json")))
    return config, sdar_generate.sizes_of(config, config["repository"][0])


@pytest.mark.parametrize("trace", [1, 0])
def test_dry_run_of_the_new_cell(trace):
    p, result = run_cell(["--workload", CELL, "--seed", str(2**31 + 11),
                          "--seconds", "4", "--trace", str(trace), "--dry-run"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(result) == KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert {"logit_gap_mean", "logit_gap_max", "confidence_gap_mean",
            "confidence_gap_max", "unmask_count_wrong", "finished_short",
            "tokens_out_of_range", "requests_compared"} <= set(
                result["compared"])
    if trace == 0:
        assert set(result["metrics"]) == {"tok_per_s", "setup_s"}
        return
    # the program's new counters are read; the device metrics are not
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(COUNTERS) <= set(metrics)
    assert 0.8 <= metrics["diffusion_tokens_per_row_pass"] <= 1.34
    assert 10 < metrics["diffusion_commit_pass_share"] <= 34
    assert 0 < metrics["diffusion_rows_per_step"] <= 4
    assert "decode_step_roofline" not in metrics


BROKEN_BLOCK = """
import sys
sys.path.insert(0, {bench!r})
import run
from tpuserver.models import llama_serving
sound = llama_serving.LlamaGenerateModel._execute_scheduled
def altered(self, *a, **k):
    for n, event in enumerate(sound(self, *a, **k)):
        if n == {at}:      # {what}
            event = dict(event, {name}=event[{name!r}] {op})
        yield event
llama_serving.LlamaGenerateModel._execute_scheduled = altered
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("name,op,number", [
    ("TOKEN", "* 0 + (event['TOKEN'] + 977) % 2047", "logit_gap_max"),
    ("UNMASK_PASS", "* 0", "unmask_count_wrong")])
def test_a_broken_block_stream_is_not_correct(name, op, number):
    """A token altered where it is produced, or a block that claims all
    its positions for pass 0, in every request's first block."""
    p, result = run_cell(
        ["--workload", CELL, "--seed", "77", "--seconds", "4", "--trace", "0",
         "--dry-run"], code=BROKEN_BLOCK.format(
            bench=BENCH, at=0, what=number, name=name, op=op))
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    value, limit = result["compared"][number]
    assert value > limit


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    config, s = config_and_sizes()
    assert (s["hidden_size"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"]) == (2048, 32, 4, 128)
    assert (s["moe_intermediate_size"], s["num_experts"],
            s["num_experts_per_tok"], s["vocab_size"],
            s["rope_theta"]) == (768, 128, 8, 151936, 1000000)
    assert (s["block_length"], s["mask_token_id"]) == (4, 151669)
    assert config["published"] == {"num_hidden_layers": 48}
    assert s["num_hidden_layers"] == 6
    for word in ("block length", "schedule", "mask token", "in-place logits",
                 "norm gains"):
        assert any(word in line for line in config["assumed"]), word
    entry = config["repository"][0]
    assert (entry["max_seq"], entry["max_slots"], entry["page_size"],
            entry["kv_pages"], entry["attn_impl"]) == (4608, 32, 16, 9216,
                                                       "pallas")
    # 8.72 GB of bf16 weights and a 1.81 GB pool, as the file states
    params = (roofline_sdar.shared_params(s) + s["vocab_size"] * s["hidden_size"]
              + 6 * 128 * roofline_sdar.expert_params(s))
    assert abs(params * 2 / 1e9 - 8.72) < 0.02
    pool = entry["kv_pages"] * entry["page_size"] * 6 * roofline_sdar.kv_token_bytes(s)
    assert abs(pool / 1e9 - 1.81) < 0.01


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalog_entry_stands_in_the_file():
    config, _ = config_and_sizes()
    entry = next(json.loads(line) for line in open(CATALOG)
                 if '"SDAR-30B-A3B-Chat"' in line)
    assert config["source"] == entry["source_url"]
    differ = {k for k, v in entry["config"].items() if config.get(k) != v}
    assert differ == {"num_hidden_layers"}
    manifest = json.load(open(os.path.join(os.path.dirname(BENCH),
                                           "BENCHMARK.json")))
    mine = next(c for c in manifest["configs"]
                if c["name"] == "sdar-30b-a3b-chat-l6")
    assert mine["reduced"] == ["num_hidden_layers"]
    assert mine["source"] == entry["source_url"]


def test_the_traffic_is_the_cells_own():
    """32 pinned callers; prompts 1,024 x 24 and 4,096 x 8; answers 256 /
    512 half each in every round; denoising_steps 4 and 2 for 16 callers
    each, 12 + 4 by prompt class; the same multiset whatever the seed."""
    assert MIX["kind"] == "generate_blocks" and MIX["clients"] == 32
    rounds = []
    for seed in (1, 2**31 + 11):
        sched, steps = generate_blocks.block_schedule(MIX, seed)
        classes = [({r.prompt_tokens for r in reqs},
                    {steps[(r.client, r.index)] for r in reqs})
                   for reqs in sched]
        assert all(len(p) == 1 and len(t) == 1 for p, t in classes)
        count = {}
        for p, t in classes:
            key = (min(p), min(t))
            count[key] = count.get(key, 0) + 1
        assert count == {(1024, 4): 12, (1024, 2): 12, (4096, 4): 4,
                         (4096, 2): 4}
        for reqs in sched:
            assert reqs[0].ramp and reqs[0].max_tokens % 4 == 0
            assert 16 <= reqs[0].max_tokens <= 128
            assert {r.max_tokens for r in reqs[1:]} == {256, 512}
        rounds.append([sorted((reqs[i].prompt_tokens, reqs[i].max_tokens)
                              for reqs in sched)
                       for i in range(1, MIX["rounds"] + 1)])
        for row in rounds[-1]:
            assert sorted(o for _, o in row) == [256] * 16 + [512] * 16
    assert rounds[0] == rounds[1] == [
        sorted(row) for row in traffic.request_pairs(MIX)]


def test_prompts_never_hold_the_mask_token():
    sizes = {"vocab_size": 8, "mask_token_id": 5}
    req = traffic.Request(0, 0, 4000, 4)
    ids = generate_blocks.prompt_ids(3, req, sizes)
    assert set(ids) == {0, 1, 2, 3, 4, 6, 7}


def block(positions, passes, tokens=None):
    return generate_blocks.Block(0.0, tokens or [7] * len(positions),
                                 [-1.0] * len(positions), positions, passes)


@pytest.mark.parametrize("steps,passes,wrong", [
    (4, [0, 1, 2, 3], 0), (4, [3, 0, 2, 1], 0), (2, [0, 0, 1, 1], 0),
    (3, [0, 0, 1, 2], 0), (1, [0, 0, 0, 0], 0),
    (4, [0, 0, 1, 2], 2),       # two in pass 0, none left for pass 3
    (2, [0, 1, 1, 1], 2), (3, [0, 1, 1, 2], 2), (1, [0, 0, 0, 1], 2)])
def test_unmask_count_follows_the_static_schedule(steps, passes, wrong):
    rec = types.SimpleNamespace(steps=steps,
                                blocks=[block([8, 9, 10, 11], passes)])
    assert generate_blocks.unmask_count_wrong([rec], 4) == wrong


def test_unmask_count_of_a_first_block_with_given_tokens_and_a_cut_one():
    rec = types.SimpleNamespace(steps=4, blocks=[
        block([6, 7], [0, 1]),          # two given: one a pass
        block([8, 9, 10, 11], [1, 0, 3, 2]),
        block([12, 13], [0, 0])])       # cut by max_tokens: not judged
    assert generate_blocks.unmask_count_wrong([rec], 4) == 0
    rec.blocks[0] = block([6, 7], [0, 0])
    assert generate_blocks.unmask_count_wrong([rec], 4) == 2


def test_work_counts_a_block_a_row_pass():
    _, s = config_and_sizes()
    contexts = [1028, 4100, 4608]
    flops, nbytes = roofline_sdar.decode_step_work(s, contexts, 1, 96.0, 60.0)
    positions = 3 * 4
    keys = sum(contexts) * 6
    assert flops == (2 * roofline_sdar.shared_params(s) * positions
                     + 4 * keys * 4 * 32 * 128
                     + 2 * roofline_sdar.expert_params(s) * 96)
    # K and V of the keys are read once a pass, not once a query
    a_flops, a_bytes = roofline_sdar.decode_attention_work(s, contexts)
    assert a_bytes == keys * 2048 and a_flops == 4 * keys * 4 * 32 * 128
    more = roofline_sdar.decode_step_work(s, contexts, 1, 96.0, 6 * 128)
    assert more[1] - nbytes == (6 * 128 - 60) * roofline_sdar.expert_params(s) * 2
    # a step of 32 rows streams every expert: 7.25 GB of the 8.1 it reads
    e_bytes = roofline_sdar.experts_work(s, 32 * 4 * 8 * 6, 6 * 128)[1]
    assert abs(e_bytes / 1e9 - 7.25) < 0.05


def test_prefill_work_is_block_causal_and_has_no_head():
    _, s = config_and_sizes()
    assert roofline_sdar.prefill_keys(s, 8) == 4 * 4 + 4 * 8
    assert roofline_sdar.prefill_keys(s, 1024) == 1024 * 1028 // 2
    flops, nbytes = roofline_sdar.prefill_work(s, 1024)
    # five whole layers and the sixth's K and V: never the head
    head = 2 * s["hidden_size"] * s["vocab_size"] * 1024
    whole = 2 * 1024 * 5 * (roofline_sdar.layer_shared_params(s)
                            + 8 * roofline_sdar.expert_params(s))
    assert whole < flops < whole * 1.1 and flops < whole + head
    assert nbytes < 5 * 1.25e9 + 1024 * 12288 + 5 * 2 * 2 * 8192 * 2048 + 1e7


def test_work_of_the_scopes_follows_the_trace_and_the_counters():
    config, s = config_and_sizes()
    entry = config["repository"][0]
    runs = [types.SimpleNamespace(op_dims=lambda op, n=n: [32, n, 128])
            for n in (1024, 4096)]
    ctx = types.SimpleNamespace(config=config)
    flops, nbytes = sdar_generate.work(ctx, entry, "prefill", runs)
    assert flops == sum(roofline_sdar.prefill_work(s, n)[0]
                        for n in (1024, 4096))
    lost = runs + [types.SimpleNamespace(op_dims=lambda op: [])]
    assert sdar_generate.work(ctx, entry, "flash_prefill", lost) is None

    def snap(values):
        return {"metrics": {(k, (("model", "sdar_generate"),)): v
                            for k, v in values.items()}}
    deltas = {"tpu_moe_layer_steps_total": 6000.0,
              "tpu_moe_local_pairs_total": 6000.0 * 1000,
              "tpu_moe_experts_hit_total": 6000.0 * 127}
    rec = types.SimpleNamespace(blocks=[
        generate_blocks.Block(5.0, [1] * 4, [0.0] * 4, [1024, 1025, 1026, 1027],
                              [0, 1, 0, 1]),
        generate_blocks.Block(50.0, [1] * 4, [0.0] * 4, [1028, 1029, 1030, 1031],
                              [0, 1, 2, 3])])
    ctx = types.SimpleNamespace(
        config=config, counters_t0=snap(dict.fromkeys(deltas, 0.0)),
        counters_t1=snap(deltas),
        trace_data=types.SimpleNamespace(interval=lambda: (0.0, 10.0)),
        kind=types.SimpleNamespace(records=lambda ctx: [rec]))
    # the block that arrived in the interval: two denoise passes and a
    # commit, each over start + B keys
    assert sdar_generate.pass_contexts(ctx, s) == [1028] * 3
    assert sdar_generate.routed(ctx, entry, s, 10) == (1000.0 * 60, 127.0 * 60)
    step = sdar_generate.work(ctx, entry, "decode_step", [None] * 10)
    assert step == roofline_sdar.decode_step_work(
        s, [1028] * 3, 10, 1000.0 * 60, 127.0 * 60)
    # the parent's program has no such counters: nothing to read
    ctx.counters_t0 = ctx.counters_t1 = snap({})
    assert sdar_generate.work(ctx, entry, "moe_experts", [None] * 10) is None


def test_a_program_without_the_block_step_is_refused_at_once(monkeypatch):
    """The parent commit's ``LlamaConfig`` has no ``block_len``: the
    builder says so (``run.py`` exits non-zero) before any weight is
    made."""
    from tpuserver.models import llama

    @dataclasses.dataclass(frozen=True)
    class Parent:
        vocab: int = 8

    monkeypatch.setattr(llama, "LlamaConfig", Parent)
    config, _ = config_and_sizes()
    with pytest.raises(RuntimeError, match="no block step"):
        sdar_generate.build(config, config["repository"][0])


def test_the_reference_replays_a_pass_as_a_forward_from_scratch():
    """``replay_logits``: the B positions of a replayed pass against the
    keys and values of one pass over the sequence, equal to a forward
    over sequence-so-far + block; and the int8 control reads otherwise."""
    import jax
    import jax.numpy as jnp

    import weights_sdar

    config, _ = config_and_sizes()
    dry = dict(config, **config["dry_run"])
    s = sdar_generate.sizes_of(dry, dry["repository"][0])
    shape = reference_sdar.shape_of(s)
    rng = np.random.default_rng(4)
    seq = rng.integers(0, 2047, (1, 24)).astype(np.int32)
    seen = np.array([[seq[0, 8:12], [2047, seq[0, 17], 2047, 2047]]])
    starts = np.array([[8, 16]])
    got = reference_sdar.replay_logits(11, s, seq, starts, seen)
    params = weights_sdar.weights(11, s, jnp.float32)
    with jax.default_matmul_precision("highest"):
        first = reference_sdar.forward(params, seq[0, :12],
                                       np.zeros(12, bool), shape)
        ids = np.concatenate([seq[0, :16], seen[0, 1]])
        second = reference_sdar.forward(params, ids, ids == 2047, shape)
    np.testing.assert_allclose(got[0, 0], np.asarray(first)[8:], atol=2e-4)
    np.testing.assert_allclose(got[0, 1], np.asarray(second)[16:], atol=2e-4)
    low = reference_sdar.replay_logits(11, s, seq, starts, seen, "int8")
    assert np.abs(low - got).mean() > 1e-3
