"""The AFMoE family's cell on the CPU at its dry_run sizes (window 32, so
the 128-token prompts cross it; 4 held experts of a 16-way router), and
the family's work counts."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT
from test_run import BROKEN_TOKEN, KEYS, run_cell

import reference_afmoe
import roofline_afmoe
from models import afmoe_generate

CELL = "trinity.mixed_ctx_c32"
COUNTERS = ("moe_pairs_per_layer_step", "moe_experts_hit_share",
            "window_tokens_skipped_share")


@pytest.mark.parametrize("trace", [1, 0])
def test_dry_run_of_the_new_cell(trace):
    p, result = run_cell(["--workload", CELL, "--seed", str(2**31 + 11),
                          "--seconds", "4", "--trace", str(trace), "--dry-run"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(result) == KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    if trace == 0:
        assert set(result["metrics"]) == {"tok_per_s", "setup_s"}
    else:
        # the program's new counters are read; the device metrics are not
        assert set(COUNTERS) <= set(result["metrics"])
        assert 0 < result["metrics"]["window_tokens_skipped_share"]["value"] < 100
        assert result["metrics"]["moe_pairs_per_layer_step"]["value"] > 0
        assert "moe_experts_roofline" not in result["metrics"]


def test_a_broken_timed_path_is_not_correct():
    p, result = run_cell(["--workload", CELL, "--seed", "77", "--seconds", "4",
                          "--trace", "0", "--dry-run"],
                         code=BROKEN_TOKEN.format(bench=BENCH))
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    value, limit = result["compared"]["logit_gap_max"]
    assert value > limit


def sizes():
    config = json.load(open(os.path.join(
        BENCH, "configs", "trinity-large-preview-ep8-l5.json")))
    return config, afmoe_generate.sizes_of(config, config["repository"][0])


def test_the_configuration_is_the_published_one_cut_as_stated():
    config, s = sizes()
    assert s["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert s["ffn_types"] == ["dense"] + ["moe"] * 4
    assert (s["hidden_size"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"]) == (3072, 48, 8, 128)
    assert (s["intermediate_size"], s["moe_intermediate_size"],
            s["router_experts"], s["num_experts_per_tok"],
            s["sliding_window"]) == (12288, 3072, 256, 4, 4096)
    assert len(config["layer_types"]) == 60     # the published list, whole
    # 8.64 GB of bf16 weights, as the configuration file states
    held = (roofline_afmoe.shared_params(s) + s["vocab_size"] * s["hidden_size"]
            + 4 * 32 * roofline_afmoe.expert_params(s))
    assert abs(held * 2 / 1e9 - 8.64) < 0.02


def test_work_counts_the_experts_that_were_hit_and_the_window():
    _, s = sizes()
    contexts = [100, 5000, 8700]
    # window layers read their window, the full layer everything
    assert roofline_afmoe.attended(s, contexts) == (
        sum(contexts) + 4 * (100 + 4096 + 4096))
    few = roofline_afmoe.decode_step_work(s, contexts, 1, 6.0, 5.0)
    all_held = roofline_afmoe.decode_step_work(s, contexts, 1, 6.0, 4 * 32)
    one = roofline_afmoe.expert_params(s) * 2
    assert all_held[1] - few[1] == (4 * 32 - 5) * one
    assert few[0] == all_held[0]        # FLOPs follow the pairs


def test_prefill_work_stops_at_the_window_and_at_the_held_experts():
    _, s = sizes()
    w = s["sliding_window"]
    # inside the window every layer is causal; past it a window layer
    # adds ``window`` keys a query, the full layer all of them
    assert roofline_afmoe.prefill_keys(s, 1024) == 5 * 1024 * 1025 // 2
    assert (roofline_afmoe.prefill_keys(s, 8192)
            == 8192 * 8193 // 2 + 4 * (w * (w + 1) // 2 + (8192 - w) * w))
    for tokens in (16, 1024, 8192):
        pairs, read = roofline_afmoe.prefill_routed(s, tokens)
        assert pairs == 4 * tokens / 2 and read <= min(pairs, 4 * 32)
    flops, nbytes = roofline_afmoe.prefill_work(s, 8192)
    dense = 2 * (roofline_afmoe.shared_params(s)
                 - s["hidden_size"] * s["vocab_size"]) * 8192
    assert dense < flops < 1.5 * dense
    assert nbytes < 8.64e9 + 5 * 8192 * 4096 + 2 * 2 * 16384 * 3072 + 1


def test_the_balancing_rule_levels_a_skewed_router():
    import jax
    import jax.numpy as jnp
    import numpy as np

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    skew = jax.random.normal(k1, (64,))

    def scores(key):
        return jax.nn.sigmoid(skew + 0.1 * jax.random.normal(key, (2048, 64)))

    def load(s, b):
        _, chosen = jax.lax.top_k(s + b, 4)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=64)

    bias = reference_afmoe.balance(scores(k2), 4)
    assert load(scores(k2), jnp.zeros(64)).max() == 2048    # 4 experts take all
    assert load(scores(k2), bias).max() <= 1.05 * 128
    fresh = load(scores(k3), bias)                  # other tokens, same rule
    assert fresh.min() > 0 and fresh.max() < 2 * 128


def test_flips_diagnostic_runs_dry():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "flips_afmoe.py"), "--dry-run",
         "--seeds", "5", "--rows", "1", "--tokens", "256"],
        capture_output=True, text=True, cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    row = json.loads(p.stdout.split("FLIPS ", 1)[1])
    assert len(row["differ_by_layer"]) == 4
    assert row["gap_all"]["n"] == 256
    # with the reference's choices forced, what is left is the rounding
    assert row["gap_forced_to_reference"]["mean"] <= row["gap_all"]["mean"]
    assert 0.15 < row["held_share_of_choices"] < 0.35


def fake_ctx(deltas):
    def snap(values):
        return {"metrics": {(k, (("model", "afmoe_generate"),)): v
                            for k, v in values.items()}}
    config, _ = sizes()
    return types.SimpleNamespace(
        config=config, counters_t0=snap(dict.fromkeys(deltas, 0.0)),
        counters_t1=snap(deltas))


def test_work_of_the_prefill_scopes_follows_the_traced_prompts():
    config, s = sizes()
    entry = config["repository"][0]
    runs = [types.SimpleNamespace(op_dims=lambda op, n=n: [48, n, 128])
            for n in (1024, 8192, 1024)]
    ctx = types.SimpleNamespace(config=config)
    for scope, fn in (("prefill", roofline_afmoe.prefill_work),
                      ("flash_prefill", roofline_afmoe.flash_prefill_work)):
        flops, nbytes = afmoe_generate.work(ctx, entry, scope, runs)
        assert flops == 2 * fn(s, 1024)[0] + fn(s, 8192)[0]
        assert nbytes == 2 * fn(s, 1024)[1] + fn(s, 8192)[1]
    # a prefill whose kernel the trace did not keep: nothing to read
    lost = runs + [types.SimpleNamespace(op_dims=lambda op: [])]
    assert afmoe_generate.work(ctx, entry, "prefill", lost) is None


def test_work_scales_the_counters_to_the_traced_steps():
    config, s = sizes()
    entry = config["repository"][0]
    ctx = fake_ctx({"tpu_moe_layer_steps_total": 4000.0,
                    "tpu_moe_local_pairs_total": 64000.0,
                    "tpu_moe_experts_hit_total": 50000.0})
    pairs, read = afmoe_generate.routed(ctx, entry, s, 10)
    assert (pairs, read) == (16.0 * 40, 12.5 * 40)
    assert read <= 32 * 40              # never more than are held
    # the parent's program has no such counters: nothing to read
    assert afmoe_generate.routed(fake_ctx({}), entry, s, 10) is None
