"""The DeepSeek-V3 family's cell on the CPU at its dry_run sizes (hidden
64, 8 heads of 16 + 8 / 16 over a latent of 32, 4 held experts of a
16-way router in 4 groups), the configuration as stated, and the
family's work counts."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT
from test_run import BROKEN_TOKEN, ENV, KEYS, run_cell

import reference_deepseek
import roofline_deepseek
from models import deepseek_generate

CELL = "dsv3.reasoning_ctx_c32"
NEW = ("latent_rows_read_per_step", "latent_cache_bytes_per_row",
       "moe_held_experts_hit_share.h16")


@pytest.mark.parametrize("trace", [1, 0])
def test_dry_run_of_the_new_cell(trace):
    p, result = run_cell(["--workload", CELL, "--seed", str(2**31 + 11),
                          "--seconds", "4", "--trace", str(trace), "--dry-run"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(result) == KEYS and list(result)[-1] == "compared"
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert result["correct"] is True and result["failed"] == 0
    if trace == 0:
        assert set(result["metrics"]) == {"tok_per_s", "setup_s"}
        return
    # the program's counters are read; the device metrics are not
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(metrics)
    # a dry-run row: 40 values in 128 lanes of float32, as the pool
    # stores it (the chip's: 576 values in 640 lanes of bf16, 1,280 B)
    assert metrics["latent_cache_bytes_per_row"] == 512.0
    assert metrics["latent_rows_read_per_step"] > 3 * 64
    assert 0 < metrics["moe_held_experts_hit_share.h16"] <= 100
    assert metrics["moe_pairs_per_layer_step"] > 0
    for name in ("decode_attention_roofline", "flash_prefill_roofline.gen",
                 "moe_experts_roofline", "moe_experts_hit_share"):
        assert name not in metrics


def test_readings_catch_the_int8_control_and_an_altered_token():
    """``readings.py`` at the dry-run size: the program reads correct,
    the int8 control fails the MEAN gap and an altered token the WIDEST,
    by the run's own verdict."""
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "readings.py"),
         "--workload", CELL, "--seeds", "77", "--seconds", "4", "--faults",
         "altered_token", "--dry-run"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    row = json.loads(next(line for line in p.stdout.splitlines()
                          if line.startswith("READING "))[8:])
    assert row["correct"] is True
    low, high = row["broken"]["int8"]["compared"]["logit_gap_mean"]
    assert low > high
    low, high = row["broken"]["altered_token"]["compared"]["logit_gap_max"]
    assert low > high


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
        BENCH, "configs", "deepseek-v3-ep16-l5.json")))
    return config, deepseek_generate.sizes_of(config, config["repository"][0])


def test_the_configuration_is_the_published_one_cut_as_stated():
    config, s = sizes()
    assert s["ffn_types"] == ["dense"] + ["moe"] * 4
    assert (s["hidden_size"], s["num_attention_heads"], s["q_lora_rank"],
            s["kv_lora_rank"], s["qk_nope_head_dim"], s["qk_rope_head_dim"],
            s["v_head_dim"]) == (7168, 128, 1536, 512, 128, 64, 128)
    assert (s["intermediate_size"], s["moe_intermediate_size"],
            s["router_experts"], s["n_group"], s["topk_group"],
            s["num_experts_per_tok"], s["routed_scaling_factor"]) == (
                18432, 2048, 256, 8, 4, 8, 2.5)
    assert (s["rope_factor"], s["rope_orig_max"], s["beta_fast"],
            s["beta_slow"], s["mscale_all_dim"]) == (40, 4096, 32, 1, 1)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "deepseek-v3-ep16-l5")
    cell = next(w for w in manifest["workloads"]
                if w["config"] == entry["name"])
    for line in (entry["why"], entry["source"], cell["why"]):
        # the driver refuses a line of more than 200 characters, a
        # configuration's too (manifest.validate checks a cell's alone)
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    assert sorted(entry["reduced"]) == sorted(config["published"])
    assert all(config[k] != config["published"][k] for k in entry["reduced"])
    assert config["published"]["vocab_size"] == 8 * s["vocab_size"]
    assert config["deployment"]["chips_per_layer"] * s["n_routed_experts"] == 256
    # 9.13 GB of bf16 weights and a 2.10 GB pool, as the file states
    held = (roofline_deepseek.shared_params(s)
            + s["vocab_size"] * s["hidden_size"]
            + 4 * 16 * roofline_deepseek.expert_params(s))
    assert abs(held * 2 / 1e9 - 9.13) < 0.01
    assert roofline_deepseek.attention_params(s) == 187_105_280
    entry = config["repository"][0]
    assert entry["kv_pages"] * entry["page_size"] == 32 * entry["max_seq"]


def test_work_counts_the_latent_row_once_and_the_experts_hit():
    _, s = sizes()
    # per cached token a layer: 2 x 128 x (576 + 512) FLOPs on 1,152 B
    flops, nbytes = roofline_deepseek.decode_attention_work(s, [1000, 24])
    assert (flops, nbytes) == (5 * 1024 * 278528, 5 * 1024 * 1152)
    assert flops / nbytes == pytest.approx(241.8, abs=0.1)   # the v5e's ridge
    contexts = [9000, 5000, 8700]
    few = roofline_deepseek.decode_step_work(s, contexts, 1, 6.0, 5.0)
    all_held = roofline_deepseek.decode_step_work(s, contexts, 1, 6.0, 4 * 16)
    one = roofline_deepseek.expert_params(s) * 2
    assert all_held[1] - few[1] == (4 * 16 - 5) * one
    assert few[0] == all_held[0]        # FLOPs follow the pairs
    # the absorbed products are 2 FLOPs a parameter of the up-projections
    assert few[0] > 2 * 3 * roofline_deepseek.shared_params(s)


def test_prefill_work_is_the_expanded_form():
    _, s = sizes()
    flops, nbytes = roofline_deepseek.flash_prefill_work(s, 8192)
    assert flops == 5 * (8192 * 8193 // 2) * 128 * 2 * (192 + 128)
    assert nbytes == 5 * 8192 * 128 * (192 + 192 + 128 + 128) * 2
    for tokens in (64, 4096, 8192):
        pairs, read = roofline_deepseek.prefill_routed(
            dict(s, num_experts=s["n_routed_experts"]), tokens)
        assert pairs == 4 * tokens / 2 and read <= min(pairs, 4 * 16)
    flops, nbytes = roofline_deepseek.prefill_work(s, 8192)
    dense = 2 * (roofline_deepseek.shared_params(s)
                 - s["hidden_size"] * s["vocab_size"]) * 8192
    assert dense < flops < 2.0 * dense
    assert nbytes < 9.13e9 + 5 * 8192 * 1152 + 2 * 2 * 16384 * 7168 + 1


def test_the_balancing_rule_levels_a_skewed_grouped_router():
    import jax
    import jax.numpy as jnp
    import numpy as np

    s = {"top_k": 8, "n_group": 8, "topk_group": 4}
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    skew = jax.random.normal(k1, (64,))

    def scores(key):
        return jax.nn.sigmoid(skew + 0.1 * jax.random.normal(key, (2048, 64)))

    def load(sc, b):
        chosen = reference_deepseek.choose(sc + b, s)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=64)

    bias = reference_deepseek.balance(scores(k2), s)
    raw = load(scores(k2), jnp.zeros(64))
    assert raw.max() > 1500 and (raw == 0).sum() > 32   # 4 groups take all
    assert load(scores(k2), bias).max() <= 1.1 * 256
    fresh = load(scores(k3), bias)                  # other tokens, same rule
    assert fresh.min() > 0 and fresh.max() < 2 * 256


def test_work_of_the_prefill_scopes_follows_the_traced_prompts():
    config, s = sizes()
    entry = config["repository"][0]
    runs = [types.SimpleNamespace(op_dims=lambda op, n=n: [128, n, 128])
            for n in (4096, 8192, 4096)]
    ctx = types.SimpleNamespace(config=config)
    for scope, fn in (("prefill", roofline_deepseek.prefill_work),
                      ("flash_prefill", roofline_deepseek.flash_prefill_work)):
        flops, nbytes = deepseek_generate.work(ctx, entry, scope, runs)
        assert flops == 2 * fn(s, 4096)[0] + fn(s, 8192)[0]
        assert nbytes == 2 * fn(s, 4096)[1] + fn(s, 8192)[1]
    lost = runs + [types.SimpleNamespace(op_dims=lambda op: [])]
    assert deepseek_generate.work(ctx, entry, "prefill", lost) is None


def test_the_builder_serves_the_family_as_data():
    """No model's name in the program: the builder hands
    ``LlamaGenerateModel`` a configuration whose block is data."""
    config, s = sizes()
    dry = dict(config, **config["dry_run"])
    model = deepseek_generate.build(dry, dry["repository"][0])
    cfg = model._cfg
    assert cfg.mla is not None and not cfg.plain
    assert (cfg.mla.width, cfg.mla.row) == (40, 128)
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.first,
            cfg.moe.n_group, cfg.moe.topk_group) == (16, 4, 4, 4, 2)
    assert cfg.ffn_types == ("dense", "moe", "moe", "moe", "moe")
    real = deepseek_generate.build(config, config["repository"][0])._cfg
    assert (real.mla.width, real.mla.row) == (576, 640)
    assert real.moe.held == 16 and real.moe.n_experts == 256
