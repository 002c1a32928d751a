"""The LFM2-MoE family's cell on the CPU at its dry_run sizes (hidden
64, 4/2 heads of 16 in 128 lanes, 8 experts, 10 conv and 3 attention
layers), the configuration as stated, and the family's work counts."""

import json
import os
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT
from test_run import ENV, KEYS, run_cell

import roofline_lfm2
from models import lfm2_generate

CELL = "lfm2.short_chat_c32"


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
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # a dry-run row's windows: 10 conv layers x 2 rows x 64 float32
    # (the chip's: 2,048 bf16, 81,920 B); each request's last step, the
    # one-deep pipeline's wasted row, reads its windows too
    row = 10 * 2 * 64 * 4
    assert row <= metrics["conv_state_bytes_per_row"] < 1.2 * row
    # K and V of 2 heads in 128 lanes of float32 a token a layer
    assert metrics["latent_cache_bytes_per_row"] == 2 * 2 * 128 * 4
    assert metrics["moe_pairs_per_layer_step"] > 0
    # the device metrics are not read on the CPU
    for name in ("decode_attention_roofline", "moe_experts_roofline"):
        assert name not in metrics


def test_readings_catch_the_int8_control_a_shifted_window_and_an_altered_token():
    """``readings_conv.py`` at the dry-run size: the program reads
    correct; the int8 control, the reference with a window one position
    late and an altered token each read NOT correct, by the run's own
    verdict; the reference in bfloat16 is read beside them."""
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "readings_conv.py"),
         "--workload", CELL, "--seeds", "77", "--seconds", "4", "--faults",
         "altered_token", "--dry-run"],
        cwd=ROOT, env=ENV, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    row = json.loads(next(line for line in p.stdout.splitlines()
                          if line.startswith("READING "))[8:])
    assert row["correct"] is True
    assert set(row["broken"]) == {"int8", "shifted_window", "bf16",
                                  "altered_token"}
    for name, broken in row["broken"].items():
        # the bf16 reference is printed, not judged: at these toy widths
        # a bf16 rounding flips router near-ties (module docstring of
        # tests/test_lfm2.py)
        assert broken["correct"] is False or name == "bf16", name
    low, high = row["broken"]["shifted_window"]["compared"]["logit_gap_mean"]
    assert low > high


def sizes():
    config = json.load(open(os.path.join(
        BENCH, "configs", "lfm2-8b-a1b-pp2-l13.json")))
    return config, lfm2_generate.sizes_of(config, config["repository"][0])


def test_the_configuration_is_the_published_one_cut_as_stated():
    config, s = sizes()
    assert len(config["layer_types"]) == config["published"][
        "num_hidden_layers"] == 24
    assert s["layer_types"] == ["conv"] + ["full", "conv", "conv", "conv"] * 3
    assert s["ffn_types"] == ["dense"] + ["moe"] * 12
    assert (s["hidden_size"], s["num_attention_heads"],
            s["num_key_value_heads"], s["head_dim"], s["conv_L_cache"]) == (
                2048, 32, 8, 64, 3)
    assert (s["intermediate_size"], s["moe_intermediate_size"],
            s["num_experts"], s["num_experts_per_tok"],
            s["routed_scaling_factor"], s["vocab_size"]) == (
                7168, 1792, 32, 4, 1, 65536)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2-8b-a1b-pp2-l13")
    cell = next(w for w in manifest["workloads"]
                if w["config"] == entry["name"])
    assert cell["name"] == CELL and cell["chips"] == 1
    for line in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isprintable(), line
    assert sorted(entry["reduced"]) == sorted(config["published"])
    assert all(config[k] != config["published"][k] for k in entry["reduced"])
    # 9.21 GB of bf16 weights: every expert and the tied vocabulary here
    held = (roofline_lfm2.shared_params(s)
            + 12 * 32 * roofline_lfm2.expert_params(s))
    assert abs(held * 2 / 1e9 - 9.21) < 0.01
    repo = config["repository"][0]
    assert repo["kv_pages"] * repo["page_size"] == 32 * repo["max_seq"]


def test_work_counts_by_hand():
    _, s = sizes()
    assert roofline_lfm2.conv_params(s) == 16_783_360
    assert roofline_lfm2.attention_params(s) == 10_485_888
    assert roofline_lfm2.expert_params(s) == 11_010_048
    assert 3 * s["hidden_size"] * s["intermediate_size"] == 44_040_192
    # K and V of 8 heads of 64 at 2 B; 10 layers x 2 rows x 2,048 x 2 B
    assert roofline_lfm2.kv_token_bytes(s) == 2048
    assert roofline_lfm2.window_bytes(s) == 81_920
    flops, nbytes = roofline_lfm2.decode_attention_work(s, [1000, 24])
    assert (flops, nbytes) == (4 * 3 * 1024 * 32 * 64, 3 * 1024 * 2048)
    # 32 rows' windows of one step, read and written
    assert roofline_lfm2.window_traffic(s, 32) == 2 * 81_920 * 32
    few = roofline_lfm2.decode_step_work(s, [300] * 32, 1, 32 * 4 * 12, 100)
    every = roofline_lfm2.decode_step_work(s, [300] * 32, 1, 32 * 4 * 12,
                                           12 * 32)
    assert every[1] - few[1] == (12 * 32 - 100) * roofline_lfm2.expert_params(
        s) * 2
    assert few[0] == every[0]        # FLOPs follow the pairs
    # a step that reads every expert: ~9.1 GB, 92 % of it experts
    assert 8.9e9 < every[1] < 9.3e9


def test_work_of_the_scopes_follows_the_trace():
    config, s = sizes()
    entry = config["repository"][0]
    runs = [types.SimpleNamespace(op_dims=lambda op, n=n: [32, n, 64])
            for n in (256, 1024)]
    ctx = types.SimpleNamespace(config=config)
    for scope, fn in (("prefill", roofline_lfm2.prefill_work),
                      ("flash_prefill", roofline_lfm2.flash_prefill_work)):
        flops, nbytes = lfm2_generate.work(ctx, entry, scope, runs)
        assert flops == fn(s, 256)[0] + fn(s, 1024)[0]
        assert nbytes == fn(s, 256)[1] + fn(s, 1024)[1]


def test_the_builder_serves_the_family_as_data():
    """No model's name in the program: the builder hands
    ``LlamaGenerateModel`` a configuration whose block is data."""
    config, _ = sizes()
    dry = dict(config, **config["dry_run"])
    model = lfm2_generate.build(dry, dry["repository"][0])
    cfg = model._cfg
    assert cfg.conv_layers == (0, 2, 3, 4, 6, 7, 8, 10, 11, 12)
    assert cfg.attn_layers == (1, 5, 9)
    assert cfg.tie_embed and cfg.kv_width == 128 and cfg.head_dim == 16
    assert cfg.moe.route_eps == 1e-6 and cfg.moe.n_shared == 0
