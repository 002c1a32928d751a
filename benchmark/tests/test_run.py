"""The whole of a run, driven on the CPU at the configurations' dry_run
sizes: the result line's shape, the refusals, and `correct` coming out
false when the timed path is broken underneath."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def run_cell(args, code=None, cwd=ROOT):
    cmd = [sys.executable] + (["-c", code] if code else
                              [os.path.join("benchmark", "run.py")]) + args
    p = subprocess.run(cmd, cwd=cwd, env=ENV, capture_output=True, text=True,
                       timeout=600)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("cell,trace", [
    ("mistral7b.decode_c16", 1), ("mistral7b.decode_c16", 0),
    ("mistral7b.long_prompt_c8", 1), ("mistral7b.long_prompt_c8", 0)])
def test_dry_run_names_cpu_and_reports_no_device_metric(cell, trace):
    p, result = run_cell(["--workload", cell, "--seed", str(2**31 + 11),
                          "--seconds", "4", "--trace", str(trace), "--dry-run"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(result) == KEYS and list(result)[-1] == "compared"
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    device_metrics = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(result["metrics"])
    assert "breakdown" not in result
    if trace == 0:
        assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    # every number compared stands beside its limit, last on stderr too
    tail = p.stderr.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") for line in tail)


def test_no_chip_means_no_result_and_a_nonzero_exit():
    p, result = run_cell(["--workload", "mistral7b.decode_c16", "--seed", "1",
                          "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and result is None
    assert "TPU" in p.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    p, result = run_cell(["--workload", "mistral7b.decode_c16", "--seed", "1",
                          "--seconds", "1", "--trace", "0", "--dry-run"],
                         cwd=str(tmp_path))
    assert p.returncode != 0 and result is None


BROKEN_TOKEN = """
import sys
sys.path.insert(0, {bench!r})
import run
from tpuserver.models import llama_serving
sound = llama_serving.LlamaGenerateModel._execute_scheduled
def altered(self, *a, **k):
    for n, event in enumerate(sound(self, *a, **k)):
        if n == 2:      # a token altered where it is produced
            event = dict(event, TOKEN=(event["TOKEN"] + 977) % 2048)
        yield event
llama_serving.LlamaGenerateModel._execute_scheduled = altered
sys.exit(run.main(sys.argv[1:]))
"""

@pytest.mark.parametrize("cell,code,number", [
    ("mistral7b.decode_c16", BROKEN_TOKEN, "logit_gap_max"),
    ("mistral7b.long_prompt_c8", BROKEN_TOKEN, "logit_gap_max")])
def test_a_broken_timed_path_is_not_correct(cell, code, number):
    p, result = run_cell(["--workload", cell, "--seed", "77", "--seconds", "4",
                          "--trace", "0", "--dry-run"],
                         code=code.format(bench=BENCH))
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    value, limit = result["compared"][number]
    assert value > limit
