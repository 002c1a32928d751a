"""The control must come out NOT correct: the plain reference, put in the
program's place and computed in int8 (the precision below the bf16 the
configurations state), read by the run's own numbers (``generate.
_gap_numbers``, what ``check`` and ``control`` both return) against the
cells' own limits, and judged by ``compare.verdict``.  On the chip it
was read at the cells' own sizes through ``readings.py`` (PERF.md gives
the readings); here at sizes a test run can hold, and once through
``readings.py`` itself at the dry-run size."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import compare
import reference
from kinds import generate

from conftest import BENCH, ROOT

CELLS = ("mistral7b.decode_c16", "mistral7b.long_prompt_c8")


def limits(cell):
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        return json.load(f)


# Mistral's vocabulary (the near-ties a lower precision flips live there),
# an eighth of its width, 8 layers, 4 x 256 positions
SMALL = dict(hidden_size=512, intermediate_size=1536, num_attention_heads=8,
             num_key_value_heads=2, head_dim=64, num_hidden_layers=8,
             vocab_size=32768, rope_theta=1e6, rms_norm_eps=1e-5)


@pytest.mark.parametrize("seed", [3, 4, 2**31 + 5])
def test_int8_decoder_is_not_correct_and_the_reference_is(seed):
    tokens = np.random.default_rng(seed).integers(0, 32768, (4, 256))
    ref = reference.decoder_logits(seed, SMALL, tokens, [0] * 4, 256)
    low = reference.decoder_logits(seed, SMALL, tokens, [0] * 4, 256, "int8")

    def gaps(chosen):
        picked = np.take_along_axis(ref, chosen[..., None], -1)[..., 0]
        return list(ref.max(-1) - picked)

    for cell in CELLS:
        sound = generate._gap_numbers(gaps(ref.argmax(-1)), limits(cell))
        assert compare.verdict(sound)
        control = generate._gap_numbers(gaps(low.argmax(-1)), limits(cell))
        assert not compare.verdict(control)
        assert not control["logit_gap_mean"]["ok"]   # the number that separates


def test_readings_judge_program_control_and_fault_by_the_runs_own_verdict():
    """``readings.py`` end to end at the dry-run size: the program reads
    correct, a token altered in what the window produced does not.  (The
    int8 control's verdict is printed too; a few dozen tokens of a
    64-wide model do not always separate it, the cells' sizes do.)"""
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "readings.py"),
         "--workload", "mistral7b.long_prompt_c8", "--seeds", "5",
         "--seconds", "3", "--faults", "altered_token", "--dry-run"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    rows = [json.loads(line[len("READING "):])
            for line in p.stdout.splitlines() if line.startswith("READING ")]
    assert len(rows) == 1, p.stderr[-2000:]
    row, = rows
    assert row["correct"] is True
    assert set(row["broken"]) == {"int8", "altered_token"}
    altered = row["broken"]["altered_token"]
    assert altered["correct"] is False
    value, limit = altered["compared"]["logit_gap_max"]
    assert value > limit
    assert isinstance(row["broken"]["int8"]["correct"], bool)
