"""``kinds/generate_pinned.py``: ``generate``'s work, round by round, with
every caller kept to one class of prompt length."""

import json
import os

import traffic
from conftest import BENCH
from kinds import generate, generate_pinned

MIX = json.load(open(os.path.join(BENCH, "traffic", "mixed-ctx-c32.json")))


def rounds_of(schedule):
    return [sorted((reqs[i].prompt_tokens, reqs[i].max_tokens)
                   for reqs in schedule)
            for i in range(len(schedule[0]))]


def test_the_cell_names_this_kind():
    assert MIX["kind"] == "generate_pinned"


def test_every_round_offers_what_generate_offers_whatever_the_seed():
    plain = traffic.generation_schedule(MIX, 3)
    a = generate_pinned.pinned_schedule(MIX, 3)
    b = generate_pinned.pinned_schedule(MIX, 2**31 + 7)
    assert rounds_of(a)[1:] == rounds_of(plain)[1:] == rounds_of(b)[1:]
    order = lambda s: [(r.prompt_tokens, r.max_tokens) for rs in s for r in rs]
    assert order(a) != order(b)
    assert order(a) == order(generate_pinned.pinned_schedule(MIX, 3))


def test_a_caller_keeps_to_one_prompt_length_ramp_included():
    for seed in (1, 2**31 + 11):
        sched = generate_pinned.pinned_schedule(MIX, seed)
        classes = [{r.prompt_tokens for r in reqs} for reqs in sched]
        assert all(len(c) == 1 for c in classes)
        assert sorted(min(c) for c in classes) == [1024] * 24 + [8192] * 8
        for c, reqs in enumerate(sched):
            assert [r.index for r in reqs] == list(range(MIX["rounds"] + 1))
            assert all(r.client == c for r in reqs)
            assert reqs[0].ramp and reqs[0].max_tokens <= 128
            assert not any(r.ramp for r in reqs[1:])
            # a caller meets short and long answers over its rounds
            outs = [r.max_tokens for r in reqs[1:]]
            assert min(outs) < 200 and max(outs) > 350


def test_the_seed_decides_who_the_long_callers_are():
    long_of = lambda seed: {c for c, reqs in enumerate(
        generate_pinned.pinned_schedule(MIX, seed))
        if reqs[1].prompt_tokens == 8192}
    assert long_of(1) != long_of(2)


def test_everything_else_is_generates():
    for name in ("clients", "records", "series", "end_to_end", "check",
                 "attempted_failed", "histograms", "control", "fault",
                 "CONTROLS"):
        assert getattr(generate_pinned, name) is getattr(generate, name)
