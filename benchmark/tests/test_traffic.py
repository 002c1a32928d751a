import collections

import pytest

import traffic

MIX = {"clients": 16, "rounds": 24,
       "prompt_tokens": {"choices": [[256, 0.6], [1024, 0.4]]},
       "max_tokens": {"log_uniform": [128, 512]},
       "ramp": {"max_tokens": {"uniform": [16, 128]}}}


def test_same_multiset_for_two_seeds_in_another_order():
    a = traffic.generation_schedule(MIX, 1)
    b = traffic.generation_schedule(MIX, 2**31 + 7)
    assert traffic.multiset(a) == traffic.multiset(b)
    order = lambda s: [(r.prompt_tokens, r.max_tokens) for rs in s for r in rs]
    assert order(a) != order(b)
    assert order(a) == order(traffic.generation_schedule(MIX, 1))


def test_choices_are_apportioned_exactly():
    pairs = traffic.multiset(traffic.generation_schedule(MIX, 3))
    prompts, outs = zip(*pairs)
    counts = collections.Counter(prompts)
    assert counts == {256: 230, 1024: 154}      # 60 / 40 of 384
    assert min(outs) >= 128 and max(outs) <= 512
    assert 270 < sum(outs) / len(outs) < 285    # log-uniform mean ~277


def test_every_round_holds_the_same_spread_whatever_the_seed():
    sched = traffic.generation_schedule(MIX, 5)
    other = traffic.generation_schedule(MIX, 6)
    for r in range(1, 25):
        row = [reqs[r] for reqs in sched]
        assert sum(1 for q in row if q.prompt_tokens == 1024) in (6, 7)
        assert 270 < sum(q.max_tokens for q in row) / 16 < 285
        # each prompt length meets short and long answers in every round
        for length in (256, 1024):
            outs = [q.max_tokens for q in row if q.prompt_tokens == length]
            assert min(outs) < 200 and max(outs) > 350
        # the seed deals the round's requests to other clients, no more
        pairs = lambda s: sorted((reqs[r].prompt_tokens, reqs[r].max_tokens)
                                 for reqs in s)
        assert pairs(sched) == pairs(other)
    assert all(reqs[0].ramp and reqs[0].max_tokens <= 128 for reqs in sched)


def test_prompt_ids_come_from_the_seed_and_are_unshared():
    sched = traffic.generation_schedule(MIX, 9)
    a = traffic.prompt_ids(9, sched[0][1], 32768)
    assert (a == traffic.prompt_ids(9, sched[0][1], 32768)).all()
    assert (a[:16] != traffic.prompt_ids(9, sched[1][1], 32768)[:16]).any()
    assert (a[:16] != traffic.prompt_ids(10, sched[0][1], 32768)[:16]).any()
    assert a.min() >= 0 and a.max() < 32768


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        traffic.quantile_set({"zipf": [1, 2]}, 4)
    with pytest.raises(ValueError):
        traffic.distinct_values({"uniform": [1, 2]})
