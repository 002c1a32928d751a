"""Traffic kind ``generate_pinned``: ``generate``'s closed-loop callers,
series and ``correct``, with every caller kept to ONE class of prompt
length.

``generate`` hands each round's requests to the clients in an order
drawn from the seed, so a caller sends a long prompt in one round and a
short one in the next, and how many long rows are in flight over a 45 s
window is the seed's luck.  Where a long row costs the step four short
ones that luck is the reading: ``tok_per_s`` of ``trinity.mixed_ctx_c32``
spread 4.6-7.5 % over seeds under ``generate`` (PERF.md 6).  Here the
callers are who the traffic file says they are: a round's requests are
sorted by prompt length and slot ``i`` of every round goes to the same
client, so a document caller sends the long prompts of every round and
a chat caller the short ones.  The multiset of (prompt, answer) requests
of every round is ``traffic.request_pairs``'s, the same as under
``generate``; the seed decides which client holds which slot, which of
its class's answers it gets in each round, and the token ids.
"""

import numpy as np

import traffic as traffic_mod
from kinds import generate
from kinds.generate import (CONTROLS, attempted_failed, check,  # noqa: F401
                            clients, control, end_to_end, fault, histograms,
                            records, series)


def pinned_schedule(traffic, seed):
    """``[client][i] -> Request``: one ramp request, then ``rounds``
    requests, each client within one class of prompt length."""
    n = int(traffic["clients"])
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    ramp = traffic.get("ramp", {})
    ramp_outs = traffic_mod.quantile_set(
        ramp.get("max_tokens", traffic["max_tokens"]), n)
    rng.shuffle(ramp_outs)
    ramp_prompts = traffic_mod.quantile_set(
        ramp.get("prompt_tokens", traffic["prompt_tokens"]), n)
    rows = [list(zip(ramp_prompts, ramp_outs))]
    for row in traffic_mod.request_pairs(traffic):
        rng.shuffle(row)        # which answer of its class a slot gets
        rows.append(sorted(row, key=lambda pair: pair[0]))
    holder = rng.permutation(n)     # the client of each slot
    per_client = [[] for _ in range(n)]
    for i, row in enumerate(rows):
        for slot, (p, o) in enumerate(row):
            c = int(holder[slot])
            per_client[c].append(traffic_mod.Request(c, i, p, o, ramp=i == 0))
    return per_client


def prepare(ctx):
    """``generate``'s checks and warm-ups, then this kind's schedule."""
    generate.prepare(ctx)
    _, sizes = generate.target(ctx)
    ctx.schedule = pinned_schedule(ctx.traffic, ctx.seed)
    ctx.prompts = {(r.client, r.index): traffic_mod.prompt_ids(
        ctx.seed, r, sizes["vocab_size"])
        for reqs in ctx.schedule for r in reqs}
