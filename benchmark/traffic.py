"""The one general traffic generator: a traffic file of parameters in,
an equal-work schedule out.

Equal work: a distribution in the file is never sampled.  The generator
takes a fixed stratified set of quantiles from it and pairs prompt with
answer lengths by a fixed rule, so every seed offers the SAME multiset
of (prompt, answer) requests, round by round; the seed decides only the
assignment to clients (and with it the order of arrival) and the token
ids.  (A pairing drawn from the seed changed the work: a seed that gave
the long prompts the long answers held the rows at longer contexts and
read 2.5 % fewer tokens/s, PERF.md.)  The multiset is laid out in rounds
of one request per client, and every round holds (to rounding) the same
spread of lengths, so a window that ends mid-schedule has still seen the
same kind of work.

Distributions (``{"kind": [..]}``):
  {"fixed": v}                      every request v
  {"choices": [[v, weight], ...]}   discrete, apportioned by largest remainder
  {"uniform": [lo, hi]}             continuous, integer-rounded quantile midpoints
  {"log_uniform": [lo, hi]}         the same on a log scale
"""

import dataclasses
import math

import numpy as np

DIST_KINDS = ("fixed", "choices", "uniform", "log_uniform")


def quantile_set(dist, n):
    """The n stratified values of ``dist``, sorted: the multiset every
    seed gets."""
    (kind, arg), = dist.items()
    if kind == "fixed":
        return [int(arg)] * n
    if kind == "choices":
        total = float(sum(w for _, w in arg))
        exact = [n * w / total for _, w in arg]
        counts = [int(math.floor(x)) for x in exact]
        by_rest = sorted(range(len(arg)), key=lambda i: exact[i] - counts[i],
                         reverse=True)
        for i in by_rest[:n - sum(counts)]:
            counts[i] += 1
        out = []
        for (v, _), c in zip(arg, counts):
            out.extend([int(v)] * c)
        return sorted(out)
    lo, hi = float(arg[0]), float(arg[1])
    mids = [(i + 0.5) / n for i in range(n)]
    if kind == "uniform":
        return [int(round(lo + (hi - lo) * u)) for u in mids]
    if kind == "log_uniform":
        return [int(round(math.exp(math.log(lo) + math.log(hi / lo) * u)))
                for u in mids]
    raise ValueError("unknown distribution {!r} (known: {})".format(
        kind, ", ".join(DIST_KINDS)))


def distinct_values(dist):
    """Every value a discrete distribution can give (the shapes to warm)."""
    (kind, arg), = dist.items()
    if kind == "fixed":
        return [int(arg)]
    if kind == "choices":
        return sorted({int(v) for v, _ in arg})
    raise ValueError("{} is continuous: it has no finite shape set".format(kind))


def upper(dist):
    (kind, arg), = dist.items()
    if kind == "fixed":
        return int(arg)
    if kind == "choices":
        return max(int(v) for v, _ in arg)
    return int(arg[1])


@dataclasses.dataclass
class Request:
    client: int
    index: int          # position in the client's list
    prompt_tokens: int
    max_tokens: int
    ramp: bool = False


def _stride(n):
    """A step coprime to ``n`` near ``n`` / golden ratio: ``i * step % n``
    sends every run of consecutive ``i`` to values spread evenly over
    ``0..n-1``."""
    step = max(1, round(n * 0.6180339887))
    while math.gcd(step, n) != 1:
        step += 1
    return step


def request_pairs(traffic):
    """``[round][k] -> (prompt_tokens, max_tokens)``: the multiset every
    seed gets.  Round ``r`` takes every ``rounds``-th sorted prompt
    quantile and one answer quantile from each of ``clients`` strata;
    slot ``k`` of the round meets stratum ``(stride * k + r) % clients``,
    so neighbouring prompt lengths meet answer lengths spread over the
    whole range, in every round, and each answer is used once."""
    clients, rounds = int(traffic["clients"]), int(traffic["rounds"])
    n = clients * rounds
    prompts = quantile_set(traffic["prompt_tokens"], n)
    outs = quantile_set(traffic["max_tokens"], n)
    step = _stride(clients)
    out = []
    for r in range(rounds):
        strata = [(step * k + r) % clients for k in range(clients)]
        out.append([(prompts[r + rounds * k],
                     outs[s * rounds + (r + s) % rounds])
                    for k, s in enumerate(strata)])
    return out


def generation_schedule(traffic, seed):
    """``[client][i] -> Request``: for each client one ramp request, then
    ``rounds`` requests.  Same multiset for every seed."""
    clients = int(traffic["clients"])
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    ramp = traffic.get("ramp", {})
    ramp_prompts = quantile_set(
        ramp.get("prompt_tokens", traffic["prompt_tokens"]), clients)
    ramp_outs = quantile_set(ramp.get("max_tokens", traffic["max_tokens"]),
                             clients)
    rng.shuffle(ramp_prompts)
    rng.shuffle(ramp_outs)
    per_client = [[Request(c, 0, ramp_prompts[c], ramp_outs[c], ramp=True)]
                  for c in range(clients)]
    for r, row in enumerate(request_pairs(traffic)):
        rng.shuffle(row)        # the assignment to clients
        for c, (p, o) in enumerate(row):
            per_client[c].append(Request(c, r + 1, p, o))
    return per_client


def prompt_ids(seed, request, vocab):
    """Token ids of one request: unshared, from the seed."""
    rng = np.random.default_rng(
        [int(seed), 0x1D5, request.client, request.index])
    return rng.integers(0, vocab, (request.prompt_tokens,), dtype=np.int32)


def multiset(per_client):
    """What the schedule offers, ramp apart: the sorted (prompt, answer)
    lengths of its requests."""
    return sorted((r.prompt_tokens, r.max_tokens)
                  for rs in per_client for r in rs if not r.ramp)
