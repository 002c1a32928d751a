"""Traffic kind ``generate_blocks``: ``generate_pinned``'s closed-loop
callers and schedule against a model that generates by diffusion over
blocks, with the block stream's records.

Every caller is kept to ONE class of prompt length and ONE class of
``denoising_steps`` (its trade of quality for speed): a round's requests
are sorted by prompt length, slot ``i`` of every round goes to the same
client, and the slots of each prompt class take the traffic's step
classes in turn.  A response is a finished BLOCK: its tokens in position
order with ``POSITION`` and ``UNMASK_PASS`` beside ``LOGPROB``; every
token of a block arrives at the block's time.

``correct``: for ``check_requests`` finished requests, and in each the
first, the last and seeded other blocks up to ``check_blocks``, every
denoise pass is replayed teacher-forced from what was served (positions
whose ``UNMASK_PASS`` is earlier carry their served token, the rest the
mask token) by the plain reference (``models/<builder>.py``
``reference_logits``: one from-scratch float32 pass over prompt + all
served blocks, each replayed block's B positions against it).  Compared,
each beside its limit:

- ``logit_gap_mean`` / ``logit_gap_max``: the reference's best logit
  minus its logit of the served token, at the position and pass that
  unmasked it;
- ``confidence_gap_mean`` / ``confidence_gap_max``: the choice of
  POSITION is the discrete step here, as a router flip is.  Of a pass
  that unmasked n positions: the reference's n-th best log-confidence
  among the positions masked at that pass, minus that of each position
  served, 0 where the served position is among the reference's n best
  (for n = 1 the reference's best minus that of the position served);
- ``unmask_count_wrong``: passes of whole delivered blocks whose number
  of unmasked positions differs from the static schedule's;
- ``finished_short``, ``tokens_out_of_range`` (the mask id counts as out
  of range), ``requests_compared``, ``tokens_compared``.
"""

import dataclasses
import threading
import time

import numpy as np

import stats
import traffic as traffic_mod
from compare import at_least, at_most
from kinds import generate
from kinds.generate import (CONTROLS, WARM_CLIENT,  # noqa: F401
                            attempted_failed, histograms, records,
                            sample_finished, series, target)


@dataclasses.dataclass
class Block:
    time: float
    tokens: list
    logprobs: list
    positions: list
    passes: list


@dataclasses.dataclass
class Record:
    client: int
    index: int
    ramp: bool
    prompt_tokens: int
    max_tokens: int
    steps: int
    t_free: float = 0.0
    t_send: float = 0.0
    blocks: list = dataclasses.field(default_factory=list)
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: str = ""


class BlockStreamClient(threading.Thread):
    """One closed-loop caller of a block stream."""

    def __init__(self, url, model_name, requests, prompts, steps, stop):
        super().__init__(daemon=True, name="bench-block-client")
        self.url, self.model_name = url, model_name
        self.requests, self.prompts, self.steps = requests, prompts, steps
        self.stop = stop
        self.records = []
        self.ramped = threading.Event()
        self.exhausted = False

    def run(self):
        import tritonclient.grpc as grpcclient
        from tritonclient.utils import InferenceServerException

        prepared = [generate._inputs(
            grpcclient, self.prompts[(r.client, r.index)], r.max_tokens)
            for r in self.requests]
        client = grpcclient.InferenceServerClient(self.url)
        try:
            t_free = time.perf_counter()
            for req, inputs in zip(self.requests, prepared):
                if self.stop.is_set():
                    return
                steps = self.steps[(req.client, req.index)]
                rec = Record(req.client, req.index, req.ramp,
                             req.prompt_tokens, req.max_tokens, steps,
                             t_free=t_free)
                rec.t_send = time.perf_counter()
                stream = client.generate_stream(
                    self.model_name, inputs, resume=False,
                    parameters={"denoising_steps": steps})
                try:
                    for result in stream:
                        now = time.perf_counter()
                        block = Block(now, *(
                            result.as_numpy(name).tolist() for name in (
                                "TOKEN", "LOGPROB", "POSITION",
                                "UNMASK_PASS")))
                        rec.blocks.append(block)
                        rec.tokens.extend(block.tokens)
                        rec.token_times.extend([now] * len(block.tokens))
                        if self.stop.is_set():
                            break
                    else:
                        rec.done = True
                except InferenceServerException as e:
                    rec.error = str(e) or "error"
                finally:
                    stream.close()
                t_free = time.perf_counter()
                self.records.append(rec)
                self.ramped.set()
            self.exhausted = True
        finally:
            self.ramped.set()
            client.close()


def block_schedule(traffic, seed):
    """``([client][i] -> Request, {(client, i): denoising_steps})``: one
    ramp request, then ``rounds`` requests, each client within one class
    of prompt length and one of denoising steps.  The multiset of
    (prompt, answer) requests of every round is
    ``traffic.request_pairs``'s; the seed decides which client holds
    which slot, which of its class's answers it gets in each round, and
    the token ids.  Ramp answers are rounded up to whole blocks."""
    n, b = int(traffic["clients"]), int(traffic["block_length"])
    classes = traffic_mod.distinct_values(traffic["denoising_steps"])[::-1]
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    ramp = traffic.get("ramp", {})
    ramp_outs = [-(-v // b) * b for v in traffic_mod.quantile_set(
        ramp.get("max_tokens", traffic["max_tokens"]), n)]
    rng.shuffle(ramp_outs)
    ramp_prompts = traffic_mod.quantile_set(
        ramp.get("prompt_tokens", traffic["prompt_tokens"]), n)
    rows = [list(zip(ramp_prompts, ramp_outs))]
    for row in traffic_mod.request_pairs(traffic):
        rng.shuffle(row)        # which answer of its class a slot gets
        rows.append(sorted(row, key=lambda pair: pair[0]))
    holder = rng.permutation(n)     # the client of each slot
    # within each prompt class the step classes in turn, equal shares
    slot_steps, seen = [], {}
    for p, _ in rows[-1]:
        slot_steps.append(classes[seen.get(p, 0) % len(classes)])
        seen[p] = seen.get(p, 0) + 1
    per_client, steps = [[] for _ in range(n)], {}
    for i, row in enumerate(rows):
        for slot, (p, o) in enumerate(row):
            c = int(holder[slot])
            per_client[c].append(traffic_mod.Request(c, i, p, o, ramp=i == 0))
            steps[(c, i)] = slot_steps[slot]
    return per_client, steps


def prompt_ids(seed, request, sizes):
    """Token ids of one request, drawn from the vocabulary without the
    mask token."""
    ids = traffic_mod.prompt_ids(seed, request, sizes["vocab_size"] - 1)
    return ids + (ids >= sizes["mask_token_id"]).astype(ids.dtype)


def prepare(ctx):
    """Schedule and token ids from the seed; then one short request per
    distinct prompt length, which carries that length's prefill compile
    and, the first time, the block step's and the admit's."""
    entry, sizes = target(ctx)
    b = int(ctx.traffic["block_length"])
    if b != sizes["block_length"]:
        raise ValueError("traffic {} states blocks of {}, the configuration "
                         "{}".format(ctx.traffic["name"], b,
                                     sizes["block_length"]))
    top = traffic_mod.upper(ctx.traffic["prompt_tokens"]) + traffic_mod.upper(
        ctx.traffic["max_tokens"])
    if top > entry["max_seq"]:
        raise ValueError("traffic {} reaches {} tokens, max_seq is {}".format(
            ctx.traffic["name"], top, entry["max_seq"]))
    ctx.schedule, ctx.steps = block_schedule(ctx.traffic, ctx.seed)
    ctx.prompts = {(r.client, r.index): prompt_ids(ctx.seed, r, sizes)
                   for reqs in ctx.schedule for r in reqs}
    stop = threading.Event()
    for n in traffic_mod.distinct_values(ctx.traffic["prompt_tokens"]):
        t = time.perf_counter()
        req = traffic_mod.Request(WARM_CLIENT, n, n, 2 * b)
        warm = BlockStreamClient(
            ctx.url, ctx.traffic["model"], [req],
            {(WARM_CLIENT, n): prompt_ids(ctx.seed, req, sizes)},
            {(WARM_CLIENT, n): b}, stop)
        warm.run()
        rec = warm.records[0]
        if not rec.done or len(rec.tokens) != 2 * b:
            raise RuntimeError("warm-up at prompt length {} failed: {}".format(
                n, rec.error or rec.tokens))
        ctx.log("warm-up prompt length {}: {:.1f}s".format(
            n, time.perf_counter() - t))


def clients(ctx, stop):
    return [BlockStreamClient(ctx.url, ctx.traffic["model"], reqs,
                              ctx.prompts, ctx.steps, stop)
            for reqs in ctx.schedule]


def end_to_end(ctx):
    """Tokens arrive a block at a time, so no gap metric is taken."""
    return {"tok_per_s": stats.token_rate(records(ctx), ctx.t0, ctx.t1)}


def _logc(logits, mask_id):
    """Log-confidence ``log softmax(logits)[argmax]`` per position, the
    mask token's logit taken out; and those logits."""
    logits = np.array(logits, np.float32)
    logits[..., mask_id] = -np.inf
    top = logits.max(-1)
    return -np.log(np.exp(logits - top[..., None]).sum(-1)), logits


def jobs_of(ctx, sample):
    """The passes to replay: per sampled request its jobs ``(block index,
    pass, start, the block's B token ids as that pass saw them)``, first
    block, last block and others drawn from the seed, whole blocks
    only."""
    _, sizes = target(ctx)
    b, mask_id = sizes["block_length"], sizes["mask_token_id"]
    rng = np.random.default_rng([int(ctx.seed), 0xB10C])
    out = []
    for r in sample:
        ids = ctx.prompts[(r.client, r.index)]
        whole = [i for i, blk in enumerate(r.blocks)
                 if blk.positions[-1] % b == b - 1]
        rest = whole[1:-1]
        k = min(len(rest), int(ctx.limits["check_blocks"]) - 2)
        picked = sorted({*whole[:1], *whole[-1:], *(
            rest[i] for i in rng.choice(len(rest), k, replace=False))})
        jobs = []
        for i in picked:
            blk = r.blocks[i]
            start = blk.positions[0] // b * b
            given = list(ids[start:])       # the prompt's rest, first block
            for p in range(max(blk.passes) + 1):
                seen = given + [t if q < p else mask_id
                                for t, q in zip(blk.tokens, blk.passes)]
                jobs.append((i, p, start, seen))
        out.append(jobs)
    return out


REPLAY_ROWS = 4     # requests replayed at once: their logits are 1.2 GB


def gaps(ctx, sample, precision="f32"):
    """``(logit gaps, confidence gaps)`` over the replayed passes of the
    sampled requests, one value a served token (float32 reference).
    With ``precision`` of a control: the same for the token and the
    positions the control puts first, at the same passes."""
    entry, sizes = target(ctx)
    b, mask_id = sizes["block_length"], sizes["mask_token_id"]
    width = traffic_mod.upper(ctx.traffic["prompt_tokens"]) + traffic_mod.upper(
        ctx.traffic["max_tokens"])
    width = -(-width // 128) * 128
    logits = ctx.builders[entry["name"]].reference_logits
    logit_gaps, conf_gaps = [], []
    for at in range(0, len(sample), REPLAY_ROWS):
        some = sample[at:at + REPLAY_ROWS]
        jobs = jobs_of(ctx, some)
        most = max(len(j) for j in jobs)
        rows = np.zeros((len(some), width), np.int32)
        starts = np.zeros((len(some), most), np.int32)
        blocks = np.full((len(some), most, b), mask_id, np.int32)
        for n, (r, mine) in enumerate(zip(some, jobs)):
            ids = ctx.prompts[(r.client, r.index)]
            rows[n, :len(ids)] = ids
            rows[n, len(ids):len(ids) + len(r.tokens)] = r.tokens
            for j, (_, _, start, seen) in enumerate(mine):
                starts[n, j], blocks[n, j] = start, seen
        ref = logits(ctx.seed, sizes, rows, starts, blocks)
        low = (ref if precision == "f32" else
               logits(ctx.seed, sizes, rows, starts, blocks, precision))
        for n, (r, mine) in enumerate(zip(some, jobs)):
            for j, job in enumerate(mine):
                one = _pass_gaps(r, job, ref[n, j], low[n, j], b, mask_id,
                                 precision != "f32")
                logit_gaps.extend(one[0])
                conf_gaps.extend(one[1])
    return np.array(logit_gaps), np.array(conf_gaps)


def _pass_gaps(r, job, ref, low, b, mask_id, control):
    """The gaps of ONE replayed pass: ``ref`` / ``low`` [B, V] are the
    reference's logits and the control's (the same array without a
    control) for the block as the pass saw it."""
    i, p, start, seen = job
    blk = r.blocks[i]
    conf, plain = _logc(ref, mask_id)
    masked = np.array([t == mask_id for t in seen]) & (
        start + np.arange(b) >= r.prompt_tokens)
    at = [q - start for q, u in zip(blk.positions, blk.passes) if u == p]
    tokens = [t for t, u in zip(blk.tokens, blk.passes) if u == p]
    if control:
        # what the control would have served at this pass
        conf_low, plain_low = _logc(low, mask_id)
        order = sorted(np.flatnonzero(masked),
                       key=lambda q: (-conf_low[q], q))
        at = sorted(order[:len(at)])
        tokens = [int(plain_low[q].argmax()) for q in at]
    # the reference's n-th best among the positions masked here
    nth = np.sort(conf[masked])[::-1][len(at) - 1]
    return ([float(plain[q].max() - plain[q, t]) for q, t in zip(at, tokens)],
            [float(max(0.0, nth - conf[q])) for q in at])


def unmask_count_wrong(recs, b):
    """Passes of whole delivered blocks whose number of unmasked
    positions differs from the static schedule's (``B // T``, +1 in the
    first ``B % T`` passes, never more than are still masked)."""
    wrong = 0
    for r in recs:
        for blk in r.blocks:
            if blk.positions[-1] % b != b - 1:
                continue        # cut by max_tokens
            left = len(blk.positions)
            for p in range(max(blk.passes) + 1):
                due = min(left, b // r.steps + (1 if p < b % r.steps else 0))
                wrong += blk.passes.count(p) != due
                left -= due
            wrong += left != 0
    return wrong


def _gap_numbers(logit_gaps, conf_gaps, limits):
    return {
        "tokens_compared": at_least(len(logit_gaps),
                                    int(limits["check_min_tokens"])),
        "logit_gap_max": at_most(float(logit_gaps.max()),
                                 float(limits["logit_gap_max"])),
        "logit_gap_mean": at_most(float(logit_gaps.mean()),
                                  float(limits["logit_gap_mean"])),
        "confidence_gap_max": at_most(float(conf_gaps.max()),
                                      float(limits["confidence_gap_max"])),
        "confidence_gap_mean": at_most(float(conf_gaps.mean()),
                                       float(limits["confidence_gap_mean"])),
    }


def check(ctx):
    """Every number compared, each beside its limit (``limits/<cell>.json``,
    set from chip readings: PERF.md)."""
    _, sizes = target(ctx)
    recs = records(ctx)
    sample = sample_finished(ctx)
    out = {
        "finished_short": at_most(sum(
            1 for r in recs if r.done and len(r.tokens) != r.max_tokens), 0),
        "tokens_out_of_range": at_most(sum(
            1 for r in recs for t in r.tokens
            if not 0 <= t < sizes["vocab_size"]
            or t == sizes["mask_token_id"]), 0),
        "unmask_count_wrong": at_most(
            unmask_count_wrong(recs, sizes["block_length"]), 0),
        "requests_compared": at_least(len(sample), min(
            int(ctx.limits["check_requests"]), 2)),
    }
    if sample:
        out.update(_gap_numbers(*gaps(ctx, sample), ctx.limits))
    return out


def control(ctx, precision):
    """What ``check`` reads with the control in the program's place."""
    return _gap_numbers(*gaps(ctx, sample_finished(ctx), precision),
                        ctx.limits)


def fault(ctx, name):
    """What ``check`` reads with a fault planted in what the window
    produced.  ``altered_token``: one served token of a replayed block of
    the longest sampled request, at a place drawn from the seed, is
    another one."""
    if name != "altered_token":
        raise ValueError("unknown fault {!r}".format(name))
    _, sizes = target(ctx)
    sample = sample_finished(ctx)
    rng = np.random.default_rng([int(ctx.seed), 0xFA17])
    i = jobs_of(ctx, sample[:1])[0][0][0]       # a block that is replayed
    blocks = list(sample[0].blocks)
    tokens = list(blocks[i].tokens)
    at = int(rng.integers(len(tokens)))
    tokens[at] = (tokens[at] + 977) % (sizes["vocab_size"] - 1)
    tokens[at] += tokens[at] >= sizes["mask_token_id"]
    blocks[i] = dataclasses.replace(blocks[i], tokens=tokens)
    sample[0] = dataclasses.replace(
        sample[0], blocks=blocks,
        tokens=[t for blk in blocks for t in blk.tokens])
    return _gap_numbers(*gaps(ctx, sample), ctx.limits)
