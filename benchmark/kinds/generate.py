"""Traffic kind ``generate``: closed-loop streams over
``tritonclient.grpc`` ``generate_stream`` against a generation model.

Each client is one thread that sends its next request only when the
last has ended; the schedule comes from ``traffic.generation_schedule``.
TTFT / gap / rate arithmetic is ``stats.py``'s (copied in spirit from
``perfanalyzer.generation._GenCollector``; nothing of ``perfanalyzer`` is
imported).
"""

import dataclasses
import threading
import time

import numpy as np

import stats
from compare import at_least, at_most
import traffic as traffic_mod

GAP_EDGES = (0, 10, 20, 30, 35, 40, 50, 60, 80, 100, 150, 200, 300, 500, 1000)
TTFT_EDGES = (0, 50, 100, 150, 200, 300, 400, 600, 800, 1200, 2000, 5000)
WARM_CLIENT = 1 << 20     # the warm-up requests' client number: no caller's
CONTROLS = ("int8",)      # the precision below the configurations' bf16


@dataclasses.dataclass
class Record:
    client: int
    index: int
    ramp: bool
    prompt_tokens: int
    max_tokens: int
    t_free: float = 0.0
    t_send: float = 0.0
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: str = ""


def _inputs(grpcclient, ids, max_tokens):
    p_in = grpcclient.InferInput("PROMPT_IDS", [len(ids)], "INT32")
    p_in.set_data_from_numpy(ids)
    m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
    m_in.set_data_from_numpy(np.array([max_tokens], np.int32))
    return [p_in, m_in]


class StreamClient(threading.Thread):
    """One closed-loop caller."""

    def __init__(self, url, model_name, requests, prompts, stop):
        super().__init__(daemon=True, name="bench-stream-client")
        self.url, self.model_name = url, model_name
        self.requests, self.prompts, self.stop = requests, prompts, stop
        self.records = []
        self.ramped = threading.Event()
        self.exhausted = False

    def run(self):
        import tritonclient.grpc as grpcclient
        from tritonclient.utils import InferenceServerException

        prepared = [_inputs(grpcclient, self.prompts[(r.client, r.index)],
                            r.max_tokens) for r in self.requests]
        client = grpcclient.InferenceServerClient(self.url)
        try:
            t_free = time.perf_counter()
            for req, inputs in zip(self.requests, prepared):
                if self.stop.is_set():
                    return
                rec = Record(req.client, req.index, req.ramp,
                             req.prompt_tokens, req.max_tokens, t_free=t_free)
                rec.t_send = time.perf_counter()
                stream = client.generate_stream(
                    self.model_name, inputs, resume=False)
                try:
                    for result in stream:
                        rec.token_times.append(time.perf_counter())
                        rec.tokens.append(int(result.as_numpy("TOKEN")[0]))
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


def target(ctx):
    """The repository entry the traffic names, and its sizes as its
    builder reads them (``models/<builder>.py``: ``sizes_of`` with a
    ``vocab_size``, and ``reference_logits`` for ``correct``)."""
    entry = next(e for e in ctx.config["repository"]
                 if e["name"] == ctx.traffic["model"])
    return entry, ctx.builders[entry["name"]].sizes_of(ctx.config, entry)


def prepare(ctx):
    """Schedule and token ids from the seed; then one short request per
    distinct prompt length, which carries that length's prefill compile
    and, the first time, the step's and the admit's."""
    entry, sizes = target(ctx)
    top = traffic_mod.upper(ctx.traffic["prompt_tokens"]) + traffic_mod.upper(
        ctx.traffic["max_tokens"])
    if top > entry["max_seq"]:
        raise ValueError("traffic {} reaches {} tokens, max_seq is {}".format(
            ctx.traffic["name"], top, entry["max_seq"]))
    ctx.schedule = traffic_mod.generation_schedule(ctx.traffic, ctx.seed)
    ctx.prompts = {(r.client, r.index): traffic_mod.prompt_ids(
        ctx.seed, r, sizes["vocab_size"])
        for reqs in ctx.schedule for r in reqs}
    lengths = set(traffic_mod.distinct_values(ctx.traffic["prompt_tokens"]))
    ramp = ctx.traffic.get("ramp", {})
    if "prompt_tokens" in ramp:
        lengths |= set(traffic_mod.distinct_values(ramp["prompt_tokens"]))
    stop = threading.Event()
    for n in sorted(lengths):
        t = time.perf_counter()
        req = traffic_mod.Request(WARM_CLIENT, n, n, 2)
        warm = StreamClient(ctx.url, ctx.traffic["model"], [req],
                            {(WARM_CLIENT, n): traffic_mod.prompt_ids(
                                ctx.seed, req, sizes["vocab_size"])}, stop)
        warm.run()
        rec = warm.records[0]
        if not rec.done or len(rec.tokens) != 2:
            raise RuntimeError("warm-up at prompt length {} failed: {}".format(
                n, rec.error or rec.tokens))
        ctx.log("warm-up prompt length {}: {:.1f}s".format(
            n, time.perf_counter() - t))


def clients(ctx, stop):
    return [StreamClient(ctx.url, ctx.traffic["model"], reqs, ctx.prompts,
                         stop) for reqs in ctx.schedule]


def records(ctx):
    return [r for c in ctx.clients for r in c.records]


def series(ctx):
    recs, t0, t1 = records(ctx), ctx.t0, ctx.t1
    return {"gap_ms": stats.gaps_ms(recs, t0, t1),
            "ttft_ms": stats.ttfts_ms(recs, t0, t1),
            "late_ms": stats.late_ms(recs, t0, t1)}


def end_to_end(ctx):
    s = series(ctx)
    return {"tok_per_s": stats.token_rate(records(ctx), ctx.t0, ctx.t1),
            "itl_ms_p99": stats.percentile(s["gap_ms"], 99),
            "ttft_ms_p50": stats.percentile(s["ttft_ms"], 50)}


def attempted_failed(ctx):
    """Requests that were in flight at some time in the window; a failed
    one has missed every limit."""
    live = [r for r in records(ctx) if r.t_send < ctx.t1 and (
        not r.token_times or r.token_times[-1] >= ctx.t0 or r.error)]
    failed = sum(1 for r in live if r.error)
    if any(c.exhausted for c in ctx.clients):
        raise RuntimeError("a client ran out of schedule: raise 'rounds' in "
                           "traffic {}".format(ctx.traffic["name"]))
    return len(live), failed


def histograms(ctx):
    s = series(ctx)
    return ["gap_ms histogram (n={}): {}".format(
                len(s["gap_ms"]), stats.histogram(s["gap_ms"], GAP_EDGES)),
            "ttft_ms histogram (n={}): {}".format(
                len(s["ttft_ms"]), stats.histogram(s["ttft_ms"], TTFT_EDGES)),
            "gap_ms p50 {:.3f} p90 {:.3f} p99 {:.3f} | ttft_ms p50 {:.3f} p75 "
            "{:.3f} p90 {:.3f} p95 {:.3f} mean {:.3f}".format(
                *(stats.percentile(s[k], q) or 0.0 for k, q in (
                    ("gap_ms", 50), ("gap_ms", 90), ("gap_ms", 99),
                    ("ttft_ms", 50), ("ttft_ms", 75), ("ttft_ms", 90),
                    ("ttft_ms", 95))),
                sum(s["ttft_ms"]) / max(1, len(s["ttft_ms"])))]


def sample_finished(ctx):
    """The requests the reference follows: the longest the window
    finished, and others drawn from the seed."""
    done = [r for r in records(ctx) if r.done and r.token_times
            and ctx.t0 <= r.token_times[-1] < ctx.t1]
    if not done:
        return []
    done.sort(key=lambda r: (r.client, r.index))
    longest = max(done, key=lambda r: r.prompt_tokens + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(ctx.seed), 0xC4EC])
    k = min(len(rest), int(ctx.limits["check_requests"]) - 1)
    picked = [rest[i] for i in rng.choice(len(rest), k, replace=False)]
    return [longest] + picked


def logit_gaps(ctx, sample, precision="f32"):
    """Per sampled request, by how far each served token's reference
    logit lies below the reference's best at its position (float32
    reference, one teacher-forced pass over prompt + served tokens).
    With ``precision`` of a control: the same for the token the control
    puts first, at the same positions."""
    entry, sizes = target(ctx)
    count = traffic_mod.upper(ctx.traffic["max_tokens"])
    width = traffic_mod.upper(ctx.traffic["prompt_tokens"]) + count
    width = -(-width // 128) * 128
    rows = np.zeros((len(sample), width), np.int32)
    for i, r in enumerate(sample):
        ids = ctx.prompts[(r.client, r.index)]
        rows[i, :len(ids)] = ids
        rows[i, len(ids):len(ids) + len(r.tokens)] = r.tokens
    first = [r.prompt_tokens - 1 for r in sample]
    logits = ctx.builders[entry["name"]].reference_logits
    ref = logits(ctx.seed, sizes, rows, first, count)
    chosen = [np.asarray(r.tokens) for r in sample]
    if precision != "f32":
        low = logits(ctx.seed, sizes, rows, first, count, precision)
        chosen = [low[i, :len(r.tokens)].argmax(-1)
                  for i, r in enumerate(sample)]
    gaps = []
    for i, r in enumerate(sample):
        n = len(r.tokens)
        best = ref[i, :n].max(-1)
        gaps.append(best - ref[i, np.arange(n), chosen[i]])
    return gaps


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
            if not 0 <= t < sizes["vocab_size"]), 0),
        "requests_compared": at_least(len(sample), min(
            int(ctx.limits["check_requests"]), 2)),
    }
    if sample:
        out.update(_gap_numbers(logit_gaps(ctx, sample), ctx.limits))
    return out


def _gap_numbers(gaps, limits):
    """The widest gap catches one altered token; the mean gap is what
    separates the precisions (it goes with the square of the rounding
    error, the widest only with the error itself: PERF.md)."""
    tokens = sum(len(g) for g in gaps)
    return {
        "tokens_compared": at_least(tokens, int(limits["check_min_tokens"])),
        "logit_gap_max": at_most(float(max(g.max() for g in gaps)),
                                 float(limits["logit_gap_max"])),
        "logit_gap_mean": at_most(float(sum(g.sum() for g in gaps) / tokens),
                                  float(limits["logit_gap_mean"])),
    }


def control(ctx, precision):
    """What ``check`` reads with the control in the program's place: the
    reference in ``precision`` over the same prompts and served tokens,
    each number beside the cell's own limit, for ``compare.verdict``."""
    return _gap_numbers(logit_gaps(ctx, sample_finished(ctx), precision),
                        ctx.limits)


def fault(ctx, name):
    """What ``check`` reads with a fault planted in what the window
    produced.  ``altered_token``: one served token of the longest sampled
    request, at a place drawn from the seed, is another one (the requests
    that follow it in the stream are the program's own, as they are when
    a token is altered where it is delivered)."""
    if name != "altered_token":
        raise ValueError("unknown fault {!r}".format(name))
    _, sizes = target(ctx)
    sample = sample_finished(ctx)
    rng = np.random.default_rng([int(ctx.seed), 0xFA17])
    at = int(rng.integers(len(sample[0].tokens)))
    tokens = list(sample[0].tokens)
    tokens[at] = (tokens[at] + 977) % sizes["vocab_size"]
    sample[0] = dataclasses.replace(sample[0], tokens=tokens)
    return _gap_numbers(logit_gaps(ctx, sample), ctx.limits)
