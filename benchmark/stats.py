"""Percentile, rate and histogram arithmetic of the benchmark.

Copied in spirit from ``perfanalyzer.metrics`` / ``generation._GenCollector``
(the benchmark imports nothing of ``perfanalyzer``): a percentile is the
linear interpolation between the two nearest order statistics, a rate is
a count over the whole window's seconds, and nothing here is a median of
chunks.
"""

import math


def percentile(values, q):
    """``q`` in [0, 100]; linear interpolation between order statistics.
    ``None`` for no samples: a metric with nothing to read is left out,
    never reported as 0."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t, t0, t1):
    return t0 <= t < t1


def token_rate(records, t0, t1):
    """Output tokens received inside [t0, t1) over the window's seconds:
    every stream counts, those the window's end cut included."""
    n = sum(1 for r in records for t in r.token_times if in_window(t, t0, t1))
    return n / (t1 - t0)


def gaps_ms(records, t0, t1):
    """All gaps between consecutive tokens of all streams whose later
    token arrived in the window."""
    out = []
    for r in records:
        ts = r.token_times
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                   if in_window(b, t0, t1))
    return out


def ttfts_ms(records, t0, t1):
    """send -> first token, for requests whose first token arrived in
    the window."""
    return [(r.token_times[0] - r.t_send) * 1e3 for r in records
            if r.token_times and in_window(r.token_times[0], t0, t1)]


def late_ms(records, t0, t1):
    """How late each send ran after its client was free."""
    return [(r.t_send - r.t_free) * 1e3 for r in records
            if in_window(r.t_send, t0, t1)]


def histogram(values, edges):
    """Counts per [edge_i, edge_{i+1}) with an open last bucket, as one
    printable line."""
    counts = [0] * len(edges)
    for v in values:
        i = 0
        while i + 1 < len(edges) and v >= edges[i + 1]:
            i += 1
        counts[i] += 1
    return " ".join("{}:{}".format(e, c) for e, c in zip(edges, counts))
