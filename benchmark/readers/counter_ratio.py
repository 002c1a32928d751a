"""Window delta of one program counter over the window delta of another,
times ``scale``.  A term is ``{"metric": <exposition sample>}``, labelled
with the traffic's model."""

import counters


def _delta(ctx, term):
    return counters.delta(ctx, term["metric"], model=ctx.traffic["model"])


def read(ctx, spec):
    num, den = _delta(ctx, spec["numerator"]), _delta(ctx, spec["denominator"])
    if num is None or not den:
        return None
    return num / den * spec["scale"]
