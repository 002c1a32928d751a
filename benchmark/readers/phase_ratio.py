"""Window delta of a program counter summed over some values of its
``phase`` label, over another such sum or a plain sample's delta, times
``scale``.  A term is ``{"metric": <exposition sample>}``, with
``"phases": [<label values>]`` where the sample carries the label; both
are labelled with the traffic's model.  A program that lacks a sample
(the commit before the counter was added) reads None."""

import counters


def _delta(ctx, term):
    model = ctx.traffic["model"]
    if "phases" not in term:
        return counters.delta(ctx, term["metric"], model=model)
    parts = [counters.delta(ctx, term["metric"], model=model, phase=phase)
             for phase in term["phases"]]
    return None if None in parts else sum(parts)


def read(ctx, spec):
    num, den = _delta(ctx, spec["numerator"]), _delta(ctx, spec["denominator"])
    if num is None or not den:
        return None
    return num / den * spec["scale"]
