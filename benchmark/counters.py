"""The program's counters, read in-process: the ``/metrics`` exposition
(``InferenceServer.metrics_text``) and the scheduler's page gauges.  The benchmark takes the numbers; the parsing
and the window deltas are its own."""

import re

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text):
    """``{(sample name, ((label, value), ...)): float}``."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


def snapshot(ctx):
    """Counters now: the exposition's samples."""
    return {"metrics": parse_exposition(ctx.core.metrics_text())}


def sample(snap, name, **labels):
    return snap["metrics"].get((name, tuple(sorted(labels.items()))))


def delta(ctx, name, **labels):
    """Window delta of one exposition sample, or None where it is absent."""
    a = sample(ctx.counters_t0, name, **labels)
    b = sample(ctx.counters_t1, name, **labels)
    return None if a is None or b is None else b - a


def gauges(ctx):
    """The page pool's occupancy of every scheduler-backed model."""
    out = {}
    for name, model in ctx.models.items():
        stats_fn = getattr(model, "scheduler_stats", None)
        stats = stats_fn() if callable(stats_fn) else None
        if stats and stats.get("pages_total"):
            out[name] = (stats["pages_free"], stats["pages_total"])
    return out
