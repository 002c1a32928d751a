"""A percentile of one of the window's client-clock series (``series``:
of the cell's kind: gap_ms, ttft_ms, late_ms; ``q``)."""

import stats


def read(ctx, spec):
    return stats.percentile(ctx.series.get(spec["series"], []), spec["q"])
