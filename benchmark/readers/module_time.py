"""Device time of one executable (``scope``) in the traced window:
``p50_ms`` of its runs, its ``busy_share`` of the window in %, or
``ms_per_ktok`` of the prompt tokens it prefilled."""

import devicework
import stats


def read(ctx, spec):
    t = ctx.trace_data
    if t is None:
        return None
    runs = devicework.runs_of(ctx, spec["scope"])
    if spec["stat"] == "busy_share" and t.window_s:
        # a traced window in which it never ran is a reading, not a gap
        return 100.0 * sum(r.dur for r in runs) / t.window_s
    if not runs:
        return None
    seconds = sum(r.dur for r in runs)
    if spec["stat"] == "p50_ms":
        return stats.percentile([r.dur * 1e3 for r in runs], 50)
    if spec["stat"] == "ms_per_ktok":
        tokens = sum(devicework.prompt_tokens(ctx, spec["scope"], runs))
        return seconds * 1e3 / (tokens / 1000.0) if tokens else None
    raise ValueError("unknown stat {!r}".format(spec["stat"]))
