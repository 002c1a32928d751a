"""Share of the traced window in which no operation ran on the device."""


def read(ctx, spec):
    t = ctx.trace_data
    if t is None or not t.window_s or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
