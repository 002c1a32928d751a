"""Peak share of the page pool in use over the window: 1 - free/total of
the traffic's model, sampled a few times a second."""


def read(ctx, spec):
    shares = [1.0 - free / total
              for s in ctx.samples
              for free, total in [s.get(ctx.traffic["model"], (None, 0))]
              if total]
    return 100.0 * max(shares) if shares else None
