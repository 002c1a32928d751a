"""A step's or a kernel's share of the chip: ``roofline`` (the larger of
FLOPs/peak and bytes/bandwidth over its device time) or ``mfu`` (useful
FLOPs over device time x the bf16 peak).  The work is what the algorithm
needs (``roofline.py``), the time is the device trace's."""

import devicework
import roofline


def read(ctx, spec):
    if ctx.trace_data is None or ctx.dry_run:
        return None
    work = devicework.work(ctx, spec["scope"])
    if work is None:
        return None
    flops, nbytes, seconds = work
    chip = roofline.chip(ctx.device_info["kind"])
    if spec["share"] == "mfu":
        return roofline.mfu(flops, seconds, chip)
    share, bound = roofline.roofline_share(flops, nbytes, seconds, chip)
    ctx.log("{}: {:.1f}% of its roofline, {}-bound".format(
        spec["name"], share, bound))
    return share
