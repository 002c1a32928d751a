"""The work the device did in the traced window, for the shares of
roofline and peak: which executable or kernel a scope is, how long it
ran (the trace), and what the algorithm needed (``roofline.py``) for the
rows and prompts served there.

Nothing of a model family is written here: each ``models/<builder>.py``
of the configuration's repository brings its ``TRACE_LABELS``, its
``SCOPES`` and its ``work``, and a scope is looked up among them.
"""


def labels(ctx):
    """The label rules of every model in the repository, for ``trace.load``."""
    return tuple(rule for b in ctx.builders.values()
                 for rule in getattr(b, "TRACE_LABELS", ()))


def owner(ctx, scope):
    """``(entry, builder, spec)`` of the repository's model whose builder
    names ``scope``, or None where none does."""
    for entry in ctx.config["repository"]:
        builder = ctx.builders[entry["name"]]
        spec = getattr(builder, "SCOPES", {}).get(scope)
        if spec is not None:
            return entry, builder, spec
    return None


def runs_of(ctx, scope):
    """The runs, in the traced window, of the executable ``scope`` names."""
    found = owner(ctx, scope)
    if found is None or ctx.trace_data is None:
        return []
    return ctx.trace_data.runs_of(found[2]["label"])


def prompt_tokens(ctx, scope, runs):
    """The prompt tokens each run of a prefill scope took in."""
    return owner(ctx, scope)[1].prompt_tokens(runs)


def work(ctx, scope):
    """``(flops, bytes, device seconds)`` of a scope over the traced
    window, or None where the trace holds nothing of it."""
    found, runs = owner(ctx, scope), runs_of(ctx, scope)
    if not runs or ctx.trace_data.interval() is None:
        return None
    entry, builder, spec = found
    seconds = (sum(r.op_seconds(spec["op"]) for r in runs) if "op" in spec
               else sum(r.dur for r in runs))
    if not seconds:
        return None
    needed = builder.work(ctx, entry, scope, runs)
    return None if needed is None else (*needed, seconds)
