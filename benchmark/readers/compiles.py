"""Compiles and compile-cache reads that fell inside the window (jax's
monitoring events): there should be none."""


def read(ctx, spec):
    return ctx.compiles_in_window
