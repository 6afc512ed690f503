"""Seconds of XLA backend compiles (persistent-cache loads included) in
set-up, from JAX's monitoring events."""


def read(ctx):
    return ctx.compile_setup_s
