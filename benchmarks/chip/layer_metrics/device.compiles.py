"""Device layer: backend compiles (jax's compile monitoring events,
persistent-cache loads included) inside the measured window."""


def read(ctx):
    return ctx["compiles"]
