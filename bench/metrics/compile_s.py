"""compile_s: seconds of XLA compiles and compile-cache reads during
set-up, summed from jax's own monitoring events."""


def read(ctx):
    return ctx.clock.seconds.get("setup", 0.0)
