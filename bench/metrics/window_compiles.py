"""window_compiles (program counter: jax.monitoring): executables built
or loaded from the persistent cache inside the measured window; it
should read 0."""


def read(ctx):
    return ctx["clock"].total("window")[1]
