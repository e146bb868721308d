"""compile_s (program counter: jax.monitoring): seconds spent during
set-up building executables (backend compiles) or loading them from the
persistent compilation cache."""


def read(ctx):
    return ctx["clock"].total("setup")[0]
