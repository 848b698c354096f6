"""Seconds JAX's ``backend_compile_duration`` events summed to during
set-up (compiling, or loading executables from the persistent cache)."""


def reduce(ctx):
    return ctx.setup_compile_s
