"""Seconds JAX spent compiling or loading programs from its persistent
cache, summed over the run up to the window's end (JAX's own
``backend_compile_duration`` events). Moves ``setup_s``."""


def compute(ctx):
    return ctx.end["compile_s"] if ctx.end["compiles"] else None
