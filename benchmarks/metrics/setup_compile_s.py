"""``setup_compile_s``: seconds JAX spent in backend compilation before the
window opened (the sum of its ``backend_compile_duration`` events: cold
compiles, loads from the persistent cache and eager sub-second compiles
alike). Source: program counter. Moves ``setup_s``."""


def read(ctx):
    return float(ctx["counters"]["setup_compile_s"])
