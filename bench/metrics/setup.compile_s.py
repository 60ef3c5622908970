"""Seconds of backend compiles, or loads from the persistent compile cache,
during set-up (``/jax/core/compile/backend_compile_duration`` events)."""


def read(run):
    return run.compile_s if run.compile_s > 0 else None
