"""Executables loaded while the window ran (JAX's
``backend_compile_duration`` events); set-up warms every shape, so 0."""


def read(run: dict):
    return run["window_compiles"]
