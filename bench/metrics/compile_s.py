"""Seconds of ``backend_compile_duration`` events during set-up: compiles
and persistent-cache loads of the bucket programs."""


def read(run: dict):
    return run["setup_compile_s"]
