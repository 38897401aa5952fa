"""Seconds jax spent getting executables during set-up: compiling on a cache
miss, loading on a hit."""


def read(run):
    return run.compile_setup["seconds"], run.compile_setup["compiles"]
