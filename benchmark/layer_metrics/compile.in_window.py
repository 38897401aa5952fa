"""Executables jax got inside the measured window. Must be 0."""


def read(run):
    return run.compile_window["compiles"] - run.compile_setup["compiles"]
