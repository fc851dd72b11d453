"""Seconds from the harness's first line to the window's first dispatch:
JAX and TPU start-up, the program's host set-up, loading or compiling its
programs, and the warm-up of the cell's shapes."""


def read(run):
    return run.setup_s
