"""Seconds the backend took to compile the builder's step, or to load it
from the persistent compilation cache: JAX's
``backend_compile_duration``."""

from benchmarks import setup_reduce


def read(ctx):
    return setup_reduce.step_seconds("compile")
