"""Seconds the builder's step took from Python to a jaxpr in its build:
JAX's ``jaxpr_trace_duration`` of the outermost function, every jitted
function it calls inside (the flash and routed-expert wrappers) held in
it."""

from benchmarks import setup_reduce


def read(ctx):
    return setup_reduce.step_seconds("trace")
