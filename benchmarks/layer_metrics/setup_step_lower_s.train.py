"""Seconds the builder's step took from its jaxpr to an MLIR module:
JAX's ``jaxpr_to_mlir_module_duration``, every Pallas call site's
lowering to Mosaic included. Paid on every run, cache or none."""

from benchmarks import setup_reduce


def read(ctx):
    return setup_reduce.step_seconds("lower")
