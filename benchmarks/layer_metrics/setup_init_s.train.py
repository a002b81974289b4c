"""Host seconds of the program's ``hvd.init`` span: the whole of
``basics.init`` (the backend is up before it, from the benchmark's
``jax.devices()``)."""

from benchmarks import setup_reduce


def read(ctx):
    return setup_reduce.span_seconds("hvd.init")
