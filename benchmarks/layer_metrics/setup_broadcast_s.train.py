"""Host seconds of the program's ``hvd.broadcast_parameters`` span: the
whole tree through ``optim.broadcast_parameters``, the eager broadcast
programs' compiles and dispatch included (not their completion)."""

from benchmarks import setup_reduce


def read(ctx):
    return setup_reduce.span_seconds("hvd.broadcast_parameters")
