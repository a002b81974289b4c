"""Of the device time under ``hvd.sync`` (and of any collective, by its HLO
kind), the share during which an operation outside it runs on the same
device: what of the exchange is hidden behind compute (the device that
hides least; a ``while`` around the exchange is not "an operation")."""

from benchmarks import scope_reduce


def read(ctx):
    red = scope_reduce.for_cell(ctx)
    if not red or red["sync_hidden_share"] is None:
        return None
    return 100.0 * red["sync_hidden_share"]
