"""The program's own count of gradient bytes exchanged per step (its
``grad_sync_bytes_per_step`` gauge, ring model, from shapes)."""


def read(ctx):
    value = ctx["grad_sync_bytes_per_step"]
    return value / 1e6 if value else None
