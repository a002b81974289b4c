"""1 - the union of operation intervals over the traced window, on the
device that idles most."""


def read(ctx):
    red = ctx["reduced"]
    return 100.0 * red["idle_share_worst"] if red else None
