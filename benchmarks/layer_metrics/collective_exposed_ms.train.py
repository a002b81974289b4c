"""Per step, the time a collective runs on a device while no other
operation runs there (worst device of the traced steps)."""


def read(ctx):
    red = ctx["reduced"]
    if not red or not red["collective_s"] or not ctx["traced_steps"]:
        return None
    return 1e3 * red["collective_exposed_s"] / ctx["traced_steps"]
