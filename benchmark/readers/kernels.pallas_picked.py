"""How many of the kernel gate's decisions picked a Pallas kernel."""


def read(ctx):
    return ctx["counters"]["pallas_picked"]
