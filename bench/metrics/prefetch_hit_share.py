"""Share of the experts a layer needed that a prefetch had already brought:
prefetch hits over hits plus demand misses, inside the window."""


def read(ctx):
    hits, misses = ctx.delta("prefetch_hits"), ctx.delta("demand_misses")
    return 100.0 * hits / (hits + misses) if hits + misses else None
