"""Decode steps per device-to-host sync of the engine's host loop, over
every serve() call in the window (ServeResult counters)."""


def read(ctx):
    syncs = sum(r.host_syncs for r in ctx.results)
    return sum(r.decode_steps for r in ctx.results) / syncs if syncs else None
