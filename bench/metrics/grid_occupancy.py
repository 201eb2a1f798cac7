"""Occupied share of the decode grid the scheduler kept busy:
busy_slot_steps / (n_slots x decode_steps), summed over the window's jobs
(ServeResult counters)."""


def read(ctx):
    grid = sum(r.n_slots * r.decode_steps for r in ctx.results)
    return 100.0 * sum(r.busy_slot_steps for r in ctx.results) / grid \
        if grid else None
