"""Set-up: process start to the first timed call (device init, weights,
calibration, PTQ, warm-up of every shape the cell serves)."""


def read(ctx):
    return ctx.setup_s
