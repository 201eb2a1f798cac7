"""Share of the traced window in which no operation ran on the device:
1 - union of device op intervals / window, averaged over the chips used."""


def read(ctx):
    if ctx.trace is None:
        return None
    share = ctx.trace.idle_share()
    return None if share is None else 100.0 * share
