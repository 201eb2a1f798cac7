"""95th percentile (linear interpolation) of the wall time of a serve()
call, over every call in the window."""

import numpy as np


def read(ctx):
    return float(np.percentile([c.wall_s for c in ctx.window.calls], 95)
                 * 1e3)
