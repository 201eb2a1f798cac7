"""Output tokens (winning hypotheses) of every job in the window over the
summed wall time of their serve() calls."""


def read(ctx):
    return ctx.window.n_tokens / ctx.window.serve_s
