"""Router balance: the least-loaded replica's output tokens over the
most-loaded replica's, summed over the window's jobs."""


def read(ctx):
    per = ctx.replica_tokens
    if len(per) < 2 or max(per) == 0:
        return None
    return 100.0 * min(per) / max(per)
