"""The model's matmul and attention operations for the traffic served
(logits head included, at the same peak) over window x chips x the chip's
INT8 peak."""


def read(ctx):
    if ctx.work is None or not ctx.peaks:
        return None
    peak = ctx.peaks["int8_ops_per_s"] * ctx.n_chips
    return 100.0 * ctx.work.model_ops / (ctx.window.elapsed_s * peak)
