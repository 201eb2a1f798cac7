"""Roofline share of the paged decode-attention kernel
(decode_attention_paged_pallas): live KV bytes read (INT8 K/V and their
f32 scales) / HBM bandwidth, over the device time the trace attributes to
the kernel."""


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t = ctx.trace.kernel_s.get("decode_attention_paged_pallas", 0.0)
    if t <= 0:
        return None
    return 100.0 * ctx.work.kv_bytes / ctx.peaks["hbm_bytes_per_s"] / t
