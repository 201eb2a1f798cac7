"""Roofline share of the INT8 GEMM kernel (int8_matmul_pallas): the larger
of counted INT8 ops / peak and counted bytes / HBM bandwidth, over the
device time the trace attributes to the kernel."""

from harness.work import roofline_s


def read(ctx):
    if ctx.trace is None or not ctx.peaks:
        return None
    t = ctx.trace.kernel_s.get("int8_matmul_pallas", 0.0)
    if t <= 0:
        return None
    least = roofline_s(ctx.work.gemm_ops, ctx.work.gemm_bytes,
                       ctx.peaks["int8_ops_per_s"],
                       ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
