"""Share of the GAT kernel pair's roofline, in percent: the least time
the chip needs for the GAT work of the traced generations (per call the
larger of FLOPs over the bf16 peak and bytes over HBM bandwidth, at the
real node count with a dense f32 adjacency; flops.gat_kernel_calls
counts the calls at the shapes whose backend is the Pallas pair) over
the device time of the Pallas forward and backward kernels.  The trace
names those custom calls after the jitted ``gat_mp`` and the transforms
around it (``vmap_vmap_jit_gat_mp___``, ``jvp_...``, ``transpose_...``).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import flops  # noqa: E402

KERNELS = r'^%\S*gat_mp\S* = .*custom_call_target="tpu_custom_call"'


def read(ctx):
    if ctx.peaks is None or not ctx.gat_calls:
        return None
    kernel_s = ctx.reduced.op_s(KERNELS)
    if kernel_s <= 0:
        return None
    least, _, _ = flops.kernel_least_time_s(
        ctx.gat_calls, ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.generations / kernel_s
