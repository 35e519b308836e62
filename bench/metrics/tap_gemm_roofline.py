"""Share of their roofline that the tap-GEMM kernels reach (%).

The least time of each needed pass that dispatched to ``pallas`` is the
larger of its operations at the bf16 peak and its compact bytes at HBM
bandwidth, counted from the conv's own geometry (``bench.flops``), so
padding to tiles or lanes shows as lost share.  It is divided by the
device time of the ops whose name holds ``tap_gemm`` or ``tap_wgrad``
(the program's kernel names) in the window.
"""

from bench.flops import least_seconds

#: the kernels' names, as the trace prints them within the op names
#: (``jvp_tap_gemm_.2``, ``transpose_jvp_tap_wgrad__.4``).
KERNELS = ("tap_gemm", "tap_wgrad")


def reduce(ctx):
    least = sum(least_seconds(conv, ctx["peak"])
                for _, conv, _, needed, engine in ctx["passes"]
                if needed and engine == "pallas")
    kernel_s = ctx["view"].op_seconds(KERNELS)
    if least == 0 or kernel_s == 0:
        return None
    return 100.0 * least * ctx["steps"] / kernel_s
