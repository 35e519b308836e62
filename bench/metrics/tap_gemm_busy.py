"""Share of the device's busy time spent in the tap-GEMM kernels (%).

The rest is glue around them (phase split and unsplit, channel padding),
the loss and the optimizer.
"""

#: the kernels' names, as the trace prints them within the op names
#: (``jvp_tap_gemm_.2``, ``transpose_jvp_tap_wgrad__.4``).
KERNELS = ("tap_gemm", "tap_wgrad")


def reduce(ctx):
    view = ctx["view"]
    kernel_s = view.op_seconds(KERNELS)
    if kernel_s == 0:
        return None
    return 100.0 * kernel_s / view.busy_s
