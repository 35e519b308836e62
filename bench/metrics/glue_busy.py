"""Share of the device's busy time spent in the glue around the tap-GEMM
kernels (%): the ops under the program's ``glue`` named scope inside a
conv pass (padding, phase split and unsplit, NCHW <-> NHWC, weight tap
gathers), by ``bench.scopes``.  Glue that XLA fuses into a pass's compute
counts as the pass, not here.
"""

from bench import scopes


def reduce(ctx):
    glue_s = scopes.seconds(ctx["view"], scopes.for_ctx(ctx))[scopes.GLUE]
    if glue_s == 0:
        return None
    return 100.0 * glue_s / ctx["view"].busy_s
