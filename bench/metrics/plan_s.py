"""Host seconds of the set-up spent resolving tile plans (s).

The program's own count (``repro.kernels.ops.plan_seconds``), reset when
the harness traces the step and read once the run is over: planning runs
only while jax traces the step, so all of it lies in the set-up.  A
program without the count reports nothing.
"""

import sys


def reduce(ctx):
    ops = sys.modules.get("repro.kernels.ops")
    read = getattr(ops, "plan_seconds", None)
    return None if read is None else read()
