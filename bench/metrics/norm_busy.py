"""Share of the device's busy time spent in the network's batch norms (%):
the ops under the program's ``batch_norm`` named scope (each BN with the
residual add and ReLU after it, forward and backward), by ``bench.norm``.
A program without the scope reports nothing.
"""

from bench import norm, scopes


def reduce(ctx):
    norm_s = norm.seconds(ctx["view"], scopes.for_ctx(ctx))
    if norm_s == 0:
        return None
    return 100.0 * norm_s / ctx["view"].busy_s
