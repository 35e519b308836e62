"""Share of the chip's bf16 peak that the step's useful operations use (%).

Useful operations are those of every conv pass the step needs, each at
the dense conv's multiply-adds (``bench.flops``); a pass the step does not
need (the input gradient of a layer whose input is the image) is not
counted.  Time is the traced window over the steps in it.
"""

from bench.flops import pass_flops


def reduce(ctx):
    flops = sum(pass_flops(conv) for _, conv, _, needed, _ in ctx["passes"]
                if needed)
    if flops == 0:
        return None
    step_s = ctx["view"].window_s / ctx["steps"]
    return 100.0 * flops / (step_s * ctx["peak"]["bf16_flops_per_s"])
