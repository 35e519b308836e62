"""Share of its roofline that every conv's input-gradient pass reaches (%).

The least time of each needed conv's input_grad pass, on whatever
engine it ran (``bench.flops.least_seconds``), times the steps, over the
device time of the ops under the program's ``conv_input_grad`` and
``conv_input_grad_T`` named scopes in the window (``bench.scopes``), glue
included.
"""

from bench.scopes import pass_roofline


def reduce(ctx):
    return pass_roofline(ctx, "input_grad")
